"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

    configs/<config>.json     model sizes, serving settings, provenance
    traffic/<traffic>.json    a mix for the general generator (loadgen.py):
                              open or closed loop, a shaped rate, shared
                              prompts, sessions of several turns
    checks/<cell>.json        the limits that decide ``correct``
    metrics/<metric>.py       one reader per metric: ``read(run)`` returns a
                              number, or None where it finds nothing to read
    code/<code>.py            everything that depends on the model's
                              architecture, named by a configuration's
                              ``"code"`` key:
        arch(conf)            the program's ArchConfig, checked against the
                              file's ``model`` block
        leaf_table(m)         weight name -> (shape, dtype, kind, scale) in
                              the engine's layout (``weightgen``)
        served_gaps(params, m, prompt, served, length, control=False)
                              the float32 reference's gap at each served
                              token, or at the control's pick
        decode_step(m, rows, ctx_rows)
                              (FLOPs, least bytes) of one decode step

Adding a configuration, a mix, a metric or a model's code is adding files
here and entries in ``BENCHMARK.json``; no file that exists needs an edit.
Outside ``code/`` no file reads a key of the ``model`` block but
``vocab_size``, which the traffic generator needs.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent.parent


class Bench:
    def __init__(self):
        self.root, self.here = ROOT, HERE
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.loaded = {}        # path -> module: each file is loaded once

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.here / "traffic" / f"{name}.json").read_text())

    def checks(self, cell: str) -> dict:
        return json.loads((self.here / "checks" / f"{cell}.json").read_text())

    def metrics_for(self, cell: str, trace: bool) -> list:
        """The end-to-end metrics a cell reports (trace off), or its
        per-layer metrics (trace on), as BENCHMARK.json entries."""
        e2e = [m for m in self.spec["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if not trace:
            return e2e
        mine = {m["name"] for m in e2e}
        # without a ``workloads`` key a metric belongs to every cell that
        # reports the end-to-end metric it moves.
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def _load(self, path: pathlib.Path, prefix: str):
        if path not in self.loaded:
            spec = importlib.util.spec_from_file_location(
                prefix + path.stem.replace(".", "_").replace("-", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self.loaded[path] = mod
        return self.loaded[path]

    def reader(self, metric: str):
        return self._load(self.here / "metrics" / f"{metric}.py",
                          "metric_").read

    def code(self, config: str):
        """The model code a configuration names (``code/<code>.py``)."""
        name = self.config(config)["code"]
        return self._load(self.here / "code" / f"{name}.py", "code_")
