"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

    configs/<config>.json     model sizes, serving settings, provenance
    traffic/<traffic>.json    a mix for the general generator (loadgen.py):
                              open or closed loop, a shaped rate, shared
                              prompts, sessions of several turns
    checks/<cell>.json        the limits that decide ``correct``
    metrics/<metric>.py       one reader per metric: ``read(run)`` returns a
                              number, or None where it finds nothing to read

Adding a configuration, a mix or a metric is adding files here and
entries in ``BENCHMARK.json``; no file that exists needs an edit.
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

    def reader(self, metric: str):
        path = self.here / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            "metric_" + metric.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
