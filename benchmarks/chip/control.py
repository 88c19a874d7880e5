#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's and the control's.

    python3 benchmarks/chip/control.py --workload qwen2.5-3b.chat \
        --seconds 10 --seeds 101 102 103

For each seed, in one process: draw the weights, serve the cell's traffic
through a fresh engine for a short window at the cell's own load, draw
the check sample as ``run.py`` does, and read two numbers over the same
sample: the widest gap of the served tokens below the float32
reference's best (the program's reading), and the widest gap of the
tokens the fp8 control puts first (the control's reading).  A limit lies
above the largest program reading and below the smallest control
reading (``checks/<cell>.json``).  The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent.parent / "src"))

import loadgen  # noqa: E402
import registry  # noqa: E402


def readings(cell_name: str, seed: int, seconds: float, bench=None) -> dict:
    import run
    import serveloop
    import weightgen
    from repro.serve import ServingEngine
    bench = bench or registry.Bench()
    cell = bench.cell(cell_name)
    conf, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    m = conf["model"]
    code = bench.code(cell["config"])
    arch, sc = run.build(code, conf, mix)
    params = weightgen.draw(code.leaf_table(m), seed)
    eng = ServingEngine(arch, params, sc)
    eng.warmup()
    loop = serveloop.drive(eng, loadgen.schedule(mix, seed, seconds,
                                                 m["vocab_size"]), mix)
    del eng
    gc.collect()
    out = {"seed": seed, "program": 0.0, "control": 0.0, "tokens": 0}
    for r in run.sample(loop, mix, seed):
        for key, ctl in (("program", False), ("control", True)):
            g = code.served_gaps(params, m, r.prompt, r.tokens,
                                 sc.slot_rows, control=ctl)
            out[key] = max(out[key], float(g.max()))
        out["tokens"] += len(r.tokens)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import run
    bench = registry.Bench()
    run.check_device(int(bench.cell(args.workload)["chips"]))
    run.enable_cache()
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed, args.seconds, bench)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
