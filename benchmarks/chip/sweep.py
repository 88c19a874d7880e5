#!/usr/bin/env python3
"""Find the rate a cell's engine sustains: one sweep over arrival rates.

    python3 benchmarks/chip/sweep.py --workload qwen2.5-3b.chat \
        --rates 1.0 1.5 2.0 2.5 --seconds 40 --seed 3

One process, a fresh engine for each rate: the cell's mix runs with that
rate (warm-up, then the window, not followed further), and one line
reports what came in and out of the window: requests due, finished,
still in the system at the window's close (the backlog), completions
and tokens per second, and for the first and the second half of the
window the mean number of requests in the system, the mean wait from
due time to admission, and TTFT by nearest rank over the requests due
in it.  Below the knee the number in the system and the wait hold
steady from one half to the next; above it they grow.  The TTFT of a
half follows where the longest prompts fall and is no test of its own.
A cell's traffic file keeps the rate that was chosen, as a number; the
benchmark never searches for one.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent.parent / "src"))

import loadgen  # noqa: E402
import registry  # noqa: E402
from readings import nearest_rank  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import run
    bench = registry.Bench()
    cell = bench.cell(args.workload)
    run.check_device(int(cell["chips"]))
    import serveloop
    import weightgen
    from repro.serve import ServingEngine
    run.enable_cache()
    conf, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    code = bench.code(cell["config"])
    arch, sc = run.build(code, conf, mix)
    params = weightgen.draw(code.leaf_table(conf["model"]), args.seed)
    for rate in args.rates:
        eng = ServingEngine(arch, params, sc)     # a fresh, empty engine
        eng.warmup()
        m = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate),
                 follow="none")
        sched = loadgen.schedule(m, args.seed, args.seconds,
                                 conf["model"]["vocab_size"])
        t = time.perf_counter()
        loop = serveloop.drive(eng, sched, m)
        ws, we = loop.window
        due = [r for r in loop.requests if r.in_window]
        half = ws + (we - ws) / 2
        ttft = lambda rs: 1e3 * nearest_rank(  # noqa: E731
            [(r.first_t if r.first_t is not None else we) - r.due
             for r in rs], 90) if rs else None
        done_in = [r for r in loop.requests
                   if r.done and r.last_t is not None and ws <= r.last_t < we]
        toks = sum(t.tokens for t in loop.ticks if ws <= t.t1 < we)
        backlog = sum(1 for r in loop.requests
                      if r.submit_t is not None and not r.done)

        def in_system(t0, t1):      # time-averaged requests submitted, not done
            return sum(max(0.0, min(t1, r.last_t if r.done else we)
                           - max(t0, r.submit_t))
                       for r in loop.requests if r.submit_t is not None
                       ) / (t1 - t0)

        def wait_ms(rs):
            return 1e3 * sum((r.admit_t if r.admit_t is not None else we)
                             - r.due for r in rs) / len(rs) if rs else None
        del eng
        print(json.dumps({
            "rate": rate, "due": len(due), "finished_in_window": len(done_in),
            "backlog_at_close": backlog,
            "completions_per_s": len(done_in) / (we - ws),
            "out_tokens_per_s": toks / (we - ws),
            "in_system_first_half": in_system(ws, half),
            "in_system_second_half": in_system(half, we),
            "queue_wait_ms_first_half": wait_ms(
                [r for r in due if r.due < half]),
            "queue_wait_ms_second_half": wait_ms(
                [r for r in due if r.due >= half]),
            "ttft_p90_ms_first_half": ttft([r for r in due if r.due < half]),
            "ttft_p90_ms_second_half": ttft([r for r in due if r.due >= half]),
            "wall_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
