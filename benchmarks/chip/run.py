#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 benchmarks/chip/run.py --workload qwen2.5-3b.chat --seed 7 \
        --seconds 10 --trace 0

The cell (a ``workloads`` entry of ``BENCHMARK.json``) names a
configuration and a traffic mix; both, the limits that decide
``correct`` and every metric's reader are found by name (``registry``).
Set-up draws the weights on the device from ``--seed``, builds one
``ServingEngine`` and compiles its prefill and decode programs
(``warmup()``), with JAX's compile cache at the checkout's fixed
``.jax_cache``.  The traffic then runs through ``submit()`` / ``tick()``:
a warm-up that is not counted, the measured window of ``--seconds``, and,
where the mix follows its window's requests to their last token, a tail.
Nothing should compile in the window; the count is printed.  With
``--trace 1`` the last TRACE_SECONDS of the window are traced and the
per-layer metrics reported instead of the end-to-end ones.

Once the loop has ended and the peak memory is read, the engine is freed
and the plain float32 reference of the configuration's model code
(``code/<code>.py``) checks a sample of the served requests drawn from
the seed.  The last stdout line is the result JSON; the numbers
compared, each beside its limit, are the last stderr lines and the
result's last key.  Without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent.parent / "src"))

import numpy as np  # noqa: E402

import loadgen  # noqa: E402
import registry  # noqa: E402

TRACE_SECONDS = 10.0
TRACE_DIR = HERE / "runs" / "trace"


def log(msg: str) -> None:
    print(msg, flush=True)


def enable_cache() -> str:
    """JAX's persistent compile cache at the checkout's fixed directory
    (or JAX_COMPILATION_CACHE_DIR), keeping every program, however quick
    to compile, so that later runs of a cell compile nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def check_device(chips: int):
    """The devices, or exit: a TPU is required and there is no fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"run.py: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"run.py: the cell needs {chips} chips, found "
                         f"{len(devs)}")
    return devs


@dataclasses.dataclass
class Run:
    """What a metric reader gets."""
    loop: object                    # serveloop.Loop
    setup_s: float
    serve: object                   # the ServeConfig
    num_pages: int
    model: dict                     # the configuration's model block
    code: object                    # its model code (code/<code>.py)
    device_kind: str
    trace: object = None            # trace_reduce.Trace


def build(code, conf: dict, mix: dict):
    """(ArchConfig, ServeConfig) of a configuration under a mix."""
    from repro.serve import ServeConfig
    arch = code.arch(conf)
    out = int(mix["output_tokens"])
    sc = ServeConfig(**conf["serve"], max_new_tokens=out,
                     max_seq=loadgen.max_prompt_tokens(mix) + out)
    return arch, sc


def sample(loop, mix: dict, seed: int):
    """The requests the reference checks: the longest prompt among the
    finished ones, then others drawn from the seed."""
    pool = [r for r in loop.requests if r.done and
            (r.in_window or mix["follow"] != "window_arrivals")]
    if not pool:
        return []
    first = max(pool, key=lambda r: (len(r.prompt), -r.idx))
    rest = [r for r in pool if r is not first]
    rng = np.random.default_rng([int(seed), 3])
    k = min(int(mix["check_sample"]) - 1, len(rest))
    return [first] + [rest[i] for i in sorted(rng.choice(len(rest), k,
                                                         replace=False))]


def verify(code, params, m: dict, loop, mix: dict, limits: dict, seed: int,
           length: int) -> dict:
    """The numbers that decide ``correct``, each {"value", "limit"}.  A
    request that failed or never finished (``tally``) fails the run: its
    latency would otherwise count only until the run gave up on it."""
    out = int(mix["output_tokens"])
    finished = [r for r in loop.requests if r.done]
    chosen = sample(loop, mix, seed)
    worst = 0.0
    for r in chosen:
        g = code.served_gaps(params, m, r.prompt, r.tokens, length)
        worst = max(worst, float(g.max()))
    if not math.isfinite(worst):
        worst = 1e30            # a served id outside the vocabulary
    return {
        "max_logit_gap": {"value": worst,
                          "limit": limits["max_logit_gap"]["limit"]},
        "short_outputs": {"value": sum(len(r.tokens) != out
                                       for r in finished), "limit": 0},
        "unchecked": {"value": int(mix["check_sample"]) - len(chosen),
                      "limit": 0},
        "failed": {"value": tally(loop, mix)[1], "limit": 0},
    }


def tally(loop, mix: dict):
    """(attempted, failed).  Where the mix follows its window's requests:
    every request due in the window, and those not finished.  Otherwise:
    the requests that ended (finished or failed) inside the window."""
    ws, we = loop.window
    if mix["follow"] == "window_arrivals":
        due = [r for r in loop.requests if r.in_window]
        return len(due), sum(not r.done for r in due)
    ended = [r for r in loop.requests if r.req is not None and r.req.done
             and ws <= (r.submit_t if r.failed else r.last_t) < we]
    return len(ended), sum(r.failed for r in ended)


def summary(loop) -> list:
    ws, we = loop.window
    due = [r for r in loop.requests if r.in_window]
    subs = [r for r in loop.requests if r.submit_t is not None]
    late = [r.submit_t - r.due for r in subs] or [0.0]
    win = [t for t in loop.ticks if ws <= t.t1 < we]
    low = loop.lowerings
    return [
        f"window {we - ws:.1f} s: {len(due)} requests due in it (the latency "
        f"sample), {sum(r.done for r in due)} of them finished; "
        f"{len(subs)} submitted and {sum(r.done for r in loop.requests)} "
        f"finished in the whole run; loop ended at {loop.end:.2f} s",
        f"window ticks {len(win)}, of which decode {sum(1 for t in win if t.active)}"
        f", tokens {sum(t.tokens for t in win)}, prompt rows prefilled "
        f"{sum(t.prefill_rows for t in win)}",
        f"programs lowered (of them read from the cache): warm-up "
        f"{low.get('warmup.lowered', 0)} ({low.get('warmup.cached', 0)}), "
        f"window {low.get('window.lowered', 0)} "
        f"({low.get('window.cached', 0)})",
        f"generator lateness (submit - due): mean {1e3 * np.mean(late):.2f} ms,"
        f" max {1e3 * max(late):.2f} ms over {len(subs)} submissions",
    ]


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = registry.Bench()
    cell = bench.cell(args.workload)
    devs = check_device(int(cell["chips"]))
    import jax
    import serveloop
    import trace_reduce
    import weightgen
    from repro.models import abstract_params
    from repro.serve import ServingEngine

    cache = enable_cache()
    compiles = serveloop.watch_compiles()
    conf = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    limits = bench.checks(cell["name"])
    m = conf["model"]
    code = bench.code(cell["config"])
    arch, sc = build(code, conf, mix)
    sched = loadgen.schedule(mix, args.seed, args.seconds, m["vocab_size"])

    t = time.perf_counter()
    params = weightgen.draw(code.leaf_table(m), args.seed)
    weightgen.check_layout(params, abstract_params(arch))
    t_weights = time.perf_counter() - t
    t = time.perf_counter()
    eng = ServingEngine(arch, params, sc)
    eng.warmup()
    t_engine = time.perf_counter() - t
    num_pages = eng.num_pages
    setup_s = time.perf_counter() - T_START
    log(f"setup: {setup_s:.3f} s = weights {t_weights:.3f} s + engine "
        f"build, compile and warm-up {t_engine:.3f} s + the rest; compile "
        f"cache {cache}; programs lowered {compiles.counts['setup.lowered']}, "
        f"of them read from the cache {compiles.counts['setup.cached']}")

    trace_dir = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = TRACE_DIR
    loop = serveloop.drive(eng, sched, mix, trace_dir,
                           min(TRACE_SECONDS, args.seconds))
    stats = devs[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    del eng
    gc.collect()

    t = time.perf_counter()
    checks = verify(code, params, m, loop, mix, limits, args.seed,
                    sc.slot_rows)
    t_ref = time.perf_counter() - t
    trace = None
    if args.trace:
        trace = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    run = Run(loop, setup_s, sc, num_pages, m, code, devs[0].device_kind,
              trace)

    metrics = {}
    for md in bench.metrics_for(cell["name"], bool(args.trace)):
        v = bench.reader(md["name"])(run)
        if v is not None:
            metrics[md["name"]] = {"value": v, "unit": md["unit"]}

    attempted, n_failed = tally(loop, mix)
    for line in summary(loop):
        log(line)
    log(f"peak device memory: {peak} bytes; reference check {t_ref:.2f} s")
    if trace is not None:
        log(f"trace: window {trace.window_s:.3f} s, busy {trace.busy_s:.3f} s, "
            f"prefill dispatches {len(trace.dispatches('prefill'))}, decode "
            f"dispatches {len(trace.dispatches('decode'))}")

    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": passed(checks), "attempted": attempted,
              "failed": n_failed, "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace.top_ops(),
                               "idle_gaps": trace.idle_by_host()}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
