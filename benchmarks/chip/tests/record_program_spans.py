#!/usr/bin/env python3
"""Record ``testdata/program_spans.xplane.pb``: a small trace with the
engine's own ``serve.*`` spans beside the device's programs.

    python3 benchmarks/chip/tests/record_program_spans.py <out.xplane.pb>

A reduced qwen2.5-3b engine (16-row chunks, 4 slots) serves three
requests whose prompts take one to three chunks, so that the trace holds
fresh and resumed prefill dispatches and decode ticks, traced with the
harness's profiler options and its host spans (``window``, ``gen.submit``,
``engine.tick``, ``read``, ``gen.wait``), after one untraced pass that
compiles the sampling programs ``warmup()`` leaves out.  Run it on the
chip: the device plane it records is what ``test_program_trace.py``
reads.
"""
from __future__ import annotations

import pathlib
import shutil
import sys
import tempfile
import time

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

PROMPTS = ((40, 0), (10, 0), (24, 2))       # (length, ticks before submit)


def serve(eng, span, rid0: int) -> int:
    """Serve PROMPTS as the harness would; returns the ticks taken."""
    from repro.serve import Request
    tick = 0
    while tick < 3 or eng.sched.has_work():
        due = [(i, n) for i, (n, at) in enumerate(PROMPTS) if at == tick]
        if due:
            with span("gen.submit"):
                for i, n in due:
                    eng.submit(Request(rid0 + i, [1 + (7 * i + k) % 500
                                                  for k in range(n)]))
        with span("engine.tick"):
            eng.tick()
        with span("read"):
            eng.pages_in_use()
        if tick == 1:
            with span("gen.wait"):
                time.sleep(0.002)
        tick += 1
    return tick


def main(out: str) -> None:
    import jax
    from jax.profiler import TraceAnnotation as span

    import serveloop
    import trace_reduce
    from repro.configs import get_config, reduce_config
    from repro.models import init_params
    from repro.serve import ServeConfig, ServingEngine

    cfg = reduce_config(get_config("qwen2.5-3b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, ServeConfig(
        max_batch=4, max_prompt=16, max_new_tokens=4, max_seq=48,
        page_size=8))
    eng.warmup()
    serve(eng, span, 0)         # compiles what warmup() does not: sampling
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=serveloop._trace_options())
    with span("window"):
        ticks = serve(eng, span, len(PROMPTS))
    jax.profiler.stop_trace()
    shutil.copy(trace_reduce.find_xplane(tmp), out)
    shutil.rmtree(tmp)
    print(f"{out}: {pathlib.Path(out).stat().st_size} bytes, {ticks} ticks")


if __name__ == "__main__":
    main(sys.argv[1])
