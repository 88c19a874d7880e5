"""Prompt rows prefilled in the traced window over the rows the prefill
dispatches of that window computed (dispatches x max_batch x max_prompt:
every dispatch runs at its full padded shape), in %."""
from readings import ticks_between


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    n = len(run.trace.dispatches("prefill"))
    if not n:
        return None
    rows = sum(t.prefill_rows for t in ticks_between(run, *run.loop.trace_window))
    return 100.0 * rows / (n * run.serve.max_batch * run.serve.max_prompt)
