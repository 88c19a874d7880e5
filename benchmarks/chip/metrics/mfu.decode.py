"""Decode steps' share of the chip's roofline over the traced window, in %.

Each decode step's least time is the larger of its FLOPs over the bf16
peak and its least bytes (the weights once, the KV rows its requests
attend) over HBM bandwidth (the model code's ``decode_step``, ``peaks``).
The mean least time of the host-recorded decode ticks inside the traced
window, times the decode dispatches the trace counts there, over the
window."""
import counts
from peaks import peaks_for
from readings import ticks_between


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    n = len(run.trace.dispatches("decode"))
    steps = [t for t in ticks_between(run, *run.loop.trace_window)
             if t.decode_rows]
    if not n or not steps:
        return None
    pk = peaks_for(run.device_kind)
    step = run.code.decode_step
    least = [counts.roofline_s(*step(run.model, t.decode_rows, t.decode_ctx),
                               pk) for t in steps]
    return 100.0 * n * (sum(least) / len(least)) / run.trace.window_s
