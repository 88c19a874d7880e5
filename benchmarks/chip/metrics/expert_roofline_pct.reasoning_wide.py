"""The held experts' grouped matmul: its share of its roofline over the
traced window, in %.

Device time: the window's operations on the first device named as the
kernel (``KERNEL``, megablox ``gmm``), which reads each layer's expert
weights in place, so that its time holds all their traffic.  Least time:
for each step kind, the mean least time of the host-recorded ticks of
that kind inside the traced window, times the dispatches of that kind
the trace counts.  A tick's least time is the model code's
``expert_dispatch`` at the picks its rows (decode rows, or prompt rows
prefilled) route to the held experts and the held experts they reach,
as uniform routing gives them on average (``expected_routing``)."""
import re

import counts
from peaks import peaks_for
from readings import ticks_between

KERNEL = re.compile(r"^%?gmm(\.\d+)?$")
ROWS = {"decode": lambda t: t.decode_rows, "prefill": lambda t: t.prefill_rows}


def kernel_s(trace) -> float:
    dev = trace.devices[min(trace.devices)]
    lo, hi = trace.window
    return 1e-9 * sum(max(0.0, min(e, hi) - max(s, lo))
                      for name, s, e in dev.ops
                      if KERNEL.match(name.split(" ", 1)[0]))


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    busy = kernel_s(run.trace)
    ticks = ticks_between(run, *run.loop.trace_window)
    if not busy or not ticks:
        return None
    pk = peaks_for(run.device_kind)
    code, m = run.code, run.model
    least = 0.0
    for kind, rows_of in ROWS.items():
        rows = [rows_of(t) for t in ticks if rows_of(t)]
        n = len(run.trace.dispatches(kind))
        if rows and n:
            least += n * sum(counts.roofline_s(*code.expert_dispatch(
                m, *code.expected_routing(m, r)), pk) for r in rows) / len(rows)
    return 100.0 * least / busy
