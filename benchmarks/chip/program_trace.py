#!/usr/bin/env python3
"""The serving engine's own spans in a profiler trace, beside the device.

    python3 benchmarks/chip/program_trace.py <trace dir or .xplane.pb>
    python3 benchmarks/chip/program_trace.py --workload <cell> --seed <n> \
        --seconds <s>

The engine (``repro.serve.engine``) writes host spans named ``serve.*``
on the profiler's clock, nested as its calls are: ``serve.tick`` holds
``serve.admit`` (one ``serve.place`` per request placed), ``serve.prefill``
(``serve.iotlb``, ``serve.sync``), ``serve.grow`` and ``serve.decode``
(``serve.sync``, ``serve.emit``, ``serve.finish``).  ``serve.submit``,
``serve.place`` and ``serve.finish`` carry the request's ``rid``;
``serve.prefill`` carries ``rows`` (prompt rows), ``padded_rows`` (the
rows the dispatch computes) and ``resumed``.  ``reduce`` reads a
trace as ``trace_reduce.reduce`` does and keeps these spans too, as
``Trace.program``: (name, start, end, args) in start order, the args
being the span's int event stats.  Everything ``trace_reduce`` reads is
read as it reads it.

The first form prints the readings of a trace that is there.  The second
runs one cell as ``run.py --trace 1`` does, with its trace reduced here,
and prints one more line after ``run.py``'s result: these readings.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import re
import sys
from typing import Dict, List, Optional, Tuple

import trace_reduce

PREFIX = "serve."
PREFILL = re.compile(r"^jit_prefill(\W|$)")
_harness_reduce = trace_reduce.reduce

Span = Tuple[str, float, float, dict]


def deepest(spans: List[Span]) -> List[Tuple[float, float, str]]:
    """Disjoint (start, end, name) pieces of the time ``spans`` cover,
    each named by the deepest span over it; spans nest (one thread)."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []         # (name, end), innermost last
    t = 0.0

    def upto(b: float) -> None:
        nonlocal t
        if stack and b > t:
            out.append((t, b, stack[-1][0]))
        t = b

    for name, s, e, _ in sorted(spans, key=lambda p: (p[1], -p[2])):
        while stack and stack[-1][1] <= s:
            upto(stack[-1][1])
            stack.pop()
        upto(s)
        stack.append((name, min(e, stack[-1][1]) if stack else e))
    while stack:
        upto(stack[-1][1])
        stack.pop()
    return out


@dataclasses.dataclass
class Trace(trace_reduce.Trace):
    program: List[Span] = dataclasses.field(default_factory=list)

    def _inside(self, s: float) -> bool:
        return self.window[0] <= s < self.window[1]

    def tick_host_ms(self) -> List[float]:
        """For each ``serve.tick`` that starts inside the window, in ms:
        its duration less the time its ``serve.sync`` descendants cover.
        That is the host's own work in the tick, which the device can
        only wait out."""
        syncs = [(s, e) for n, s, e, _ in self.program if n == "serve.sync"]
        starts = [s for s, _ in syncs]
        out = []
        for n, s, e, _ in self.program:
            if n != "serve.tick" or not self._inside(s):
                continue
            inner = syncs[bisect.bisect_left(starts, s):
                          bisect.bisect_left(starts, e)]
            inner = trace_reduce.union(trace_reduce.clip(inner, s, e))
            out.append((e - s - sum(b - a for a, b in inner)) * 1e-6)
        return out

    def prefill_split(self) -> Optional[Dict[str, List[float]]]:
        """{"fresh": [...], "resumed": [...]}: device seconds of each
        ``jit_prefill`` run that started inside the window, on the first
        device, by the ``resumed`` arg of the ``serve.prefill`` span that
        dispatched it.  Runs and spans are paired in start order, after
        dropping runs that began before the first span.  None where the
        counts differ or a run would start before its span: no number is
        better than a mispaired one."""
        spans = [p for p in self.program if p[0] == "serve.prefill"]
        if not spans or not self.devices:
            return None
        dev = self.devices[min(self.devices)]
        runs = sorted((s, e) for name, s, e in dev.modules
                      if PREFILL.match(name) and s >= spans[0][1])
        if len(runs) != len(spans) or any(
                r[0] < p[1] for r, p in zip(runs, spans)):
            return None
        out: Dict[str, List[float]] = {"fresh": [], "resumed": []}
        for (s, e), p in zip(runs, spans):
            if self._inside(s):
                kind = "resumed" if p[3].get("resumed") else "fresh"
                out[kind].append((e - s) * 1e-9)
        return out

    def _by_rid(self, name: str) -> Dict[int, Span]:
        return {p[3]["rid"]: p for p in self.program
                if p[0] == name and "rid" in p[3]}

    def queue_wait_ms(self) -> List[float]:
        """For each request placed inside the window whose ``serve.submit``
        the trace holds, in ms: from its submit to its ``serve.place``.
        A request submitted before the trace began is left out."""
        submits = self._by_rid("serve.submit")
        return [(s - submits[rid][1]) * 1e-6
                for rid, (_, s, _, _) in self._by_rid("serve.place").items()
                if rid in submits and self._inside(s)]

    def slot_ms(self) -> List[float]:
        """For each request finished inside the window whose
        ``serve.place`` the trace holds, in ms: from its place to the end
        of its ``serve.finish``, the time it held its slot."""
        places = self._by_rid("serve.place")
        return [(e - places[rid][1]) * 1e-6
                for rid, (_, s, e, _) in self._by_rid("serve.finish").items()
                if rid in places and self._inside(s)]

    def prefill_useful_pct(self) -> Optional[float]:
        """Prompt rows over the rows computed, over the ``serve.prefill``
        spans that start inside the window, in %."""
        spans = [p[3] for p in self.program
                 if p[0] == "serve.prefill" and self._inside(p[1])]
        padded = sum(a["padded_rows"] for a in spans)
        return 100.0 * sum(a["rows"] for a in spans) / padded if padded \
            else None

    def idle_by_program(self, n: Optional[int] = None) -> List[list]:
        """[label, seconds]: the device's idle time in the window, each
        piece of it put down to the deepest span open on the host then: a
        ``serve.*`` span where one is, else the harness's span
        (``engine.tick``, ``gen.wait``, ...), else ``other``."""
        pieces = deepest(self.program + [(name, s, e, {})
                                         for name, s, e in self.spans])
        starts = [s for s, _, _ in pieces]
        tot: Dict[str, float] = collections.Counter()
        for gs, ge in self.idle_gaps():
            left = ge - gs
            for s, e, name in pieces[max(0, bisect.bisect_right(starts, gs)
                                         - 1):]:
                if s >= ge:
                    break
                ov = min(e, ge) - max(s, gs)
                if ov > 0:
                    tot[name] += ov * 1e-9
                    left -= ov
            if left > 0:
                tot["other"] += left * 1e-9
        return [[k, v] for k, v in tot.most_common(n)]


def program_spans(path: str) -> List[Span]:
    """Every host event named ``serve.*`` in the trace at ``path``, as
    (name, start, end, args) in start order (an enclosing span first)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name, float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns),
                                dict(ev.stats)))
    return sorted(out, key=lambda p: (p[1], -p[2]))


def reduce(path: str, window=None) -> Trace:
    """``trace_reduce.reduce`` of ``path``, with the program's spans."""
    base = _harness_reduce(path, window)
    fields = {f.name: getattr(base, f.name)
              for f in dataclasses.fields(base)}
    return Trace(**fields, program=program_spans(path))


def _mean(xs, scale: float = 1.0) -> Optional[float]:
    return scale * sum(xs) / len(xs) if xs else None


def readings(trace: Trace) -> dict:
    """The readings of the four planned metrics (``tick_host_ms``,
    ``prefill_*_ms``), ``breakdown.idle_in_program`` and what checks
    them: the pairs against the window's prefill dispatches, the harness's
    idle labels, and the spans' own ``queue_wait_ms`` and
    ``prefill_useful_pct`` beside the harness's metrics of those names."""
    ticks = trace.tick_host_ms()
    split = trace.prefill_split()
    return {
        "tick_host_ms": _mean(ticks),
        "ticks": len(ticks),
        "prefill_fresh_ms": _mean(split["fresh"], 1e3) if split else None,
        "prefill_resumed_ms": _mean(split["resumed"], 1e3) if split else None,
        "prefill_paired": (len(split["fresh"]) + len(split["resumed"])
                           if split else None),
        "prefill_dispatches": len(trace.dispatches("prefill")),
        "idle_gaps": trace.idle_by_host(),
        "idle_in_program": trace.idle_by_program(),
        "queue_wait_ms": _mean(trace.queue_wait_ms()),
        "slot_ms": _mean(trace.slot_ms()),
        "prefill_useful_pct": trace.prefill_useful_pct(),
    }


def run_cell(argv) -> int:
    """``run.py`` once with ``--trace 1``, its trace reduced by ``reduce``
    (run.py's readers see a ``Trace`` as before); then these readings.
    It stands in until ``run.py`` reduces its trace with ``reduce``."""
    import run
    kept = []

    def keep(path, window=None):
        kept.append(reduce(path, window))
        return kept[-1]

    trace_reduce.reduce = keep
    try:
        rc = run.main(list(argv) + ["--trace", "1"])
    finally:
        trace_reduce.reduce = _harness_reduce
    if kept:
        print(json.dumps(readings(kept[-1])), flush=True)
    return rc


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0].startswith("--"):
        return run_cell(argv)
    if len(argv) != 1:
        raise SystemExit(__doc__)
    path = argv[0]
    if not path.endswith(".xplane.pb"):
        path = trace_reduce.find_xplane(path)
    print(json.dumps(readings(reduce(path))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
