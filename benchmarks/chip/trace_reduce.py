"""Reduce a profiler trace (``*.xplane.pb``) to what the metrics read.

Read with ``jax.profiler.ProfileData``.  A device is a plane named
``/device:TPU:<n>``; on it the line ``XLA Ops`` holds every operation the
device ran and ``XLA Modules`` one event per executed program (a jitted
dispatch), named after the program (``jit_prefill(...)``,
``jit_decode(...)``).  The host plane ``/host:CPU`` carries the harness's
own spans; the span named ``window`` bounds the traced window, and the
others (``engine.tick``, ``gen.wait``, ``gen.submit``, ``read``) label the
device's idle gaps by what the host was doing in them.  All times are in
nanoseconds on the profiler's clock.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import re
from typing import Dict, List, Tuple

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
HOST_SPANS = ("engine.tick", "gen.wait", "gen.submit", "read")
CONTAINERS = ("%while", "%conditional", "%call")


def union(intervals) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Device:
    ops: List[Tuple[str, float, float]]        # (name, start, end)
    modules: List[Tuple[str, float, float]]


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    devices: Dict[int, Device]
    spans: List[Tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self, dev: Device):
        return union(clip([(s, e) for _, s, e in dev.ops], *self.window))

    @property
    def busy_s(self) -> float:
        """Seconds in which any operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(sum(e - s for s, e in self.busy(d))
                   for d in self.devices.values()) * 1e-9 / len(self.devices)

    def dispatches(self, kind: str) -> List[float]:
        """Device seconds of each program run named ``jit_<kind>`` that
        started inside the window, on the first device."""
        if not self.devices:
            return []
        dev = self.devices[min(self.devices)]
        pat = re.compile(rf"^jit_{re.escape(kind)}(\W|$)")
        return [(e - s) * 1e-9 for name, s, e in dev.modules
                if pat.match(name) and self.window[0] <= s < self.window[1]]

    def top_ops(self, n: int = 10):
        """[name, seconds] of the operations that took most device time in
        the window, summed over runs, on the first device.  An operation
        is named by its program and its HLO name (``jit_decode(<id>):
        %fusion.12``); loops and calls, which contain other operations,
        are left out."""
        if not self.devices:
            return []
        dev = self.devices[min(self.devices)]
        mods = sorted(dev.modules, key=lambda x: x[1])
        starts = [m[1] for m in mods]
        tot = collections.Counter()
        for name, s, e in dev.ops:
            op = name.split(" ", 1)[0]
            if op.startswith(CONTAINERS):
                continue
            s, e = max(s, self.window[0]), min(e, self.window[1])
            if e <= s:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][0] if i >= 0 and mods[i][2] >= s else "?"
            tot[f"{prog}:{op}"] += (e - s) * 1e-9
        return [[k, v] for k, v in tot.most_common(n)]

    def idle_gaps(self) -> List[Tuple[float, float]]:
        """Intervals of the window in which the first device ran nothing."""
        if not self.devices:
            return []
        busy = self.busy(self.devices[min(self.devices)])
        gaps, t = [], self.window[0]
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.window[1]:
            gaps.append((t, self.window[1]))
        return gaps

    def idle_by_host(self, n: int = 10):
        """[label, seconds]: the device's idle time in the window, split by
        the host span that overlaps each gap most (``other`` if none)."""
        spans = sorted(self.spans, key=lambda x: x[1])
        starts = [s for _, s, _ in spans]
        tot = collections.Counter()
        for gs, ge in self.idle_gaps():
            best, label = 0.0, "other"
            # the harness's spans follow one another: start one back
            for name, s, e in spans[max(0, bisect.bisect_left(starts, gs) - 1):]:
                if s >= ge:
                    break
                ov = min(e, ge) - max(s, gs)
                if ov > best:
                    best, label = ov, name
            tot[label] += (ge - gs) * 1e-9
        return [[k, v] for k, v in tot.most_common(n)]


def find_xplane(trace_dir) -> str:
    found = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def reduce(path: str, window=None) -> Trace:
    """The trace at ``path``; ``window`` (start, end in ns) defaults to the
    host span named ``window``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, Device] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        m = DEVICE.match(plane.name)
        if m:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = _events(line)
                elif line.name == "XLA Modules":
                    mods = _events(line)
            devices[int(m.group(1))] = Device(ops, mods)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == "window" and window is None:
                        window = (s, e)
                    elif name in HOST_SPANS:
                        spans.append((name, s, e))
    if window is None:
        raise ValueError(f"{path}: no host span named 'window'")
    return Trace(window, devices, spans)
