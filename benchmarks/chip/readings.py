"""Small helpers the metric readers share."""
from __future__ import annotations

import math


def nearest_rank(values, q: float) -> float:
    """The q-th percentile by nearest rank: the smallest value with at
    least q% of the sample at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of an empty sample")
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def window_arrivals(run):
    """Requests due inside the measured window."""
    return [r for r in run.loop.requests if r.in_window]


def ttft_s(r) -> float:
    """First-token time minus due time; a request that failed or never got
    its first token is a miss at +inf."""
    if r.failed or r.first_t is None:
        return math.inf
    return r.first_t - r.due


def tpot_s(r) -> float:
    """Mean gap between tokens after the first; a request that failed or
    never finished is a miss at +inf."""
    if not r.done:
        return math.inf
    return (r.last_t - r.first_t) / max(1, r.seen - 1)


def finite(value):
    """``value``, or None where misses set it (such a run has failed
    requests, so it is not correct) and there is no number to report."""
    return value if math.isfinite(value) else None


def ticks_between(run, t0: float, t1: float):
    return [t for t in run.loop.ticks if t.t0 >= t0 and t.t1 <= t1]
