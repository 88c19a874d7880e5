"""Drives one ``ServingEngine`` through a traffic schedule
(``loadgen.Schedule``) and records what the metric readers need.

Time is the host clock (``time.perf_counter``) in seconds after the first
scheduled arrival.  Arrivals are submitted when they are due, between
ticks; a tick is ``engine.tick()``, which returns once its sampled tokens
are on the host.  After each tick the loop reads, from the requests and
the engine's counters: which requests got tokens (stamped with the tick's
end), which left the pending queue (stamped with the tick's start, where
admission happens), how many prompt rows each slot has prefilled, and
how many pages the pool has in use.  A request that ends hands its chain
(a session's next turn, a closed-loop client's next request) to the
schedule, which makes the next request due from that end.  A request's
latency counts from the time it was due, so a tick that holds up its
submission counts against it.

Host spans (``jax.profiler.TraceAnnotation``: ``window``, ``gen.wait``,
``gen.submit``, ``engine.tick``, ``read``) are written only in a traced
run, on the profiler's clock, for ``trace_reduce`` to label idle gaps.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import heapq
import time
from typing import List, Optional

import jax

from repro.serve import Request


@dataclasses.dataclass
class Req:
    arrival: object                 # loadgen.Arrival
    req: Optional[Request] = None
    submit_t: Optional[float] = None
    admit_t: Optional[float] = None
    first_t: Optional[float] = None
    last_t: Optional[float] = None
    seen: int = 0                   # tokens stamped so far
    prefilled: int = 0              # prompt rows prefilled so far

    idx = property(lambda self: self.arrival.idx)
    due = property(lambda self: self.arrival.due)
    prompt = property(lambda self: self.arrival.prompt)
    in_window = property(lambda self: self.arrival.in_window)

    @property
    def tokens(self) -> List[int]:
        return list(self.req.out_tokens) if self.req else []

    @property
    def done(self) -> bool:
        return bool(self.req and self.req.done and not self.req.failed)

    @property
    def failed(self) -> bool:
        return bool(self.req and self.req.failed)


@dataclasses.dataclass
class Tick:
    t0: float
    t1: float
    decode_rows: int                # tokens this tick's decode step emitted
    decode_ctx: int                 # cached positions those rows attended
    prefill_rows: int               # prompt rows prefilled this tick
    pages_in_use: int
    tokens: int                     # all tokens emitted this tick
    active: int                     # engine.active_ticks added this tick


@dataclasses.dataclass
class Loop:
    requests: List[Req]
    ticks: List[Tick]
    window: tuple                   # (start, end)
    end: float                      # when the loop stopped
    lowerings: dict                 # {"warmup": n, "window": n, ...}
    trace_window: Optional[tuple] = None   # host-clock (start, end) traced


class _Compiles:
    """Counts, by phase, programs lowered (each new trace, which is then
    compiled or read back) and those read from the persistent cache."""

    def __init__(self):
        self.phase = "setup"
        self.counts = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._lowered)
        jax.monitoring.register_event_listener(self._cached)

    def _lowered(self, event, secs, **kw):
        if event.endswith("jaxpr_to_mlir_module_duration"):
            self.counts[self.phase + ".lowered"] += 1

    def _cached(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.counts[self.phase + ".cached"] += 1


COMPILES = None


def _trace_options():
    """Device activity and the host's annotations only: the Python tracer
    (an event per Python call) would slow the host loop it measures."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def watch_compiles() -> "_Compiles":
    global COMPILES
    if COMPILES is None:
        COMPILES = _Compiles()
    return COMPILES


def drive(eng, sched, mix: dict, trace_dir=None,
          trace_seconds: float = 0.0) -> Loop:
    clock = time.perf_counter
    compiles = watch_compiles()
    compiles.counts.clear()
    ws, we = sched.window
    follow = mix["follow"] == "window_arrivals"
    give_up = we + float(mix.get("grace_s", 0.0))
    tracing = trace_dir is not None
    span = jax.profiler.TraceAnnotation if tracing else \
        (lambda name: contextlib.nullcontext())
    recs: List[Req] = []
    due = []                         # heap of (due, idx, Req) not submitted

    def add(arrival):
        recs.append(Req(arrival))
        heapq.heappush(due, (arrival.due, arrival.idx, recs[-1]))

    for arrival in sched.first:
        add(arrival)
    live: List[Req] = []
    queued = collections.deque()     # (Req, handle) not yet admitted
    ticks: List[Tick] = []
    # the trace covers the last trace_seconds of the window; the profiler
    # stops only once the loop has ended, so the seconds it takes to write
    # the trace out hold up no measured tick.
    trace_from = max(ws, we - trace_seconds)
    trace_on = trace_window = window_span = None
    compiles.phase = "warmup"
    t0 = clock()
    now = 0.0
    while True:
        now = clock() - t0
        if compiles.phase == "warmup" and now >= ws:
            compiles.phase = "window"
        if tracing and trace_on is None and now >= trace_from:
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=_trace_options())
            window_span = span("window")
            window_span.__enter__()
            trace_on = clock() - t0
        if compiles.phase == "window" and now >= we:
            compiles.phase = "after"
            if window_span is not None:
                window_span.__exit__(None, None, None)
                trace_window = (trace_on, clock() - t0)
        # a request due in the window exists by the time it closes: a
        # chain's next request is made when the last one ends, before then.
        if now >= we and (not follow or now >= give_up or
                          all(r.req is not None and r.req.done
                              for r in recs if r.in_window)):
            break
        if due and due[0][0] <= now:
            with span("gen.submit"):
                while due and due[0][0] <= now:
                    r = heapq.heappop(due)[2]
                    r.req = Request(r.idx, list(r.prompt))
                    r.submit_t = now
                    queued.append((r, eng.submit(r.req)))
                    live.append(r)
        if not eng.sched.has_work():
            wake = due[0][0] if due else float("inf")
            for t in (ws, we, trace_from if tracing else None):
                if t is not None and t > now:
                    wake = min(wake, t)
            if wake == float("inf"):
                break                   # idle, and nothing more is due
            with span("gen.wait"):
                time.sleep(max(0.0, wake - now))
            continue
        a, active = clock() - t0, eng.active_ticks
        with span("engine.tick"):
            eng.tick()
        b = clock() - t0
        with span("read"):
            ticks.append(_read(eng, live, queued, a, b,
                               eng.active_ticks - active))
            for r in live:
                if r.done:
                    nxt = sched.next(r.arrival, r.last_t, r.tokens)
                    if nxt is not None:
                        add(nxt)
            live = [r for r in live if not r.req.done]
    if trace_on is not None:
        if trace_window is None:
            window_span.__exit__(None, None, None)
            trace_window = (trace_on, clock() - t0)
        jax.profiler.stop_trace()
    return Loop(recs, ticks, (ws, we), now, dict(compiles.counts),
                trace_window)


def _read(eng, live: List[Req], queued, a: float, b: float,
          active: int) -> Tick:
    while queued and queued[0][1].status != "pending":
        queued.popleft()[0].admit_t = a
    slots = {meta.req.rid: meta.prefill_done
             for meta in eng.sched.slots if meta is not None}
    rows = ctx = pre = tokens = 0
    for r in live:
        n = len(r.req.out_tokens)
        done_rows = len(r.prompt) if n else slots.get(r.idx, 0)
        pre += done_rows - r.prefilled
        r.prefilled = done_rows
        if n > r.seen:
            tokens += n - r.seen
            # out_tokens[0] comes from prefill; token k >= 1 from a decode
            # row at position len(prompt) + k - 1, which attends
            # len(prompt) + k cached positions.
            for k in range(max(r.seen, 1), n):
                rows += 1
                ctx += len(r.prompt) + k
            if r.seen == 0:
                r.first_t = b
            r.last_t = b
            r.seen = n
    return Tick(a, b, rows, ctx, pre, eng.pages_in_use(), tokens, active)
