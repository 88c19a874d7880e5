"""One general generator for every traffic mix under ``traffic/``.

A mix is a JSON file of parameters (see ``traffic/chat.json``):

    arrivals       how requests come (see below)
    prompt_tokens  {"dist": "lognormal", "median": m, "sigma": s,
                    "min": lo, "max": hi} or {"dist": "uniform",
                    "min": lo, "max": hi}: the new tokens of each request
    prefix         optional {"count": k, "tokens": n}: every session opens
                   with one of k shared prompts of n tokens (a system prompt)
    turns          optional {"count": t, "think_s": s}: a session is t
                   requests; each next one is due s seconds after the last
                   one's final token, and its prompt is the last prompt, its
                   answer and new tokens (default: one request a session)
    output_tokens  tokens every request emits (the engine's output length
                   is one number for all requests)
    warmup_s       seconds of traffic before the measured window, not counted
    follow         "window_arrivals": after the window, keep serving until
                   every request due in it has its last token (or grace_s
                   runs out); "none": stop when the window closes
    grace_s        see ``follow``
    check_sample   how many served requests the reference checks

Arrivals, open loop: ``{"process": "poisson", "rate_per_s": r}``, with
an optional ``"pattern": [[seconds, weight], ...]`` that repeats from the
run's start and shapes the rate (each segment's rate is r x weight / the
pattern's mean weight, so r stays the mean).  Each phase of the run
(warm-up, the window, and the tail that ``follow`` asks for) holds a
fixed number of sessions, its expected count rounded, spread as a
Poisson process conditioned on that number.  Closed loop:
``{"process": "closed", "clients": n, "think_s": s}``: n clients, each
sending its next request s seconds after its last one's final token,
one session after another.

Arrival times, lengths and the choice of shared prompt come from
LAYOUT_SEED alone; ``--seed`` draws the token ids.  So every seed serves
the same work on the same schedule, in another text: the spread between
runs is the system's, not the draw's (a p90 over some tens of requests
moves by tens of percent between two Poisson draws).  A request that
follows another in its session or client is drawn from (chain, place in
the chain), so it is the same whenever it comes.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

# one draw of arrival times and lengths for every --seed (see above)
LAYOUT_SEED = 0
KEYS = {"why", "arrivals", "prompt_tokens", "prefix", "turns",
        "output_tokens", "warmup_s", "follow", "grace_s", "check_sample"}
FOLLOW = ("window_arrivals", "none")


@dataclasses.dataclass
class Arrival:
    idx: int
    due: float              # seconds after the first scheduled arrival
    prompt: List[int]
    in_window: bool
    chain: int = 0          # the session (open loop) or client (closed)
    place: int = 0          # requests before this one in its chain


def check(mix: dict) -> None:
    """Raise on a mix this generator cannot run."""
    unknown = set(mix) - KEYS
    if unknown:
        raise ValueError(f"unknown mix keys {sorted(unknown)}")
    if mix["follow"] not in FOLLOW:
        raise ValueError(f"follow must be one of {FOLLOW}")
    if mix["arrivals"]["process"] not in ("poisson", "closed"):
        raise ValueError(f"unknown arrival process "
                         f"{mix['arrivals']['process']!r}")
    if mix["prompt_tokens"]["dist"] not in ("lognormal", "uniform"):
        raise ValueError(f"unknown prompt length distribution "
                         f"{mix['prompt_tokens']['dist']!r}")
    if any(w <= 0 for _, w in mix["arrivals"].get("pattern", [])):
        raise ValueError("every pattern weight must be above 0")


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] == "uniform":
        return rng.integers(spec["min"], spec["max"] + 1, n)
    raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def _turns(mix: dict):
    t = mix.get("turns", {"count": 1, "think_s": 0.0})
    return int(t["count"]), float(t["think_s"])


def max_prompt_tokens(mix: dict) -> int:
    """The longest prompt the mix can send: the shared prompt, every
    turn's new tokens at their most, and the answers before the last."""
    turns, _ = _turns(mix)
    prefix = int(mix.get("prefix", {}).get("tokens", 0))
    return (prefix + turns * int(mix["prompt_tokens"]["max"])
            + (turns - 1) * int(mix["output_tokens"]))


class _Clock:
    """Cumulative intensity of a rate pattern over the run, and back:
    times spread evenly in its units follow the pattern's rate."""

    def __init__(self, pattern, until: float):
        self.flat = not pattern
        if self.flat:
            return
        segs = np.array(pattern, dtype=float)
        mean = (segs[:, 0] * segs[:, 1]).sum() / segs[:, 0].sum()
        ts, cs, t, c = [0.0], [0.0], 0.0, 0.0
        while t < until:
            for secs, w in segs:
                t, c = t + secs, c + secs * w / mean
                ts.append(t)
                cs.append(c)
        self.t, self.c = np.array(ts), np.array(cs)

    def units(self, t):
        return np.asarray(t) if self.flat else np.interp(t, self.t, self.c)

    def time(self, u):
        return np.asarray(u) if self.flat else np.interp(u, self.c, self.t)


class Schedule:
    """The arrivals a run starts with (``first``), and each chain's next
    request once its last one has ended (``next``)."""

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        check(mix)
        self.mix, self.seed, self.vocab = mix, int(seed), vocab
        self.turns, self.think = _turns(mix)
        warm = float(mix["warmup_s"])
        self.window = (warm, warm + float(seconds))
        tail = float(mix["grace_s"]) if mix["follow"] == FOLLOW[0] else 0.0
        pre = mix.get("prefix")
        text = np.random.default_rng([self.seed, 4])
        self.prefixes = [text.integers(0, vocab, int(pre["tokens"])).tolist()
                         for _ in range(int(pre["count"]))] if pre else [[]]
        self.made = 0
        arr = mix["arrivals"]
        if arr["process"] == "closed":
            self.closed = True
            self.first = [self._make(c, 0, 0.0, []) for c in
                          range(int(arr["clients"]))]
            return
        self.closed = False
        rate = float(arr["rate_per_s"])
        clock = _Clock(arr.get("pattern"), warm + seconds + tail)
        layout = np.random.default_rng(LAYOUT_SEED)
        text = np.random.default_rng([self.seed, 2])
        self.first = []
        for start, length in ((0.0, warm), (warm, float(seconds)),
                              (warm + seconds, tail)):
            a, b = clock.units([start, start + length])
            n = int(round(rate * (b - a)))
            dues = clock.time(a + np.sort(layout.uniform(0.0, b - a, n)))
            lens = _lengths(mix["prompt_tokens"], n, layout)
            for due, k in zip(dues, lens):
                chain = len(self.first)
                own = text.integers(0, vocab, int(k)).tolist()
                self.first.append(self._arrival(
                    chain, 0, float(due), self._prefix(chain, 0) + own))

    def _prefix(self, chain: int, place: int) -> List[int]:
        """The shared prompt that opens a session, picked by its place."""
        pick = np.random.default_rng([LAYOUT_SEED, 5, chain, place])
        return self.prefixes[int(pick.integers(len(self.prefixes)))]

    def _arrival(self, chain, place, due, prompt) -> Arrival:
        ws, we = self.window
        self.made += 1
        return Arrival(self.made - 1, due, prompt, ws <= due < we, chain,
                       place)

    def _make(self, chain, place, due, context) -> Arrival:
        """The place-th request of a chain, drawn from (chain, place)."""
        layout = np.random.default_rng([LAYOUT_SEED, 6, chain, place])
        text = np.random.default_rng([self.seed, 6, chain, place])
        k = int(_lengths(self.mix["prompt_tokens"], 1, layout)[0])
        own = text.integers(0, self.vocab, k).tolist()
        if place % self.turns == 0:        # a session opens
            context = self._prefix(chain, place)
        return self._arrival(chain, place, due, list(context) + own)

    def next(self, prev: Arrival, ended: float, answer: List[int]
             ) -> Optional[Arrival]:
        """The request after ``prev`` in its chain, or None: due a think
        time after ``prev``'s final token at ``ended``, carrying its
        prompt and answer where the session goes on."""
        place = prev.place + 1
        if place % self.turns:
            think = self.think
        elif self.closed:
            think = float(self.mix["arrivals"]["think_s"])
        else:
            return None
        return self._make(prev.chain, place, ended + think,
                          prev.prompt + list(answer))


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> Schedule:
    return Schedule(mix, seed, seconds, vocab)
