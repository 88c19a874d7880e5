"""The serving engine's own ``serve.*`` host spans under a profiler.

A tiny paged engine serves prompts of one to three chunks under
``jax.profiler`` on the CPU.  The spans read back from the trace must nest
as the engine's calls do, carry what the engine dispatched as their args,
follow each request by its ``rid``, and change nothing the engine
computes: greedy tokens are the same with a profiler session on and off.
"""
import glob
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.models import ArchConfig, init_params
from repro.serve import Request, ServeConfig, ServingEngine

CFG = ArchConfig(name="spans", family="dense", n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=100,
                 decode_margin=32, dtype=jnp.float32)
SERVE = ServeConfig(max_batch=2, max_prompt=4, max_seq=32, max_new_tokens=3,
                    page_size=4)
NAMES = ("serve.submit", "serve.tick", "serve.admit", "serve.place",
         "serve.prefill", "serve.iotlb", "serve.grow", "serve.decode",
         "serve.sync", "serve.emit", "serve.finish", "serve.spec",
         "serve.tier")
PROMPTS = {0: 3, 1: 9, 2: 5}        # rid -> prompt length (chunks of 4)
ENGINE = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro" / \
    "serve" / "engine.py"


def _prompt(rid):
    return [1 + (5 * rid + k) % 97 for k in range(PROMPTS[rid])]


def _serve(params, trace_dir=None, record=None):
    """Serve PROMPTS (rid 2 submitted after two ticks); with ``record``,
    the args of every prefill and decode dispatch are appended to it."""
    eng = ServingEngine(CFG, params, SERVE)
    eng.warmup()
    if record is not None:
        prefill, decode = eng._prefill, eng._decode

        def rec_prefill(params, cache, toks, lens, pages, offs):
            record.append(("prefill", np.asarray(toks), np.asarray(lens),
                           offs is not None))
            return prefill(params, cache, toks, lens, pages, offs)

        def rec_decode(params, cache, toks, pos, pages):
            record.append(("decode", np.asarray(pos)))
            return decode(params, cache, toks, pos, pages)

        eng._prefill, eng._decode = rec_prefill, rec_decode
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    reqs = {}
    with TraceAnnotation("window"):
        for rid in (0, 1):
            reqs[rid] = Request(rid, _prompt(rid))
            eng.submit(reqs[rid])
        for _ in range(2):
            eng.tick()
        reqs[2] = Request(2, _prompt(2))
        eng.submit(reqs[2])
        while eng.sched.has_work():
            eng.tick()
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return {rid: list(r.out_tokens) for rid, r in reqs.items()}


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def traced(params, tmp_path_factory):
    """(tokens, dispatches, spans): spans are (name, start, end, args) of
    every ``serve.*`` host event, in start order, parents first."""
    d = tmp_path_factory.mktemp("trace")
    record = []
    tokens = _serve(params, d, record)
    path = sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats)))
    spans.sort(key=lambda p: (p[1], -p[2]))
    return tokens, record, spans


def _children(spans, parent):
    """The spans directly inside ``parent``, in start order."""
    inside = [p for p in spans if p is not parent and parent[1] <= p[1]
              and p[2] <= parent[2]]
    return [p for p in inside if not any(
        q is not p and q[1] <= p[1] and p[2] <= q[2] for q in inside)]


def test_span_names_are_declared(traced):
    _, _, spans = traced
    assert {p[0] for p in spans} <= set(NAMES)
    used = set(re.findall(r'span\("(serve\.\w+)"', ENGINE.read_text()))
    assert used <= set(NAMES)
    assert {"serve.tick", "serve.submit", "serve.finish"} <= used


def test_ticks_hold_their_phases_in_order(traced):
    _, _, spans = traced
    ticks = [p for p in spans if p[0] == "serve.tick"]
    seen = set()
    for tick in ticks:
        names = [p[0] for p in _children(spans, tick)]
        seen.update(names)
        # admit, then any prefill dispatches, then grow, then decode when
        # some slot has its prompt in the cache
        assert re.fullmatch(r"a(p)*g(d)?", "".join(
            {"serve.admit": "a", "serve.prefill": "p", "serve.grow": "g",
             "serve.decode": "d"}[n] for n in names)), names
    assert seen == {"serve.admit", "serve.prefill", "serve.grow",
                    "serve.decode"}
    for p in spans:
        if p[0] in ("serve.prefill", "serve.decode"):
            kids = {c[0] for c in _children(spans, p)}
            assert "serve.sync" in kids or p[0] == "serve.prefill"
            assert kids <= {"serve.iotlb", "serve.sync", "serve.emit"}


def test_args_are_what_the_engine_dispatched(traced):
    _, record, spans = traced
    prefills = [p[3] for p in spans if p[0] == "serve.prefill"]
    decodes = [p[3] for p in spans if p[0] == "serve.decode"]
    got_p = [r for r in record if r[0] == "prefill"]
    got_d = [r for r in record if r[0] == "decode"]
    assert len(prefills) == len(got_p) and len(decodes) == len(got_d)
    assert {a["resumed"] for a in prefills} == {0, 1}
    for a, (_, toks, lens, resumed) in zip(prefills, got_p):
        assert a["rows"] == int(lens.sum())
        assert a["padded_rows"] == toks.size
        assert a["resumed"] == int(resumed)
    assert sum(a["rows"] for a in prefills) == sum(PROMPTS.values())
    assert len([p for p in spans if p[0] == "serve.iotlb"]) == len(prefills)


def test_one_rid_runs_from_submit_to_finish(traced):
    tokens, _, spans = traced
    for rid, toks in tokens.items():
        mine = [p for p in spans if p[3].get("rid") == rid]
        assert [p[0] for p in mine] == ["serve.submit", "serve.place",
                                        "serve.finish"], rid
        submit, place, finish = mine
        assert submit[2] <= place[1] and place[2] <= finish[1]
        assert len(toks) == SERVE.max_new_tokens


def test_greedy_tokens_same_with_profiler_on_and_off(traced, params):
    tokens, _, _ = traced
    assert _serve(params) == tokens
