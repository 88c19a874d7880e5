"""program_trace: the engine's ``serve.*`` spans read beside the device.

Synthetic traces check the pairing and labelling rules.  A small trace
recorded on one TPU v5e (``record_program_spans.py``: a reduced
qwen2.5-3b engine, three requests over six ticks, five prefill
dispatches of which three resumed) is read against numbers counted from
the file by a separate pass: a 1-ns timeline of the window painted with
the innermost open span and masked by the device's busy ops, and each
prefill run paired with the last ``serve.prefill`` span begun before it.
(Its spans carry three args the engine has since dropped, ``slot``,
``slots`` and ``tokens``; nothing reads them.)
A tiny engine traced on the CPU checks ``tick_host_ms`` against its
spans' durations counted by hand; the harness, run through
``program_trace`` on the CPU, still prints its own result line and then
the program's readings.
"""
from __future__ import annotations

import glob
import json
import math

import pytest

import chipbench_fixture as fx

import jax
import program_trace
import trace_reduce
from program_trace import Trace, deepest
from trace_reduce import Device

MS = 1e6                                    # ns


def synthetic(modules, program, spans=(), ops=None):
    """A trace over (0, 100 ms) whose device ran ``modules`` (name, start,
    end in ms), each module one op unless ``ops`` are given."""
    mods = [(n, s * MS, e * MS) for n, s, e in modules]
    ops = mods if ops is None else [(n, s * MS, e * MS) for n, s, e in ops]
    return Trace((0.0, 100 * MS), {0: Device(ops, mods)},
                 [(n, s * MS, e * MS) for n, s, e in spans],
                 [(n, s * MS, e * MS, a) for n, s, e, a in program])


PREFILLS = [("jit_prefill(1)", 2, 10), ("jit_prefill(1)", 21, 35),
            ("jit_decode(2)", 36, 40), ("jit_prefill(1)", 50, 60)]
SPANS = [("serve.prefill", 1, 3, {"resumed": 0}),
         ("serve.prefill", 20, 22, {"resumed": 1}),
         ("serve.prefill", 45, 46, {"resumed": 0})]


def test_prefill_split_pairs_in_start_order():
    split = synthetic(PREFILLS, SPANS).prefill_split()
    assert split == {"fresh": [pytest.approx(8e-3), pytest.approx(10e-3)],
                     "resumed": [pytest.approx(14e-3)]}


def test_prefill_split_drops_runs_before_the_first_span():
    early = [("jit_prefill(1)", 0.5, 1.5)] + PREFILLS
    assert synthetic(early, SPANS).prefill_split() == \
        synthetic(PREFILLS, SPANS).prefill_split()


@pytest.mark.parametrize("modules,program", [
    (PREFILLS, SPANS[:2]),                          # a run without a span
    (PREFILLS[:2], SPANS),                          # a span without a run
    ([("jit_prefill(1)", 2, 10), ("jit_prefill(1)", 15, 18),
      ("jit_prefill(1)", 50, 60)], SPANS),          # a run before its span
    (PREFILLS, []),                                 # no spans at all
])
def test_prefill_split_refuses_what_cannot_be_paired(modules, program):
    assert synthetic(modules, program).prefill_split() is None


def test_deepest_names_each_piece_by_the_innermost_span():
    spans = [("serve.tick", 0, 10, {}), ("serve.decode", 2, 8, {}),
             ("serve.sync", 3, 5, {}), ("serve.emit", 6, 7, {}),
             ("serve.tick", 12, 13, {})]
    assert deepest(spans) == [
        (0, 2, "serve.tick"), (2, 3, "serve.decode"), (3, 5, "serve.sync"),
        (5, 6, "serve.decode"), (6, 7, "serve.emit"), (7, 8, "serve.decode"),
        (8, 10, "serve.tick"), (12, 13, "serve.tick")]


def test_idle_by_program_puts_idle_time_down_to_the_deepest_span():
    # device busy 0-10, 14-30, 40-100: gaps 10-14 and 30-40
    busy = [("jit_decode(2)", 0, 10), ("jit_decode(2)", 14, 30),
            ("jit_decode(2)", 40, 100)]
    program = [("serve.tick", 9, 16, {}), ("serve.emit", 9, 11, {}),
               ("serve.grow", 12, 13, {})]
    spans = [("engine.tick", 8, 16), ("read", 16, 17), ("gen.wait", 29, 35)]
    tr = synthetic(busy, program, spans)
    got = dict(tr.idle_by_program())
    # 10-14: serve.emit 10-11, serve.tick 11-12 and 13-14, serve.grow
    # 12-13; 30-40: the harness's gen.wait 30-35, then nothing open
    assert got == {"serve.tick": pytest.approx(2e-3),
                   "serve.emit": pytest.approx(1e-3),
                   "serve.grow": pytest.approx(1e-3),
                   "gen.wait": pytest.approx(5e-3),
                   "other": pytest.approx(5e-3)}
    assert math.isclose(sum(got.values()),
                        sum(s for _, s in tr.idle_by_host()))


def test_tick_host_ms_subtracts_sync_descendants():
    program = [("serve.tick", 1, 11, {}), ("serve.decode", 2, 10, {}),
               ("serve.sync", 3, 8, {}), ("serve.tick", 20, 24, {}),
               ("serve.sync", 21, 22, {}), ("serve.tick", 100, 101, {})]
    assert synthetic([], program).tick_host_ms() == \
        [pytest.approx(5.0), pytest.approx(3.0)]


def test_requests_by_rid_and_prefill_rows():
    program = [
        ("serve.submit", 1, 2, {"rid": 7}), ("serve.submit", 3, 4, {"rid": 8}),
        ("serve.place", 5, 6, {"rid": 7}),
        ("serve.prefill", 6, 7, {"rows": 10, "padded_rows": 64,
                                 "resumed": 0}),
        ("serve.place", 20, 21, {"rid": 9}),        # submitted before
        ("serve.finish", 30, 31, {"rid": 7}),
        ("serve.place", 40, 41, {}),                # deferred: no rid
        ("serve.prefill", 50, 51, {"rows": 22, "padded_rows": 64,
                                   "resumed": 1}),
        ("serve.finish", 60, 61, {"rid": 10}),      # placed before
        ("serve.place", 120, 121, {"rid": 8}),      # after the window
        ("serve.prefill", 120, 121, {"rows": 64, "padded_rows": 64,
                                     "resumed": 0})]
    tr = synthetic([], program)
    assert tr.queue_wait_ms() == [pytest.approx(4.0)]
    assert tr.slot_ms() == [pytest.approx(26.0)]
    assert tr.prefill_useful_pct() == pytest.approx(100 * 32 / 128)
    assert synthetic([], program[:3]).prefill_useful_pct() is None


RECORDED = fx.CHIP / "testdata" / "program_spans.xplane.pb"
# counted by the separate pass, in ns
RECORDED_IDLE = {
    "serve.sync": 10173353, "serve.decode": 7219381,
    "serve.prefill": 6530322, "gen.wait": 2071390, "serve.tick": 453873,
    "serve.place": 317849, "serve.grow": 128949, "serve.finish": 102241,
    "gen.submit": 97870, "serve.iotlb": 86851, "serve.admit": 71481,
    "other": 62930, "serve.emit": 56210, "engine.tick": 24021,
    "read": 19260, "serve.submit": 15340}
RECORDED_TICKS = [2954952, 2505520, 4311201, 2764591, 1402031, 1345911]


@pytest.fixture(scope="module")
def recorded():
    return program_trace.reduce(str(RECORDED))


def test_recorded_prefill_split(recorded):
    split = recorded.prefill_split()
    assert split["fresh"] == pytest.approx([29814e-9, 30041e-9])
    assert split["resumed"] == pytest.approx([41034e-9, 40538e-9, 40650e-9])
    assert len(recorded.dispatches("prefill")) == 5


def test_recorded_idle_by_program(recorded):
    got = dict(recorded.idle_by_program())
    assert got == pytest.approx({k: v * 1e-9
                                 for k, v in RECORDED_IDLE.items()})
    assert math.isclose(recorded.window_s - recorded.busy_s,
                        sum(RECORDED_IDLE.values()) * 1e-9)
    # the harness's own labels are unchanged
    assert {k for k, _ in recorded.idle_by_host()} == {"engine.tick",
                                                       "gen.wait"}


def test_recorded_requests(recorded):
    # prompts of 40, 10 and 24 rows in five dispatches of 4 x 16 rows
    assert recorded.prefill_useful_pct() == pytest.approx(100 * 74 / 320)
    assert len(recorded.queue_wait_ms()) == len(recorded.slot_ms()) == 3
    assert all(0 < t < 1e3 * recorded.window_s
               for t in recorded.queue_wait_ms() + recorded.slot_ms())


def test_recorded_tick_host_ms(recorded):
    assert recorded.tick_host_ms() == pytest.approx(
        [t * 1e-6 for t in RECORDED_TICKS])


@pytest.fixture(scope="module")
def engine_trace(tmp_path_factory):
    """A tiny engine's ticks traced on the CPU: (Trace, raw spans)."""
    import jax.numpy as jnp
    from repro.models import ArchConfig, init_params
    from repro.serve import Request, ServeConfig, ServingEngine
    cfg = ArchConfig(name="pt", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=100,
                     decode_margin=32, dtype=jnp.float32)
    eng = ServingEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                        ServeConfig(max_batch=2, max_prompt=4, max_seq=32,
                                    max_new_tokens=3, page_size=4))
    eng.warmup()
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation("window"):
        for rid, n in enumerate((3, 9, 5)):
            eng.submit(Request(rid, list(range(1, n + 1))))
        while eng.sched.has_work():
            eng.tick()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))[-1]
    return program_trace.reduce(path), program_trace.program_spans(path)


def test_engine_ticks_host_ms_by_hand(engine_trace):
    trace, raw = engine_trace
    ticks = [p for p in raw if p[0] == "serve.tick"]
    syncs = [p for p in raw if p[0] == "serve.sync"]
    want = []
    for _, s, e, _ in ticks:
        inside = [b - a for _, a, b, _ in syncs if s <= a and b <= e]
        want.append((e - s - sum(inside)) * 1e-6)
    assert len(want) == 4
    assert trace.tick_host_ms() == pytest.approx(want)
    assert all(0 < t for t in want)
    assert trace.program == raw
    # a CPU trace has no TPU plane: nothing to pair, no gaps to label
    assert trace.prefill_split() is None and trace.idle_by_program() == []


def test_harness_through_program_trace(tmp_path, monkeypatch, capsys):
    import registry
    import run
    checkout = fx.make_checkout(tmp_path / "checkout")
    monkeypatch.setattr(registry, "ROOT", checkout)
    monkeypatch.setattr(registry, "HERE", checkout / "benchmarks" / "chip")
    monkeypatch.setattr(run, "check_device", lambda chips: jax.devices())
    monkeypatch.setattr(run, "enable_cache", lambda: "off")
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    assert program_trace.main(["--workload", fx.CELL, "--seed", "6",
                               "--seconds", "1.5"]) == 0
    assert trace_reduce.reduce is program_trace._harness_reduce
    lines = capsys.readouterr().out.strip().splitlines()
    res, got = json.loads(lines[-2]), json.loads(lines[-1])
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"queue_wait_ms.chat", "ticks_per_s.tiny"}
    assert got["ticks"] > 0 and got["tick_host_ms"] > 0
    assert got["queue_wait_ms"] >= 0 and got["slot_ms"] > 0
    assert 0 < got["prefill_useful_pct"] <= 100
    # a CPU trace has no TPU plane: no prefill runs to pair
    assert got["prefill_dispatches"] == 0 and got["prefill_paired"] is None
