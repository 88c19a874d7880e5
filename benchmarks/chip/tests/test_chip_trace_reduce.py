"""trace_reduce on a small trace recorded on one TPU v5e: a reduced
qwen2.5-3b engine serving three requests over six ticks (harness spans
``gen.submit``, ``engine.tick``, ``read``).  The expected numbers were
counted from the file by a separate pass: a 1-ns boolean timeline for the
busy time, and a plain count of the ``XLA Modules`` events."""
from __future__ import annotations

import math

import pytest

import chipbench_fixture as fx

import trace_reduce

TRACE = fx.CHIP / "testdata" / "engine_ticks.xplane.pb"
# first harness span start .. last harness span end, in ns
WINDOW = (45511216.0, 70700823.0)


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.reduce(str(TRACE), window=WINDOW)


def test_busy_and_idle(trace):
    assert list(trace.devices) == [0]
    assert math.isclose(trace.window_s, 25189607e-9)
    assert math.isclose(trace.busy_s, 193646e-9)
    idle = sum(s for _, s in trace.idle_by_host())
    assert math.isclose(idle, trace.window_s - trace.busy_s)
    labels = {k for k, _ in trace.idle_by_host()}
    assert labels <= {"engine.tick", "gen.submit", "read", "other"}
    assert "engine.tick" in labels


@pytest.mark.parametrize("kind,n,total_ns", [
    ("prefill", 3, 74828), ("decode", 6, 109204), ("_argmax", 9, 7384)])
def test_dispatches_per_program(trace, kind, n, total_ns):
    d = trace.dispatches(kind)
    assert len(d) == n
    assert math.isclose(sum(d), total_ns * 1e-9)


def test_top_ops_name_their_program(trace):
    top = trace.top_ops()
    assert 0 < len(top) <= 10
    assert all(":" in name and not name.split(":")[-1].startswith("%while")
               for name, _ in top)
    assert sum(s for _, s in top) <= trace.busy_s + 1e-9


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]
