"""The harness end to end on the CPU at a tiny size.

The look for a chip is steered here (``run.check_device``), not by a flag
of the program.  The cells are fixtures added to a copy of the benchmark
(``chipbench_fixture``), one of them on model code of its own (MLA in
two stages).  Besides a sound run, each fault the cells can have is
planted under the timed path and must turn ``correct`` false: a token
altered where it is sampled, and a decode step that returns its KV state
unchanged.  The fp8 control must read above the limit too.
"""
from __future__ import annotations

import json
import subprocess
import sys

import pytest

import chipbench_fixture as fx

import jax
import numpy as np


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return fx.make_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.fixture
def harness(checkout, monkeypatch, tmp_path):
    import registry
    import run
    monkeypatch.setattr(registry, "ROOT", checkout)
    monkeypatch.setattr(registry, "HERE", checkout / "benchmarks" / "chip")
    monkeypatch.setattr(run, "check_device", lambda chips: jax.devices())
    monkeypatch.setattr(run, "enable_cache", lambda: "off")
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    return run


def result(run, capsys, seed, trace=0, cell=fx.CELL):
    assert run.main(["--workload", cell, "--seed", str(seed),
                     "--seconds", "1.5", "--trace", str(trace)]) == 0
    out, err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    assert err.strip().splitlines()[-1].startswith("check ")
    assert list(res)[-1] == "checks"
    return res


def test_sound_run_is_correct(harness, capsys):
    res = result(harness, capsys, 2**31 + 11)
    assert res["correct"], res["checks"]
    assert res["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                             "memory_peak_bytes": None}
    assert set(res["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert res["attempted"] >= 4 and res["failed"] == 0


def test_traced_run_reports_per_layer_metrics(harness, capsys):
    res = result(harness, capsys, 5, trace=1)
    assert res["correct"], res["checks"]
    # the CPU trace has no TPU plane: device readers find nothing and are
    # left out; the fixture's new metric and the host-clock one are read.
    assert set(res["metrics"]) == {"queue_wait_ms.chat", "ticks_per_s.tiny"}
    assert res["device"]["window_s"] > 0


def test_throughput_cell(harness, capsys):
    res = result(harness, capsys, 3, cell=fx.THROUGHPUT_CELL)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"out_tokens_per_s", "setup_s"}
    res = result(harness, capsys, 4, trace=1, cell=fx.THROUGHPUT_CELL)
    assert res["correct"], res["checks"]
    # mfu.decode needs the device trace, absent on the CPU
    assert set(res["metrics"]) == {"batch_occupancy_pct.reasoning",
                                   "pool_used_pct.reasoning"}
    occ = res["metrics"]["batch_occupancy_pct.reasoning"]["value"]
    assert 50 < occ <= 100


def test_closed_loop_sessions_cell(harness, capsys, monkeypatch):
    """A mix of another arrival process, with shared prompts and turns
    that carry their context, added as data alone."""
    import serveloop
    loops = []
    drive = serveloop.drive
    monkeypatch.setattr(serveloop, "drive",
                        lambda *a, **k: loops.append(drive(*a, **k)) or
                        loops[-1])
    res = result(harness, capsys, 2**31 + 3, cell=fx.SESSIONS_CELL)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    reqs = {(r.arrival.chain, r.arrival.place): r for r in loops[0].requests}
    assert {c for c, _ in reqs} == {0, 1, 2}
    carried = [(reqs[c, p - 1], r) for (c, p), r in reqs.items() if p % 2]
    assert carried and res["attempted"] >= 3 and res["failed"] == 0
    for prev, r in carried:
        assert r.prompt[:len(prev.prompt) + 8] == prev.prompt + prev.tokens
        assert r.due >= prev.last_t + 0.1


def test_failed_request_is_a_miss_and_fails_the_run(harness, capsys,
                                                    monkeypatch):
    """One request due in the window is rejected on submission: it counts
    as failed, and the run is not correct."""
    from repro.serve import engine
    orig = engine.ServingEngine.submit

    def reject_one(self, req):
        if req.rid != 8:            # the fixture mix's third in the window
            return orig(self, req)
        req.failed = req.done = True
        return engine.RequestHandle(self, req)

    monkeypatch.setattr(engine.ServingEngine, "submit", reject_one)
    res = result(harness, capsys, 8)
    assert not res["correct"]
    assert res["failed"] == res["checks"]["failed"]["value"] == 1


def shift_sampled_tokens(monkeypatch):
    from repro.serve import engine
    orig = engine.ServingEngine._sample

    def shifted(self, logits):
        return (orig(self, logits) + 1) % self.cfg.vocab_size

    monkeypatch.setattr(engine.ServingEngine, "_sample", shifted)


def test_altered_token_is_caught(harness, capsys, monkeypatch):
    shift_sampled_tokens(monkeypatch)
    assert not result(harness, capsys, 7)["correct"]


def test_model_code_of_its_own_runs_correct(harness, capsys):
    """The MLA cell, added with new files alone, serves through two stages
    and its reference passes the program's tokens."""
    res = result(harness, capsys, 2**31 + 12, cell=fx.MLA_CELL)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert res["attempted"] >= 4 and res["failed"] == 0
    assert res["checks"]["max_logit_gap"]["value"] > 0


def test_model_code_of_its_own_catches_an_altered_token(harness, capsys,
                                                         monkeypatch):
    shift_sampled_tokens(monkeypatch)
    res = result(harness, capsys, 9, cell=fx.MLA_CELL)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]


def test_unchanged_decode_state_is_caught(harness, capsys, monkeypatch):
    from repro.serve import engine
    orig = engine.make_paged_decode_step

    def frozen(cfg):
        step = orig(cfg)

        def decode(params, cache, token, pos, pages):
            logits, _ = step(params, cache, token, pos, pages)
            return logits, cache
        return decode

    monkeypatch.setattr(engine, "make_paged_decode_step", frozen)
    assert not result(harness, capsys, 7)["correct"]


def test_fp8_control_reads_above_the_limit(checkout, monkeypatch):
    """The control (the reference in fp8, in the program's place) fails
    the fixture cell's limit on the program's own served requests, which
    pass it."""
    import control
    import registry
    monkeypatch.setattr(registry, "ROOT", checkout)
    monkeypatch.setattr(registry, "HERE", checkout / "benchmarks" / "chip")
    bench = registry.Bench()
    limit = bench.checks(fx.CELL)["max_logit_gap"]["limit"]
    for seed in (1, 2, 3):
        r = control.readings(fx.CELL, seed, 1.5, bench)
        assert r["program"] <= limit < r["control"], r


def test_mla_fp8_control_reads_above_the_limit(checkout, monkeypatch):
    """Likewise for the MLA cell, through its own model code."""
    import control
    import registry
    monkeypatch.setattr(registry, "ROOT", checkout)
    monkeypatch.setattr(registry, "HERE", checkout / "benchmarks" / "chip")
    bench = registry.Bench()
    limit = bench.checks(fx.MLA_CELL)["max_logit_gap"]["limit"]
    for seed in (13, 14):
        r = control.readings(fx.MLA_CELL, seed, 1.5, bench)
        assert r["program"] <= limit < r["control"], r


def test_run_without_a_tpu_exits_nonzero():
    proc = subprocess.run(
        [sys.executable, str(fx.CHIP / "run.py"), "--workload",
         "qwen2.5-3b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=fx.REPO, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""
