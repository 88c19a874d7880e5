"""The DeepSeek-V2 model code (``code/deepseek_v2.py``) on the CPU, on a
tiny configuration of its own (``fixtures/configs/tiny-deepseek.json``:
MLA with YaRN, a dense layer, then two expert layers holding 4 of 16
experts, gates not renormalised).  The program served through
``ServingEngine`` (prefill, a resumed chunk, then decode through the
paged latent pool) agrees with the plain float32 reference on logits;
the cell runs through ``run.main`` and its fp8 control reads above the
limit; the operation counts at the published widths; the expert roofline
reader on a synthetic trace."""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_fixture as fx

import registry
import weightgen
from trace_reduce import Device, Trace

CELL = "tiny-deepseek.tiny-reasoning"
METRICS = ("out_tokens_per_s", "mfu.decode.reasoning_wide",
           "expert_roofline_pct.reasoning_wide")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    tmp = fx.make_checkout(tmp_path_factory.mktemp("checkout"))
    spec = json.loads((tmp / "BENCHMARK.json").read_text())
    conf = json.loads((fx.FIXTURES / "configs" / "tiny-deepseek.json")
                      .read_text())
    spec["configs"].append({
        "name": "tiny-deepseek", "source": conf["source"],
        "file": "benchmarks/chip/configs/tiny-deepseek.json",
        "reduced": conf["reduced"],
        "why": "a CPU test of the DeepSeek-V2 model code"})
    spec["workloads"].append({
        "name": CELL, "config": "tiny-deepseek", "traffic": "tiny-reasoning",
        "chips": 1, "why": "a CPU test of the DeepSeek-V2 model code"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in METRICS:
            m["workloads"].append(CELL)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return tmp


@pytest.fixture
def bench(checkout, monkeypatch):
    monkeypatch.setattr(registry, "ROOT", checkout)
    monkeypatch.setattr(registry, "HERE", checkout / "benchmarks" / "chip")
    return registry.Bench()


def _served_logits(code, conf, dtype, params, prompts):
    from repro.serve import Request, ServeConfig, ServingEngine
    arch = code.arch(conf).with_(dtype=dtype)
    sc = ServeConfig(**conf["serve"], max_new_tokens=6, max_seq=40,
                     record_logits=True)
    params = jax.tree.map(lambda x: x.astype(dtype)
                          if x.dtype == jnp.bfloat16 else x, params)
    out = ServingEngine(arch, params, sc).run(
        [Request(i, p) for i, p in enumerate(prompts)])
    return {r.rid: (r.out_tokens, np.stack(r.logits).astype(np.float32))
            for r in out}


def test_served_logits_agree_with_the_reference(bench):
    """Prefill (one prompt over two chunks, so one resumed) and decode
    through the paged latent pool, the program in float32, against the
    reference's full forward over the same tokens."""
    code = bench.code("tiny-deepseek")
    conf = bench.config("tiny-deepseek")
    m = conf["model"]
    params = weightgen.draw(code.leaf_table(m), 3)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, 500, n)) for n in (21, 5, 11)]
    served = _served_logits(code, conf, jnp.float32, params, prompts)
    vocab = m["vocab_size"]
    head = params["lm_head"].astype(jnp.float32)[:, :vocab]
    worst = 0.0
    for i, p in enumerate(prompts):
        toks, got = served[i]
        with jax.default_matmul_precision("highest"):
            hid = code.hidden(params, jnp.asarray(p + toks[:-1]), m)
            want = np.asarray(hid @ head)[len(p) - 1:]
        worst = max(worst, float(np.max(np.abs(got[:, :vocab] - want))))
    # logits of O(0.1-1): float32 rounding in another summation order,
    # ~1e-6; a bf16 program misses by ~1e-2 (below)
    assert worst < 2e-4, worst
    bf16 = _served_logits(code, conf, jnp.bfloat16, params, prompts)
    off = max(float(np.max(np.abs(bf16[i][1][:, :vocab]
                                  - served[i][1][:, :vocab])))
              for i in range(3))
    assert off > 10 * 2e-4, off


def test_tiny_cell_runs_correct_through_the_harness(bench, checkout,
                                                    monkeypatch, capsys,
                                                    tmp_path):
    """The cell through ``run.main``: correct, with its end-to-end
    metrics and no failed request."""
    import run
    monkeypatch.setattr(run, "check_device", lambda chips: jax.devices())
    monkeypatch.setattr(run, "enable_cache", lambda: "off")
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    assert run.main(["--workload", CELL, "--seed", str(2**31 + 5),
                     "--seconds", "1.5", "--trace", "0"]) == 0
    res = json.loads(capsys.readouterr()[0].strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert res["failed"] == 0


def test_fp8_control_reads_above_the_limit(bench):
    import control
    limit = bench.checks(CELL)["max_logit_gap"]["limit"]
    for seed in (13, 14):
        r = control.readings(CELL, seed, 1.5, bench)
        assert r["program"] <= limit < r["control"], r


def _published():
    conf = json.loads((fx.CHIP / "configs" / "deepseek-v2-lite-e16.json")
                      .read_text())
    return registry.Bench().code("deepseek-v2-lite-e16"), conf["model"]


def test_decode_counts_match_a_hand_count():
    """DeepSeek-V2-Lite's published widths with 16 of 64 experts held."""
    code, m = _published()
    # per layer MLA: w_q 2048 x 16*192, w_dkv 2048 x 576, w_uk and w_uv
    # 512 x 16*128 each, w_o 16*128 x 2048
    attn = 2048 * 3072 + 2048 * 576 + 2 * 512 * 2048 + 2048 * 2048
    assert code.attn_params(m) == attn == 13_762_560
    expert = 3 * 2048 * 1408
    outside = 27 * attn + 3 * 2048 * 10944 \
        + 26 * (2048 * 64 + 2 * expert) + 2048 * 102400
    flops, nbytes = code.decode_step(m, 48, 40_000)
    # 6 picks a row, a quarter of them held here
    assert flops == 2 * 48 * (outside + 26 * 1.5 * expert) \
        + 2 * 16 * (2 * 512 + 64) * 27 * 40_000
    touched = 16 * (1 - (58 / 64) ** 48)
    small = 27 * (2 * 2048 + 512) + 2048
    assert nbytes == pytest.approx(
        (outside + small + 26 * touched * expert) * 2
        + 27 * 576 * 2 * (40_000 + 48) + 48 * 2048 * 2, rel=1e-12)
    # the held experts: 7.13 of 9.34 GB of weights read at 48 rows
    assert 26 * touched * expert * 2 / 1e9 == pytest.approx(7.13, abs=0.01)
    assert (outside + 26 * touched * expert) * 2 / 1e9 == \
        pytest.approx(9.34, abs=0.01)


def test_expert_dispatch_counts():
    code, m = _published()
    expert = 3 * 2048 * 1408
    flops, nbytes = code.expert_dispatch(m, 26 * 72, 26 * 15.5)
    assert flops == 2 * 26 * 72 * expert
    assert nbytes == (26 * 15.5 * expert + 26 * 72 * 2 * 2048) * 2


def test_expert_roofline_reader_on_a_synthetic_trace(bench):
    """Kernel time from the ops named as the kernel inside the window;
    least time from the host-recorded rows of each step kind at the
    routing that uniform picks give, times that kind's dispatches."""
    import counts
    from peaks import PEAKS
    from serveloop import Loop, Tick
    code = bench.code("deepseek-v2-lite-e16")
    m = bench.config("deepseek-v2-lite-e16")["model"]
    read = bench.reader("expert_roofline_pct.reasoning_wide")
    ms = 1e6
    mods = [("jit_decode(1)", 10 * ms, 40 * ms),
            ("jit_decode(1)", 50 * ms, 80 * ms),
            ("jit_prefill(2)", 85 * ms, 99 * ms)]
    ops = [("%gmm.1", 12 * ms, 20 * ms), ("gmm.1", 52 * ms, 60 * ms),
           ("%gmm.2", 86 * ms, 96 * ms), ("fusion.3", 20 * ms, 30 * ms),
           ("%gmm_staged", 30 * ms, 31 * ms),     # another op's name
           ("gmm.1", 120 * ms, 130 * ms)]         # outside the window
    trace = Trace((0.0, 100 * ms), {0: Device(ops, mods)}, [])
    ticks = [Tick(0.0, 0.04, 48, 9000, 0, 0, 48, 1),
             Tick(0.05, 0.08, 46, 8000, 0, 0, 46, 1),
             Tick(0.085, 0.099, 0, 0, 300, 0, 1, 0),
             Tick(0.2, 0.3, 48, 9000, 0, 0, 48, 1)]   # after the window

    class Run:
        pass
    run = Run()
    run.trace, run.code, run.model = trace, code, m
    run.loop = Loop([], ticks, (0.0, 0.1), 0.3, {}, (0.0, 0.1))
    run.device_kind = "TPU v5 lite"
    pk = PEAKS["TPU v5 lite"]

    def least(rows):
        return counts.roofline_s(
            *code.expert_dispatch(m, *code.expected_routing(m, rows)), pk)
    want = (least(48) + least(46)) + least(300)
    assert read(run) == pytest.approx(100 * want / 26e-3, rel=1e-12)
    picks, touched = code.expected_routing(m, 48)
    assert picks == 26 * 48 * 6 * 16 / 64
    assert touched == pytest.approx(26 * 16 * (1 - (58 / 64) ** 48))
    run.trace = Trace((0.0, 100 * ms), {0: Device(ops[3:5], mods)}, [])
    assert read(run) is None          # no kernel in the window
    assert math.isfinite(want)
