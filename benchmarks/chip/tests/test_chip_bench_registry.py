"""BENCHMARK.json against the contract it is written to, and the pieces
the harness finds by name: configurations, mixes, limits, readers,
the traffic generator, and each configuration's model code: the weight
layout, the reference and the operation counts."""
from __future__ import annotations

import hashlib
import json
import math
import re

import numpy as np
import pytest

import chipbench_fixture as fx

import counts
import loadgen
import registry
import weightgen

SPEC = json.loads((fx.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_keys_names_and_units():
    assert set(SPEC) == KEYS["top"]
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[kind]]
        assert len(names) == len(set(names)), kind
        for e in SPEC[kind]:
            extra = {"workloads"} if kind in ("end_to_end", "per_layer") else set()
            assert KEYS[kind] <= set(e) <= KEYS[kind] | extra, e
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
            for text in ("why", "layer", "source"):
                if text in e:
                    assert 1 <= len(e[text]) <= 200 and "\n" not in e[text]
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for c in SPEC["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"] for m in SPEC["end_to_end"]} >= {"setup_s"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["paths"] == ["benchmarks/chip"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    bench = registry.Bench()
    w = bench.cell(cell)
    conf = bench.config(w["config"])
    assert conf["source"] == next(c["source"] for c in SPEC["configs"]
                                  if c["name"] == w["config"])
    for key in ("reduced", "assumed", "departures", "deployment", "serve"):
        assert key in conf, key
    assert conf["reduced"] == next(c["reduced"] for c in SPEC["configs"]
                                   if c["name"] == w["config"])
    loadgen.check(bench.traffic(w["traffic"]))
    assert bench.checks(cell)["max_logit_gap"]["limit"] > 0
    for trace in (False, True):
        for m in bench.metrics_for(cell, trace):
            assert callable(bench.reader(m["name"]))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_what_its_metrics_move(cell):
    bench = registry.Bench()
    e2e = {m["name"] for m in bench.metrics_for(cell, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = bench.metrics_for(cell, True)
    assert layer
    for m in layer:
        assert m["moves"] in e2e, (cell, m["name"])
    for m in SPEC["per_layer"]:
        for c in m.get("workloads", []):
            assert m["moves"] in {e["name"] for e in bench.metrics_for(c, False)}


def test_per_layer_layers_name_one_layer_each():
    for m in SPEC["per_layer"]:
        assert m["layer"] in ("scheduler", "page pool", "model step", "device")


MIX = {"arrivals": {"process": "poisson", "rate_per_s": 3.0},
       "prompt_tokens": {"dist": "lognormal", "median": 64, "sigma": 1.0,
                         "min": 4, "max": 512},
       "output_tokens": 8, "warmup_s": 5, "follow": "window_arrivals",
       "grace_s": 10, "check_sample": 2}


def test_traffic_repeats_for_a_seed_and_changes_text_for_another():
    a = loadgen.schedule(MIX, 2**31 + 5, 20, 1000).first
    b = loadgen.schedule(MIX, 2**31 + 5, 20, 1000).first
    c = loadgen.schedule(MIX, 6, 20, 1000).first
    key = lambda s: [(x.due, x.prompt, x.in_window) for x in s]  # noqa: E731
    assert key(a) == key(b)
    assert key(a) != key(c)
    # another seed: the same arrivals and prompt lengths, another text, so
    # every seed serves the same work.
    shape = lambda s: [(x.due, len(x.prompt), x.in_window) for x in s]  # noqa
    assert shape(a) == shape(c)
    assert [x.prompt for x in a] != [x.prompt for x in c]
    assert sum(x.in_window for x in a) == round(3.0 * 20)
    assert max(x.due for x in a if x.in_window) <= 25.0
    assert all(4 <= len(x.prompt) <= 512 for x in a)


def test_rate_pattern_keeps_the_mean_and_shapes_the_rate():
    # 5x the base rate for 2 s in every 10 s, at the same mean rate
    mix = dict(MIX, arrivals={"process": "poisson", "rate_per_s": 3.0,
                              "pattern": [[2, 5], [8, 1]]})
    s = loadgen.schedule(mix, 1, 40, 1000)
    win = [x.due for x in s.first if x.in_window]
    assert len(win) == round(3.0 * 40)
    burst = sum(1 for t in win if t % 10 < 2)
    # a burst second carries 5 / 1.8 of the mean rate: 8 s of 40 hold
    # 5 x 8 / (5 x 8 + 32) of the arrivals
    assert abs(burst / len(win) - 40 / 72) < 0.12
    assert loadgen.schedule(mix, 1, 40, 1000).first[7].due == s.first[7].due


def test_sessions_share_prefixes_and_carry_their_context():
    mix = dict(MIX, prefix={"count": 2, "tokens": 30},
               turns={"count": 3, "think_s": 0.5},
               prompt_tokens={"dist": "uniform", "min": 5, "max": 9})
    s = loadgen.schedule(mix, 9, 20, 1000)
    heads = s.first
    assert {tuple(x.prompt[:30]) for x in heads} == \
        {tuple(p) for p in s.prefixes}
    assert all(35 <= len(x.prompt) <= 39 for x in heads)
    answer = [1] * 8
    second = s.next(heads[0], 4.0, answer)
    assert second.due == 4.5 and second.place == 1
    assert second.prompt[:len(heads[0].prompt) + 8] == heads[0].prompt + answer
    third = s.next(second, 6.0, answer)
    assert s.next(third, 7.0, answer) is None
    # the same request whenever it comes
    again = loadgen.schedule(mix, 9, 20, 1000).next(heads[0], 9.0, answer)
    assert again.prompt == second.prompt
    assert loadgen.max_prompt_tokens(mix) == 30 + 3 * 9 + 2 * 8 >= \
        len(third.prompt)


def test_closed_loop_clients_send_one_request_after_another():
    mix = dict(MIX, arrivals={"process": "closed", "clients": 3,
                              "think_s": 0.25})
    s = loadgen.schedule(mix, 2, 20, 1000)
    assert [x.due for x in s.first] == [0.0, 0.0, 0.0]
    nxt = s.next(s.first[1], 5.0, [3] * 8)
    assert (nxt.chain, nxt.place, nxt.due, nxt.in_window) == (1, 1, 5.25, True)
    # one request a session: no context carried
    assert len(nxt.prompt) <= 512
    assert loadgen.max_prompt_tokens(mix) == 512


def test_failed_requests_are_misses_at_infinity():
    import importlib
    import types
    reqs = [types.SimpleNamespace(in_window=True, due=0.0, first_t=0.1 * i,
                                  last_t=0.1 * i + 1.0, seen=11, done=True,
                                  failed=False) for i in range(1, 11)]
    run = types.SimpleNamespace(loop=types.SimpleNamespace(requests=reqs))
    readings = importlib.import_module("readings")
    bench = registry.Bench()
    read = {m: bench.reader(m) for m in ("ttft_p90_ms", "tpot_p90_ms")}
    assert math.isclose(read["ttft_p90_ms"](run), 900.0)
    assert math.isclose(read["tpot_p90_ms"](run), 100.0)
    # one miss: the 9th of 10 moves up a rank; two: the p90 is a miss
    reqs[0].failed, reqs[0].done = True, False
    assert math.isclose(read["ttft_p90_ms"](run), 1000.0)
    assert readings.ttft_s(reqs[0]) == readings.tpot_s(reqs[0]) == math.inf
    reqs[1].first_t, reqs[1].done = None, False
    assert read["ttft_p90_ms"](run) is None
    assert read["tpot_p90_ms"](run) is None


@pytest.mark.parametrize("bad", [{"follow": "forever"}, {"burst": 2},
                                 {"arrivals": {"process": "gamma"}}])
def test_mix_the_generator_cannot_run_is_refused(bad):
    with pytest.raises(ValueError):
        loadgen.check(dict(MIX, **bad))


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return fx.make_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.fixture
def fixture_bench(checkout, monkeypatch):
    """The registry of the checkout: the real entries and the fixture's."""
    monkeypatch.setattr(registry, "ROOT", checkout)
    monkeypatch.setattr(registry, "HERE", checkout / "benchmarks" / "chip")
    return registry.Bench()


def test_weight_layout_matches_the_program(fixture_bench):
    """Every configuration, the fixtures' included, through its own model
    code: the drawn tree has the program's structure and shapes."""
    import jax
    from repro.models import abstract_params
    import run
    names = [c["name"] for c in fixture_bench.spec["configs"]]
    assert {"tiny-qwen3", "tiny-mla"} < set(names)
    for name in names:
        code = fixture_bench.code(name)
        conf = fixture_bench.config(name)
        arch, _ = run.build(code, conf, {"output_tokens": 8, "prompt_tokens":
                                         {"max": 64}})
        want = abstract_params(arch)
        table = code.leaf_table(conf["model"])
        got = weightgen._nest({k: np.zeros(0) for k in table})
        assert jax.tree.structure(got) == jax.tree.structure(want), name
        got = weightgen._nest({k: v[0] for k, v in table.items()})
        assert jax.tree.leaves(got, is_leaf=lambda x: isinstance(x, tuple)) \
            == [tuple(x.shape) for x in jax.tree.leaves(want)], name


def test_counts_for_qwen2_5_3b_match_a_hand_count():
    code = registry.Bench().code("qwen2.5-3b")
    m = json.loads((fx.CHIP / "configs" / "qwen2.5-3b.json").read_text())["model"]
    # per layer: q 2048x2048, k and v 2048x256 each, o 2048x2048,
    # gate/up/down 3 x 2048x11008
    per_layer = 2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008
    assert code.layer_params(m) == per_layer == 77_070_336
    head = 2048 * 151936
    # one decode step of 16 rows attending 16 x 1000 positions
    flops, nbytes = code.decode_step(m, 16, 16_000)
    assert flops == 2 * 16 * (36 * per_layer + head) + 4 * 16 * 128 * 36 * 16_000
    small = 2 * 2048 + (16 + 2 + 2) * 128           # norms, QKV biases
    kv_row = 36 * 2 * 2 * 128 * 2                  # 36,864 bytes
    weights = (36 * (per_layer + small) + 2048 + head) * 2
    assert nbytes == weights + kv_row * (16_000 + 16) + 16 * 2048 * 2
    assert math.isclose(weights / 1e9, 6.1719, rel_tol=1e-4)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert counts.roofline_s(flops, nbytes, peaks) == nbytes / 819e9


def test_counts_for_mla_match_a_hand_count(fixture_bench):
    """The fixture's MLA counts at DeepSeek-V2-Lite's dense-layer widths:
    one layer, 16 heads, latent 512, nope/rope/v 128/64/128."""
    code = fixture_bench.code("tiny-mla")
    m = {"hidden_size": 2048, "intermediate_size": 10944,
         "num_attention_heads": 16, "kv_lora_rank": 512,
         "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
         "num_hidden_layers": 1, "vocab_size": 102400}
    # w_q 2048 x 16*192, w_dkv 2048 x 576, w_uk and w_uv 512 x 16*128
    # each, w_o 16*128 x 2048, gate/up/down 3 x 2048 x 10944
    per_layer = 2048 * 3072 + 2048 * 576 + 2 * 512 * 2048 + 2048 * 2048 \
        + 3 * 2048 * 10944
    assert code.layer_params(m) == per_layer == 81_002_496
    head = 2048 * 102400
    flops, nbytes = code.decode_step(m, 4, 4_000)
    # scores against 576 latent+rope columns, values in 512, per head
    assert flops == 2 * 4 * (per_layer + head) + 2 * 16 * 1088 * 4_000
    weights = (per_layer + 2 * 2048 + 512 + 2048 + head) * 2
    assert nbytes == weights + 576 * 2 * (4_000 + 4) + 4 * 2048 * 2


# Recorded from the tree before the model code moved into ``code/``: the
# digest of the weights ``weightgen`` drew for tiny-qwen3, and the float32
# gaps (program, fp8 control) the reference read over one fixed request.
# The weights are integer draws and elementwise arithmetic: the same bits
# on any host.  The moved reference gave the recorded gaps to the bit on
# the host they were recorded on; XLA's CPU dots sum in an order that
# depends on the host (1.8e-7 between two instruction-set caps), so they
# are held to 1e-5, far below what a change of the reference's
# mathematics moves (bf16 inputs to its projections: 1.1e-3).
PROMPT = [3, 141, 59, 265, 358, 97, 93, 238, 462, 64, 338, 327, 95, 28]
SERVED = [84, 197, 169, 399, 375, 105, 320, 97, 494, 459, 230]
DENSE_RECORD = {
    2**31 + 11: (
        "2f62378d70dc11b7bf7290f85143ad5af19a8d264deb72c038af93446f88e619",
        [0.8866314888000488, 0.5996506810188293, 0.2758978009223938,
         0.6608873605728149, 0.6428309082984924, 0.6157194375991821,
         0.448322057723999, 0.5733518600463867, 0.7107202410697937,
         0.5665387511253357, 0.5221256613731384],
        [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0]),
    7: (
        "6b53abfd37abc41b438ee4f772b6df16a33d8950f4b1c86e2e02d2913808e161",
        [0.35070866346359253, 0.37547537684440613, 0.421529084444046,
         0.5926416516304016, 0.5397074818611145, 0.4140439033508301,
         0.6405903697013855, 0.5941125750541687, 0.5300873517990112,
         0.42788267135620117, 0.221110999584198],
        [0.0, 0.0, 0.0, 0.0, 0.0, 0.019796103239059448, 0.0, 0.0, 0.0,
         0.0, 0.0]),
}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(DENSE_RECORD))
def test_dense_weights_and_reference_keep_their_numbers(fixture_bench, seed):
    import jax
    code = fixture_bench.code("tiny-qwen3")
    m = fixture_bench.config("tiny-qwen3")["model"]
    params = weightgen.draw(code.leaf_table(m), seed)
    weights, gaps, control = DENSE_RECORD[seed]
    assert _digest(jax.tree.leaves(params)) == weights
    got = code.served_gaps(params, m, PROMPT, SERVED, 32)
    np.testing.assert_allclose(got, np.float32(gaps), rtol=0, atol=1e-5)
    got = code.served_gaps(params, m, PROMPT, SERVED, 32, control=True)
    np.testing.assert_allclose(got, np.float32(control), rtol=0, atol=1e-5)


# Recorded likewise: the digest of each cell configuration's leaf table
# (names in order, shapes, dtypes, kinds, scales) and decode_step at
# three (rows, context rows) points.
DENSE_TABLES = {
    "qwen2.5-3b": (
        "96a2bac5a2624e795edf58345553a562c1d3fa2efc77bf336c27f21cfa4398ac",
        [(6171688960, 6171955200), (76533989376, 6482026496),
         (103460896768, 6762356736)]),
    "qwen3-8b-18L": (
        "6d4dec082165631661a94d8b83daa91a584e89fc18dc21feaecaa6f37270e4da",
        [(8190722048, 8190895104), (100762386432, 8811037696),
         (135765426176, 9371698176)]),
}


@pytest.mark.parametrize("config", sorted(DENSE_TABLES))
def test_dense_tables_and_counts_keep_their_numbers(config):
    bench = registry.Bench()
    code, m = bench.code(config), bench.config(config)["model"]
    table = code.leaf_table(m)
    rows = [[k, list(v[0]), v[1], v[2], v[3]] for k, v in table.items()]
    digest, steps = DENSE_TABLES[config]
    assert hashlib.sha256(json.dumps(rows).encode()).hexdigest() == digest
    assert [code.decode_step(m, r, c) for r, c in
            ((1, 1), (12, 12 * 700), (16, 16_000))] == steps


MODEL_KEYS = ("num_hidden_layers", "num_key_value_heads", "head_dim",
              "intermediate_size", "qk_norm", "attention_bias",
              "tie_word_embeddings")


def test_only_model_code_reads_the_model_block():
    """Outside ``code/`` no harness file names a ``model``-block key but
    ``vocab_size``, nor reaches model code but through the registry."""
    files = [f for f in fx.CHIP.rglob("*.py")
             if "code" not in f.relative_to(fx.CHIP).parts[:-1]
             and "tests" not in f.relative_to(fx.CHIP).parts]
    assert fx.CHIP / "run.py" in files and len(files) > 20
    for f in files:
        text = f.read_text()
        for word in MODEL_KEYS + ("refmodel", "counts.decode_step"):
            assert word not in text, (f, word)
