"""Serving engine vs teacher-forced oracle + IOTLB containment."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.iotlb import Iotlb, IotlbFault, Window
from repro.core.quant import QuantConfig
from repro.models import ArchConfig, forward, init_params
from repro.models.model import quantize_for_serving
from repro.serve import Request, ServeConfig, ServingEngine

CFG = ArchConfig(name="srv", family="dense", n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=100,
                 decode_margin=32)

# one reduced config per cache-carrying model family (f32 so the oracle
# argmax comparison is free of bf16 tie noise).
FAMILY_CFGS = {
    "dense": CFG.with_(dtype=jnp.float32),
    "moe": ArchConfig(
        name="srv_moe", family="moe", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=0, vocab_size=100, n_experts=4, top_k=2,
        d_ff_expert=64, capacity_factor=8.0, decode_margin=32,
        dtype=jnp.float32),
    "mla": ArchConfig(
        name="srv_mla", family="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=128, vocab_size=100, kv_lora_rank=32,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, decode_margin=32,
        pattern=(("scan", "mla_mlp", 2),), dtype=jnp.float32),
    "ssm": ArchConfig(
        name="srv_ssm", family="ssm", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=128, vocab_size=100, ssm_state=16,
        ssm_headdim=32, ssm_chunk=8, decode_margin=32,
        pattern=(("scan", "mamba", 2),), dtype=jnp.float32),
    "xlstm": ArchConfig(
        name="srv_xlstm", family="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=128, vocab_size=100, ssm_chunk=8,
        decode_margin=32, pattern=(("scan", "mlstm", 1),
                                   ("scan", "slstm", 1)),
        dtype=jnp.float32),
    "hybrid": ArchConfig(
        name="srv_hyb", family="hybrid", n_layers=4, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab_size=100, ssm_state=16,
        ssm_headdim=32, ssm_chunk=8, decode_margin=32,
        pattern=(("group", (("mamba", 1), ("shared_attn", 1)), 2),),
        dtype=jnp.float32),
}


def _oracle(params, cfg, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        lg, _, _ = forward(params, jnp.asarray(toks, jnp.int32)[None, :],
                           cfg, mode="train")
        toks.append(int(jnp.argmax(lg[0, -1])))
    return toks[len(prompt):]


def test_engine_matches_oracle_mixed_lengths():
    params = init_params(CFG, jax.random.PRNGKey(0))
    reqs = [Request(0, [5, 7, 11]), Request(1, [3, 1, 4, 1, 5, 9]),
            Request(2, [2, 7])]
    eng = ServingEngine(CFG, params, ServeConfig(
        max_batch=2, max_prompt=16, max_new_tokens=5))
    out = eng.run(reqs)
    for r in out:
        assert r.done
        assert r.out_tokens == _oracle(params, CFG, r.prompt, 5), r.rid


def test_engine_packed_weights_w8():
    params = init_params(CFG, jax.random.PRNGKey(0))
    q = QuantConfig(mode="wo", w_bits=8, use_kernel=False)
    cfg_q = CFG.with_(quant=q)
    qparams, n = quantize_for_serving(cfg_q, params)
    assert n > 0
    out = ServingEngine(cfg_q, qparams, ServeConfig(
        max_batch=2, max_prompt=16, max_new_tokens=4)).run(
        [Request(0, [5, 7, 11])])
    assert len(out[0].out_tokens) == 4


@pytest.mark.parametrize("family", sorted(FAMILY_CFGS))
def test_chunked_prefill_matches_oracle_all_families(family):
    """Chunked prefill == teacher-forced oracle, token for token, with
    mixed prompt lengths and slot reuse after release (4 reqs, 2 slots)."""
    cfg = FAMILY_CFGS[family]
    params = init_params(cfg, jax.random.PRNGKey(0))
    reqs = [Request(0, [5, 7, 11]), Request(1, [3, 1, 4, 1, 5, 9]),
            Request(2, [2, 7]), Request(3, [9, 8, 7, 6, 5, 4, 3, 2])]
    eng = ServingEngine(cfg, params, ServeConfig(
        max_batch=2, max_prompt=16, max_new_tokens=3))
    out = eng.run(reqs)
    assert len(out) == len(reqs)
    for r in out:
        assert r.done and not r.failed
        assert r.out_tokens == _oracle(params, cfg, r.prompt, 3), \
            (family, r.rid)


def test_admission_wave_is_single_prefill_dispatch():
    """All free slots are admitted in ONE chunked-prefill call."""
    params = init_params(CFG, jax.random.PRNGKey(0))
    eng = ServingEngine(CFG, params, ServeConfig(
        max_batch=4, max_prompt=16, max_new_tokens=2))
    calls = []
    orig = eng._prefill
    eng._prefill = lambda *a: (calls.append(1), orig(*a))[1]
    out = eng.run([Request(i, [2 + i, 3, 5]) for i in range(4)])
    assert len(calls) == 1          # 4 admissions, one dispatch
    assert all(r.done and len(r.out_tokens) == 2 for r in out)


def test_run_returns_completion_order():
    params = init_params(CFG, jax.random.PRNGKey(0))
    eng = ServingEngine(CFG, params, ServeConfig(
        max_batch=2, max_prompt=16, max_new_tokens=4))
    reqs = [Request(0, [5, 7, 11]), Request(1, [3, 1, 4]), Request(2, [2, 7])]
    out = eng.run(reqs)
    assert len(out) == 3 and {r.rid for r in out} == {0, 1, 2}
    # the late-admitted request (no slot free at t=0) finishes last.
    assert out[-1].rid == 2


def test_sampled_decode_deterministic_under_seed():
    """temperature=0 is greedy (oracle tests); sampled decode is
    reproducible bit-for-bit under a fixed engine seed."""
    params = init_params(CFG, jax.random.PRNGKey(0))

    def go():
        eng = ServingEngine(CFG, params, ServeConfig(
            max_batch=2, max_prompt=16, max_new_tokens=5, temperature=0.8,
            seed=123))
        out = eng.run([Request(0, [5, 7, 11]), Request(1, [3, 1, 4])])
        return {r.rid: r.out_tokens for r in out}

    assert go() == go()


def test_moe_chunk_prefill_padding_invariant_at_tight_capacity():
    """Padding tokens must not consume expert capacity: the same prompts
    produce identical outputs whether the chunk carries 16 or 64 columns
    of padding, at the DEFAULT capacity factor."""
    cfg = FAMILY_CFGS["moe"].with_(capacity_factor=1.25)
    params = init_params(cfg, jax.random.PRNGKey(0))
    reqs = lambda: [Request(0, [5, 7, 11, 2]), Request(1, [3, 1, 4])]

    def go(max_prompt):
        eng = ServingEngine(cfg, params, ServeConfig(
            max_batch=2, max_prompt=max_prompt, max_new_tokens=3))
        return {r.rid: r.out_tokens for r in eng.run(reqs())}

    assert go(16) == go(64)


# -- prefill dispatches sized to the slots that owe rows ---------------------

# every cache leaf a page pool, and 4 slots of 128-row chunks are 512 rows:
# the engine dispatches prefill in groups of PREFILL_ROWS // 128 = 2 slots.
COMPACT = dict(max_batch=4, max_prompt=128, max_seq=288, page_size=16,
               max_new_tokens=3, record_logits=True)


def _recorded(eng):
    """Wrap ``eng._prefill``: each dispatch appends (tokens shape, lengths,
    resumed, page table passed, the engine's page table then) to the
    returned list."""
    calls, orig = [], eng._prefill

    def rec(params, cache, toks, lens, *paged):
        pages, offs = paged or (None, None)
        calls.append((toks.shape, np.asarray(lens).tolist(),
                      offs is not None,
                      None if pages is None else np.asarray(pages),
                      None if eng.alloc is None
                      else eng.alloc.page_table.copy()))
        return orig(params, cache, toks, lens, *paged)
    eng._prefill = rec
    return calls


def _first_logits(params, cfg, prompt):
    lg, _, _ = forward(params, jnp.asarray(prompt, jnp.int32)[None, :], cfg,
                       mode="train")
    return np.asarray(lg[0, -1])


@pytest.mark.parametrize("family", ["dense", "mla", "moe"])
def test_prefill_wave_goes_out_in_groups_of_its_owing_slots(family):
    """A wave of four fresh slots is two dispatches of (2, max_prompt);
    the next tick's resumed chunks of slots 1 and 3 (not adjacent) are
    one, carrying those two slots' rows of the page table.  Tokens match
    the teacher-forced oracle, and each request's first-token logits are
    its own prompt's, not a neighbour row's."""
    cfg = FAMILY_CFGS[family]
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, ServeConfig(**COMPACT))
    assert eng._prefill_group == 2
    calls = _recorded(eng)
    lens = {0: 3, 1: 150, 2: 5, 3: 140}
    prompts = {rid: [1 + (7 * rid + 3 * k) % 97 for k in range(n)]
               for rid, n in lens.items()}
    out = eng.run([Request(rid, p) for rid, p in prompts.items()])
    assert [c[:3] for c in calls] == [
        ((2, 128), [3, 128], False), ((2, 128), [5, 128], False),
        ((2, 128), [22, 12], True)]
    for (_, _, _, pages, table), slots in zip(calls, ([0, 1], [2, 3],
                                                      [1, 3])):
        np.testing.assert_array_equal(pages, table[slots])
    assert len(out) == 4
    for r in out:
        assert r.done and not r.failed
        assert r.out_tokens == _oracle(params, cfg, r.prompt, 3), r.rid
        np.testing.assert_allclose(
            r.logits[0], _first_logits(params, cfg, r.prompt),
            rtol=1e-4, atol=1e-4, err_msg=f"rid {r.rid}")


@pytest.mark.parametrize("family", ["dense", "mla", "moe"])
def test_prefill_group_logits_bitwise_alone_or_in_a_wave(family):
    """Every dispatch has the fixed (group, max_prompt) shape, so a
    request's first-token logits are bitwise the same whether it is
    admitted alone (row 0 of the only dispatch) or in a wave of three
    (row 1 of the first dispatch, or row 0 of the second, padded)."""
    cfg = FAMILY_CFGS[family]
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, ServeConfig(**COMPACT))
    calls = _recorded(eng)
    prompts = [[4, 9, 2, 7, 1], [8, 3, 3, 5, 6, 2, 9], [6, 1, 7]]
    alone = {rid: eng.run([Request(rid, prompts[rid])])[0]
             for rid in (1, 2)}
    assert [c[1] for c in calls] == [[7, 0], [3, 0]]
    del calls[:]
    wave = {r.rid: r for r in eng.run(
        [Request(rid, p) for rid, p in enumerate(prompts)])}
    assert [c[:2] for c in calls] == [((2, 128), [5, 7]),
                                      ((2, 128), [3, 0])]
    for rid in (1, 2):
        assert wave[rid].out_tokens == alone[rid].out_tokens, rid
        np.testing.assert_array_equal(wave[rid].logits[0],
                                      alone[rid].logits[0], err_msg=str(rid))
    assert wave[0].out_tokens == _oracle(params, cfg, prompts[0], 3)


@pytest.mark.parametrize("family, paged", [("ssm", True), ("dense", False)],
                         ids=["ssm-paged", "dense-contiguous"])
def test_prefill_keeps_whole_batch_with_per_slot_state(family, paged):
    """Recurrent state and the contiguous cache have a batch axis, so
    their prefill still dispatches (max_batch, max_prompt), slot i in
    row i."""
    cfg = FAMILY_CFGS[family]
    params = init_params(cfg, jax.random.PRNGKey(0))
    sc = dict(COMPACT, paged=paged)
    if not paged:
        del sc["max_seq"], sc["page_size"]     # paged geometry only
    eng = ServingEngine(cfg, params, ServeConfig(**sc))
    assert eng._prefill_group == 4
    calls = _recorded(eng)
    reqs = [Request(0, [5, 7, 11]), Request(1, [3, 1, 4, 1, 5, 9]),
            Request(2, [2, 7])]
    out = eng.run(reqs)
    assert [c[:2] for c in calls] == [((4, 128), [3, 6, 2, 0])]
    for r in out:
        assert r.out_tokens == _oracle(params, cfg, r.prompt, 3), r.rid


def test_empty_prompt_rejected_cleanly():
    params = init_params(CFG, jax.random.PRNGKey(0))
    eng = ServingEngine(CFG, params, ServeConfig(
        max_batch=2, max_prompt=16, max_new_tokens=4))
    out = eng.run([Request(0, []), Request(1, [5, 7, 3])])
    empty = next(r for r in out if r.rid == 0)
    assert empty.failed and empty.out_tokens == []
    good = next(r for r in out if r.rid == 1)
    assert not good.failed
    assert good.out_tokens == _oracle(params, CFG, good.prompt, 4)


def test_strict_fault_mid_wave_leaves_engine_consistent():
    """A strict IOTLB fault during a multi-request wave must not leave
    half-placed slots behind, and vetted requests go back to pending."""
    params = init_params(CFG, jax.random.PRNGKey(0))
    eng = ServingEngine(CFG, params, ServeConfig(
        max_batch=2, max_prompt=8, max_new_tokens=4))
    bad = Request(1, list(range(2, 16)))
    pending = [Request(0, [5, 7, 3]), bad]
    with pytest.raises(IotlbFault, match="request 1"):
        eng.admit_many(pending)
    assert all(s is None for s in eng.slots)       # nothing half-placed
    assert [r.rid for r in pending] == [0]         # vetted req restored
    # the faulting request got a terminal signal, not silence.
    assert bad.failed and bad.done and bad in eng.completed
    out = eng.run(pending)                         # engine still serves
    assert out[0].out_tokens == _oracle(params, CFG, [5, 7, 3], 4)


def test_engine_overlong_prompt_faults_strict():
    """Prompt chunk + decode tail exceeding the slot window raises."""
    params = init_params(CFG, jax.random.PRNGKey(0))
    eng = ServingEngine(CFG, params, ServeConfig(
        max_batch=2, max_prompt=8, max_new_tokens=4))
    with pytest.raises(IotlbFault):
        eng.admit(Request(0, list(range(2, 16))))   # 14 + 4 > 12 window


def test_engine_overlong_prompt_rejected_nonstrict_no_corruption():
    """Non-strict: fault recorded, request rejected, neighbor unharmed."""
    params = init_params(CFG, jax.random.PRNGKey(0))
    eng = ServingEngine(CFG, params, ServeConfig(
        max_batch=2, max_prompt=8, max_new_tokens=4, strict_iotlb=False))
    bad = Request(7, list(range(2, 22)))
    good = Request(8, [5, 7, 3])
    out = eng.run([bad, good])
    bad_out = next(r for r in out if r.rid == 7)
    assert bad_out.failed and bad_out.done and bad_out.out_tokens == []
    assert eng.iotlb.faults and eng.iotlb.faults[-1].kind == "miss"
    good_out = next(r for r in out if r.rid == 8)
    assert not good_out.failed
    assert good_out.out_tokens == _oracle(params, CFG, good.prompt, 4)


def test_iotlb_permissions_and_containment():
    tlb = Iotlb()
    tlb.program(Window("a", virt_base=0, size=64, phys_base=1000))
    tlb.program(Window("ro", virt_base=64, size=64, phys_base=2000,
                       writable=False))
    assert tlb.translate(8, 16, write=True) == (1008, 16)
    with pytest.raises(IotlbFault):
        tlb.translate(70, 8, write=True)           # write to RO window
    with pytest.raises(IotlbFault):
        tlb.translate(130, 8, write=False)         # unmapped
    # graceful containment: non-strict records the fault, returns None
    assert tlb.translate(130, 8, write=True, strict=False) is None
    assert tlb.faults[-1].kind == "miss"
    n_faults = len(tlb.faults)
    with pytest.raises(IotlbFault):                # overlap rejected
        tlb.program(Window("b", virt_base=32, size=64, phys_base=3000))
    # programming faults are accounted like every other fault path.
    assert len(tlb.faults) == n_faults + 1
    assert tlb.faults[-1].kind == "overlap"


def test_iotlb_capacity_is_32_entries():
    tlb = Iotlb()
    for i in range(32):
        tlb.program(Window(f"w{i}", virt_base=i * 10, size=10,
                           phys_base=i * 10))
    with pytest.raises(IotlbFault):
        tlb.program(Window("w33", virt_base=330, size=10, phys_base=330))
    # the capacity fault is recorded before the raise (host accounting).
    assert tlb.faults and tlb.faults[-1].kind == "capacity"
    assert tlb.faults[-1].start == 330
