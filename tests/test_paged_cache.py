"""Paged KV cache vs the contiguous-cache oracle, and the page allocator.

The paged layout changes storage ADDRESSING only: chunked prefill and
decode must produce bit-identical logits to the contiguous layout (GQA and
MLA), and the engine's page allocator must reject page-exhausted
admissions (strict raise / non-strict record), defer transiently-starved
ones, grow on demand at page boundaries, and reuse pages after release.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.iotlb import IotlbFault
from repro.models import (ArchConfig, forward, init_cache, init_paged_cache,
                          init_params)
from repro.serve import Request, ServeConfig, ServingEngine

GQA = ArchConfig(name="pg", family="dense", n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=100,
                 decode_margin=32, dtype=jnp.float32)
MLA = ArchConfig(name="pg_mla", family="dense", n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=100,
                 kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                 v_head_dim=16, decode_margin=32,
                 pattern=(("scan", "mla_mlp", 2),), dtype=jnp.float32)


# -- forward-level: bit-exact logits against the contiguous layout ----------

@pytest.mark.parametrize("cfg", [GQA, MLA], ids=["gqa", "mla"])
def test_paged_chunk_and_decode_logits_bit_exact(cfg):
    """Chunk prefill + several decode steps through a PERMUTED page table
    produce bit-identical logits to the contiguous cache.

    page_size * pages_per_slot is pinned to the contiguous capacity (256)
    so both layouts reduce over the same attention-window length: the
    masked rows are exact zeros under either layout, and with equal window
    lengths the reduction tree is identical too, making the comparison
    bitwise.  (With differing window lengths the values still agree, but
    only to reduction-order rounding, ~1e-7 — see the engine-level test.)
    """
    b, sp, ps, n_pages = 2, 8, 32, 16
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, sp), 0,
                              cfg.vocab_size)
    lens = jnp.asarray([5, 8], jnp.int32)

    cache_c = init_cache(cfg, b, sp)        # capacity rounds to 256 rows
    cache_p = init_paged_cache(cfg, b, n_pages, ps)   # 8 * 32 = 256 rows
    # non-identity mapping: logical order != physical order.
    pages = jnp.asarray([[5, 2, 7, 0, 9, 12, 15, 10],
                         [1, 6, 3, 4, 13, 8, 11, 14]], jnp.int32)

    lg_c, cache_c, _ = forward(params, toks, cfg, cache=cache_c,
                               mode="chunk", pos=lens)
    lg_p, cache_p, _ = forward(params, toks, cfg, cache=cache_p,
                               mode="chunk", pos=lens, pages=pages)
    np.testing.assert_array_equal(np.asarray(lg_c), np.asarray(lg_p))

    pos = np.asarray(lens)
    tok = jnp.asarray([[3], [7]], jnp.int32)
    for _ in range(3):
        pv = jnp.asarray(pos, jnp.int32)
        lg_c, cache_c, _ = forward(params, tok, cfg, cache=cache_c,
                                   mode="decode", pos=pv)
        lg_p, cache_p, _ = forward(params, tok, cfg, cache=cache_p,
                                   mode="decode", pos=pv, pages=pages)
        np.testing.assert_array_equal(np.asarray(lg_c), np.asarray(lg_p))
        tok = jnp.argmax(lg_c[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
        pos = pos + 1


def test_paged_chunk_inactive_slot_pool_untouched():
    """A slot admitted with length 0 (and -1 pos at decode) must not write
    a single pool row — batched admission never perturbs neighbors."""
    cfg = GQA
    b, sp, ps = 2, 8, 4
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = init_paged_cache(cfg, b, 8, ps)
    pages = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, sp), 0, 100)

    def slot1_rows(cache_tree):
        # every GQA cache leaf is a stacked pool (layers, pages, ps, KV,
        # dh); slot 1 owns physical pages 4..7.
        return [np.asarray(leaf)[:, 4:8] for leaf in
                jax.tree.leaves(cache_tree)]

    _, c1, _ = forward(params, toks, cfg, cache=cache, mode="chunk",
                       pos=jnp.asarray([6, 3], jnp.int32), pages=pages)
    # refill slot 0 only; slot 1 inactive (len 0) — its pages keep c1 rows.
    _, c2, _ = forward(params, toks, cfg, cache=c1, mode="chunk",
                       pos=jnp.asarray([6, 0], jnp.int32), pages=pages)
    for b1, b2 in zip(slot1_rows(c1), slot1_rows(c2)):
        np.testing.assert_array_equal(b1, b2)
    # decode with slot 1 inactive (-1): no write through its pages.
    _, c3, _ = forward(params, jnp.asarray([[1], [2]], jnp.int32), cfg,
                       cache=c2, mode="decode",
                       pos=jnp.asarray([6, -1], jnp.int32), pages=pages)
    for b2, b3 in zip(slot1_rows(c2), slot1_rows(c3)):
        np.testing.assert_array_equal(b2, b3)


# -- engine-level: paged engine == contiguous engine ------------------------

def _run_tokens(cfg, params, sc, prompts):
    eng = ServingEngine(cfg, params, sc)
    out = eng.run([Request(i, list(p)) for i, p in enumerate(prompts)])
    return {r.rid: r.out_tokens for r in out}, eng


@pytest.mark.parametrize("cfg", [GQA, MLA], ids=["gqa", "mla"])
def test_paged_engine_matches_contiguous_engine(cfg):
    """Greedy tokens are identical between the paged engine (small pages,
    mixed prompt lengths, slot reuse, on-demand growth) and the contiguous
    engine on the same request set."""
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = [[5, 7, 11], [3, 1, 4, 1, 5, 9, 2, 6], [2, 7],
               [9, 8, 7, 6, 5]]
    base = dict(max_batch=2, max_prompt=16, max_new_tokens=5)
    got_c, _ = _run_tokens(cfg, params, ServeConfig(paged=False, **base),
                           prompts)
    got_p, eng = _run_tokens(cfg, params,
                             ServeConfig(paged=True, page_size=4, **base),
                             prompts)
    assert got_p == got_c
    # every page returned to the pool after completion.
    assert len(eng._free_pages) == eng.num_pages
    assert (eng.page_table == -1).all()


# -- page allocator behavior ------------------------------------------------

def test_page_exhaustion_admission_strict_raises():
    """A request needing more pages than the WHOLE pool is a capacity
    fault at admission: recorded, rejected, and raised in strict mode."""
    params = init_params(GQA, jax.random.PRNGKey(0))
    eng = ServingEngine(GQA, params, ServeConfig(
        max_batch=2, max_prompt=16, max_new_tokens=4, page_size=4,
        num_pages=2))
    bad = Request(3, list(range(2, 12)))       # 10 rows -> 3 pages > 2
    with pytest.raises(IotlbFault, match="request 3"):
        eng.admit(bad)
    assert bad.failed and bad.done
    assert eng.iotlb.faults[-1].kind == "capacity"
    assert len(eng._free_pages) == eng.num_pages   # nothing leaked


def test_page_exhaustion_admission_nonstrict_records_and_rejects():
    params = init_params(GQA, jax.random.PRNGKey(0))
    eng = ServingEngine(GQA, params, ServeConfig(
        max_batch=2, max_prompt=16, max_new_tokens=4, page_size=4,
        num_pages=2, strict_iotlb=False))
    bad = Request(3, list(range(2, 12)))
    good = Request(4, [5, 7, 3])
    out = eng.run([bad, good])
    bad_out = next(r for r in out if r.rid == 3)
    assert bad_out.failed and bad_out.out_tokens == []
    assert any(f.kind == "capacity" for f in eng.iotlb.faults)
    good_out = next(r for r in out if r.rid == 4)
    assert not good_out.failed and len(good_out.out_tokens) == 4


def test_transient_exhaustion_defers_then_reuses_released_pages():
    """Two requests that can't hold pages simultaneously: the second is
    DEFERRED (no fault) and admitted after the first releases — the same
    physical pages get reused."""
    params = init_params(GQA, jax.random.PRNGKey(0))
    eng = ServingEngine(GQA, params, ServeConfig(
        max_batch=2, max_prompt=8, max_new_tokens=4, page_size=4,
        num_pages=3))
    # each request: 6-row prompt -> 2 pages + growth to 3 pages max; the
    # 3-page pool fits exactly one at a time.
    reqs = [Request(0, [5, 7, 11, 2, 9, 4]), Request(1, [3, 1, 4, 1, 5, 9])]
    out = eng.run(list(reqs))
    assert len(out) == 2
    assert all(not r.failed and len(r.out_tokens) == 4 for r in out)
    assert not eng.iotlb.faults                    # deferral is NOT a fault
    assert eng.peak_active == 1                    # never co-resident
    assert len(eng._free_pages) == eng.num_pages   # all pages came back


def test_decode_grows_pages_on_demand_across_boundaries():
    """Decode crossing page boundaries allocates pages lazily — admission
    claims only prompt + first-decode pages."""
    params = init_params(GQA, jax.random.PRNGKey(0))
    eng = ServingEngine(GQA, params, ServeConfig(
        max_batch=1, max_prompt=8, max_new_tokens=10, page_size=4))
    pending = [Request(0, [5, 7, 3])]
    eng.admit_many(pending)
    assert eng.pages_in_use() == 1          # 3 prompt rows + first decode
    while any(s is not None for s in eng.slots):
        eng.step()
    # rows 0..11 were written -> 3 pages grown in by the end, then freed.
    assert len(eng._free_pages) == eng.num_pages
    req = eng.completed[-1]
    assert not req.failed and len(req.out_tokens) == 10


def test_mid_decode_exhaustion_faults_at_page_boundary():
    """Overcommit mode (reserve_decode_pages=False): pool exhausted while
    growing a decode page is a capacity fault recorded at the faulting
    row; non-strict terminates the request with its partial output,
    strict raises."""
    params = init_params(GQA, jax.random.PRNGKey(0))
    sc = dict(max_batch=1, max_prompt=8, max_new_tokens=8, page_size=4,
              num_pages=1, reserve_decode_pages=False)
    eng = ServingEngine(GQA, params, ServeConfig(strict_iotlb=False, **sc))
    out = eng.run([Request(0, [5, 7, 3])])       # needs page 1 at row 4
    assert out[0].failed and 0 < len(out[0].out_tokens) < 8
    assert eng.iotlb.faults[-1].kind == "capacity"

    eng = ServingEngine(GQA, params, ServeConfig(strict_iotlb=True, **sc))
    with pytest.raises(IotlbFault, match="exhausted"):
        eng.run([Request(0, [5, 7, 3])])

    # with reservation accounting (the default) the same request is
    # rejected UP FRONT — the pool can never exhaust mid-decode.
    eng = ServingEngine(GQA, params, ServeConfig(
        strict_iotlb=False, **{**sc, "reserve_decode_pages": True}))
    out = eng.run([Request(0, [5, 7, 3])])
    assert out[0].failed and out[0].out_tokens == []
    assert eng.iotlb.faults[-1].kind == "capacity"


def test_single_token_request_claims_no_decode_page():
    """Regression: with max_new_tokens=1 no decode tick ever writes the
    cache, so a page-aligned prompt must claim exactly its prompt pages —
    admission vetting and claiming must agree even at a 1-page pool."""
    params = init_params(GQA, jax.random.PRNGKey(0))
    eng = ServingEngine(GQA, params, ServeConfig(
        max_batch=1, max_prompt=16, max_new_tokens=1, page_size=16,
        num_pages=1))
    out = eng.run([Request(0, list(range(2, 18)))])     # 16 rows = 1 page
    assert not out[0].failed and len(out[0].out_tokens) == 1
    assert len(eng._free_pages) == eng.num_pages


def test_allocator_balances_pages_across_shards():
    """A striped pool's allocator keeps per-shard occupancy balanced
    (most-free shard first), exhausts only at POOL level, and returns a
    released page to its owning shard's free list."""
    from repro.serve.allocator import PageAllocator
    al = PageAllocator(num_pages=8, page_size=4, max_batch=4,
                       pages_per_slot=2, num_shards=4)
    # 4 allocations land on 4 distinct shards (round-robin by balance).
    for slot in range(4):
        assert al.alloc(slot, 0)
    assert al.used_by_shard() == [1, 1, 1, 1]
    assert sorted(al.shard_of(int(al.page_table[s, 0])) for s in range(4)) \
        == [0, 1, 2, 3]
    # next wave fills the second page of every shard; the pool is full.
    for slot in range(4):
        assert al.alloc(slot, 1)
    assert al.used_by_shard() == [2, 2, 2, 2]
    assert not al.alloc(0, 0)           # pool-level exhaustion only
    # release: pages go home to their own shard's free list.
    al.release_slot(2)
    assert sum(al.free_by_shard()) == 2 and al.pages_in_use() == 6
    for p in al.free_pages:
        assert al.shard_of(p) == p // al.pages_per_shard


def test_allocator_single_shard_exhaustion_does_not_fault_pool():
    """One empty shard never fails an allocation while another shard
    still has pages: exhaustion stays a pool-level event."""
    from repro.serve.allocator import PageAllocator
    al = PageAllocator(num_pages=4, page_size=4, max_batch=4,
                       pages_per_slot=4, num_shards=2)
    # drain shard 0 completely by hand.
    al._free[0].clear()
    for j in range(2):                  # shard 1 still serves
        assert al.alloc(0, j)
        assert al.shard_of(int(al.page_table[0, j])) == 1
    assert not al.alloc(0, 2)           # now the POOL is empty


def test_allocator_windows_are_shard_local():
    """IOTLB windows are programmed against shard-local physical pages:
    phys_base is the page's offset within its owning shard's stripe."""
    from repro.serve.allocator import PageAllocator
    al = PageAllocator(num_pages=8, page_size=4, max_batch=2,
                       pages_per_slot=4, num_shards=4)
    for j in range(4):
        assert al.alloc(0, j)
    by_name = {w.name: w for w in al.iotlb.windows}
    for j in range(4):
        phys = int(al.page_table[0, j])
        w = by_name[f"slot0p{j}"]
        assert w.shard == al.shard_of(phys)
        assert w.phys_base == (phys % al.pages_per_shard) * al.page_size


def test_paged_iotlb_windows_map_exactly_allocated_pages():
    """The IOTLB guards page-granular windows: rows inside an allocated
    page translate, the row just past the last allocated page misses."""
    params = init_params(GQA, jax.random.PRNGKey(0))
    eng = ServingEngine(GQA, params, ServeConfig(
        max_batch=2, max_prompt=8, max_new_tokens=4, page_size=4))
    eng.admit_many([Request(0, [5, 7, 3])])      # slot 0: 1 page (rows 0-3)
    base = 0 * eng._slot_span
    assert eng.iotlb.translate(base, 4, write=True, strict=False) is not None
    assert eng.iotlb.translate(base + 4, 1, write=True,
                               strict=False) is None    # page 1 unmapped
    assert eng.iotlb.faults[-1].kind == "miss"
    # neighbors' logical windows are not mapped either.
    assert eng.iotlb.translate(eng._slot_span, 1, write=True,
                               strict=False) is None


# -- the stacked pool, written in place through the layer scan --------------

def _ref_attn(cfg, a, h, pool, pages, pos, off, fmt):
    """One layer's GQA sublayer the plain way: scatter into that layer's
    own (N, ps, ...) pool slice, gather its window, attend.  Returns (y,
    the layer's new pool)."""
    from repro.models.attention import (_decode_attention_local,
                                        _resume_attention_local)
    from repro.models.common import (dense, page_resident_rows,
                                     paged_gather, paged_gather_quant,
                                     paged_scatter, paged_scatter_quant,
                                     rope)
    b, s, _ = h.shape
    nh, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(h, a["wq"], None, a.get("bq")).reshape(b, s, nh, dh)
    k = dense(h, a["wk"], None, a.get("bk")).reshape(b, s, kvh, dh)
    v = dense(h, a["wv"], None, a.get("bv")).reshape(b, s, kvh, dh)
    if off is None:                                  # decode
        t, ok = pos[:, None], pos[:, None] >= 0
        positions = jnp.maximum(t, 0)
    else:                                            # resumed chunk
        t = off[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        ok = jnp.arange(s)[None] < pos[:, None]
        positions = t
    q, k = rope(q, positions, cfg.rope_theta), rope(k, positions,
                                                     cfg.rope_theta)
    new = {}
    if fmt is None:
        for name, rows in (("k", k), ("v", v)):
            new[name] = paged_scatter(pool[name], pages, rows, t, ok)
        kw, vw = (paged_gather(new[n], pages) for n in ("k", "v"))
    else:
        for name, rows in (("k", k), ("v", v)):
            new[name], new[name + "_scale"] = paged_scatter_quant(
                pool[name], pool[name + "_scale"], pages, rows, t, ok, fmt)
        kw, vw = (paged_gather_quant(new[n], new[n + "_scale"], pages, fmt,
                                     q.dtype) for n in ("k", "v"))
    kv_ok = page_resident_rows(pages, pool["k"].shape[1])
    if off is None:
        o = _decode_attention_local(q, kw, vw, jnp.int32(0), pos + 1, (),
                                    kv_ok=kv_ok)
    else:
        o = _resume_attention_local(q, kw, vw, off, off + pos, kv_ok=kv_ok)
    return dense(o.reshape(b, s, nh * dh), a["wo"]), new


def _ref_mla(cfg, a, h, pool, pages, pos, off, fmt):
    """One layer's MLA sublayer the plain way (absorbed decode, expanded
    resume) against that layer's own pool slice."""
    from repro.models.attention import _resume_attention_local
    from repro.models.common import (dense, page_resident_rows,
                                     paged_gather, paged_scatter, rms_norm,
                                     rope)
    assert fmt is None
    b, s, _ = h.shape
    nh, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q = dense(h, a["w_q"]).reshape(b, s, nh, dn + dr)
    if off is None:
        t, ok = pos[:, None], pos[:, None] >= 0
        positions = jnp.maximum(t, 0)
    else:
        t = off[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        ok = jnp.arange(s)[None] < pos[:, None]
        positions = t
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], positions,
                                       cfg.rope_theta)
    ckv = dense(h, a["w_dkv"])
    c_kv = rms_norm(ckv[..., :r], a["kv_norm"])
    k_rope = rope(ckv[..., r:][:, :, None, :], positions, cfg.rope_theta)
    entry = jnp.concatenate([c_kv, k_rope[:, :, 0, :]], axis=-1)
    new = {"ckv": paged_scatter(pool["ckv"], pages, entry, t, ok)}
    buf = paged_gather(new["ckv"], pages)
    c_all, kr_all = buf[..., :r], buf[..., r:]
    kv_ok = page_resident_rows(pages, new["ckv"].shape[1])
    if off is None:
        q_c = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                         a["w_uk"].reshape(r, nh, dn).astype(jnp.float32))
        sc = jnp.einsum("bqhr,bsr->bqhs", q_c.astype(h.dtype), c_all,
                        preferred_element_type=jnp.float32)
        sc += jnp.einsum("bqhd,bsd->bqhs", q_rope, kr_all,
                         preferred_element_type=jnp.float32)
        sc = sc * ((dn + dr) ** -0.5)
        kpos = jnp.arange(buf.shape[1], dtype=jnp.int32)
        mask = (kpos[None, :] <= pos[:, None]) & kv_ok
        sc = jnp.where(mask[:, None, None, :], sc, -1e30)
        ctx = jnp.einsum("bqhs,bsr->bqhr",
                         jax.nn.softmax(sc, axis=-1).astype(h.dtype), c_all,
                         preferred_element_type=jnp.float32)
        o = jnp.einsum("bqhr,rhv->bqhv", ctx, a["w_uv"].reshape(
            r, nh, dv).astype(jnp.float32)).astype(h.dtype)
    else:
        w = buf.shape[1]
        k_full = jnp.concatenate(
            [dense(c_all, a["w_uk"]).reshape(b, w, nh, dn),
             jnp.broadcast_to(kr_all[:, :, None, :], (b, w, nh, dr))],
            axis=-1)
        v_w = dense(c_all, a["w_uv"]).reshape(b, w, nh, dv)
        o = _resume_attention_local(jnp.concatenate([q_nope, q_rope], -1),
                                    k_full, v_w, off, off + pos,
                                    kv_ok=kv_ok)
    return dense(o.reshape(b, s, nh * dv), a["w_o"]), new


def _ref_forward(cfg, params, toks, pools, pages, pos, off, fmt):
    """The model step layer by layer: a scan that hands each layer its
    own pool slice and stacks the slices it returns."""
    from repro.models.blocks import apply_mlp, apply_norm
    from repro.models.common import dense, embed_lookup
    attn = _ref_mla if cfg.kv_lora_rank else _ref_attn

    def layer(x, xs):
        p, pool = xs
        y, pool = attn(cfg, p["attn"], apply_norm(p["ln1"], x, cfg), pool,
                       pages, pos, off, fmt)
        x = x + y
        return x + apply_mlp(p["ffn"], apply_norm(p["ln2"], x, cfg),
                             cfg), pool

    x, pools = jax.lax.scan(layer, embed_lookup(params["embed"], toks),
                            (params["stages"][0], pools))
    x = apply_norm(params["final_norm"], x, cfg)
    logits = dense(x, params["lm_head"])
    real = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
    return jnp.where(real, logits, jnp.asarray(-1e30, logits.dtype)), pools


@pytest.mark.parametrize("step", ["decode", "resume"])
@pytest.mark.parametrize("cfg,kv_format", [(GQA, "fp"), (GQA, "int8"),
                                           (MLA, "fp")],
                         ids=["gqa-fp", "gqa-int8", "mla-fp"])
def test_stacked_pool_step_bit_exact_per_layer(cfg, kv_format, step):
    """One decode step or resumed chunk through ``forward``, which carries
    each stacked (L, N, ps, ...) pool leaf through the layer scan and
    writes it in place, returns pools and logits bitwise equal to a
    layer-by-layer reference that scatters into and gathers from each
    layer's own pool slice.  Slot 1's write lands under an unmapped (-1)
    page-table entry and must be dropped; slot 2 is inactive."""
    from repro.core.pageformat import get_format
    b, ps, n_pages = 3, 4, 12
    fmt = None if kv_format == "fp" else get_format(kv_format)
    params = init_params(cfg, jax.random.PRNGKey(0))
    pools = init_paged_cache(cfg, b, n_pages, ps, kv_format=kv_format)[0]
    # a pool full of earlier rows, so every read and every kept row counts
    keys = iter(jax.random.split(jax.random.PRNGKey(1), len(pools)))
    pools = {n: (jax.random.randint(next(keys), l.shape, -100, 100,
                                    jnp.int32).astype(l.dtype)
                 if l.dtype == jnp.int8 else
                 jax.random.uniform(next(keys), l.shape, l.dtype, 0.5, 1.5)
                 if n.endswith("_scale") else
                 jax.random.normal(next(keys), l.shape, l.dtype))
             for n, l in pools.items()}
    pages = jnp.asarray([[3, 7, 1, 10], [5, -1, 8, 0], [2, 4, 6, 9]],
                        jnp.int32)
    if step == "decode":
        pos, off, s = jnp.asarray([6, 5, -1], jnp.int32), None, 1
    else:
        pos, s = jnp.asarray([3, 4, 0], jnp.int32), 4   # chunk lengths
        off = jnp.asarray([4, 4, 0], jnp.int32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0,
                              cfg.vocab_size)
    mode = "decode" if step == "decode" else "chunk"
    want_logits, want = jax.jit(
        lambda c: _ref_forward(cfg, params, toks, c, pages, pos, off,
                               fmt))(pools)
    # donated, as the engine jits its steps
    logits, cache, _ = jax.jit(
        lambda c: forward(params, toks, cfg, cache=c, mode=mode, pos=pos,
                          pages=pages, offset=off), donate_argnums=0)(
        [jax.tree.map(jnp.copy, pools)])
    assert set(cache[0]) == set(want)
    for name in want:
        np.testing.assert_array_equal(np.asarray(cache[0][name]),
                                      np.asarray(want[name]), err_msg=name)
        # slot 1's write under the unmapped entry left layer pages alone
        np.testing.assert_array_equal(np.asarray(cache[0][name])[:, 0],
                                      np.asarray(pools[name])[:, 0])
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(want_logits))
