"""GQA attention with context-parallel execution and seq-sharded KV caches.

Distribution strategy (baseline 'fsdp_sp' rules, DESIGN.md §5): activations
are sequence-sharded over the 'model' mesh axis.  Attention therefore runs
under shard_map:

  train/prefill — each shard holds a slice of queries; K/V are all-gathered
    over the seq axis (context parallelism) and queries are processed in
    VMEM-sized chunks with exact per-chunk softmax.  The chunk body is
    rematerialized (scan-of-checkpoint), so backward memory is flash-like:
    one chunk of scores at a time, never the (S x S) matrix.

  decode — the KV cache stays sequence-sharded (a 500k-token cache never
    lives on one chip); each shard computes partial attention over its local
    cache rows and the result is combined with the flash-decoding
    max/denominator reduction (pmax/psum over the seq axis).

  paged decode/resume — the page POOL is striped page-aligned over the
    same seq mesh axes (logical axis 'pages'; a physical page lives wholly
    on one shard).  Each shard translates the page table to its local
    indices, scatters/gathers only against its LOCAL pool slice, computes
    per-LOGICAL-page flash partials (running max + denominator + weighted
    value sum), and the shards combine with the same pmax/psum reduction.
    Because every logical page has exactly one owning shard, the
    collectives only merge a page's real partial with exact identities,
    and the final reduction over the page axis runs in the same canonical
    order at any shard count — N-shard logits are bit-identical to the
    1-shard pool's (tests/test_distributed_paging.py).  What the striping
    divides is pool MEMORY and cache reads/writes (each shard holds and
    touches 1/N of the pages); the masked score compute stays
    window-shaped per shard — compacting each shard's resident pages
    would need data-dependent shapes, so it is left dense.

Head counts never have to divide the mesh (the rule tables replicate
heads in this mode), which is what makes the scheme total over all ten
assigned architectures (yi-34b: 56 heads, musicgen: 24).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.pageformat import FP, format_for_packed
from repro.distributed.sharding import (current_mesh, lshard, make_spec,
                                        mesh_axes_for)
from repro.kernels.paged_flash_decode import (decode_kernel_config,
                                              paged_flash_decode_partials)
from repro.models.common import (ParamSpec, broadcast_offset, chunk_lengths,
                                 chunk_valid_mask, contig_scatter, dense,
                                 page_resident_rows, paged_gather,
                                 paged_gather_quant, paged_scatter,
                                 paged_scatter_quant, rms_norm, rope,
                                 shard_local_pages, stacked_as_pool)

NEG_INF = -1e30
# per-shard score-chunk budget (bytes) used to pick the query chunk size.
SCORE_BYTES_BUDGET = 1 << 30


def attn_specs(cfg) -> dict:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    specs = {
        "wq": ParamSpec((d, h * dh), ("embed", "heads"), quantize=True),
        "wk": ParamSpec((d, kv * dh), ("embed", "kv_heads"), quantize=True),
        "wv": ParamSpec((d, kv * dh), ("embed", "kv_heads"), quantize=True),
        "wo": ParamSpec((h * dh, d), ("heads", "embed"), quantize=True),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((h * dh,), ("heads",), init="zeros")
        specs["bk"] = ParamSpec((kv * dh,), ("kv_heads",), init="zeros")
        specs["bv"] = ParamSpec((kv * dh,), ("kv_heads",), init="zeros")
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((dh,), (None,), init="ones", dtype=jnp.float32)
        specs["k_norm"] = ParamSpec((dh,), (None,), init="ones", dtype=jnp.float32)
    return specs


def kv_cache_spec(cfg, batch: int, capacity: int):
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    ax = ("cache_batch", "cache_seq", "kv_heads", None)
    return {
        "k": ParamSpec((batch, capacity, kv, dh), ax, init="zeros"),
        "v": ParamSpec((batch, capacity, kv, dh), ax, init="zeros"),
    }


def paged_kv_cache_spec(cfg, num_pages: int, page_size: int, fmt=FP):
    """Paged layout: one global (num_pages, page_size, KV, dh) pool per
    layer shared by every slot; a per-slot page table (held by the serving
    engine, passed to ``forward`` as ``pages``) maps logical cache rows to
    pool pages.  The page axis carries the 'pages' logical axis: under a
    seq-sharding rule table the pool is striped page-aligned over the seq
    mesh axes instead of replicated.  Recurrent families keep their
    per-slot fixed-size state.

    ``fmt`` selects the page STORAGE format (core/pageformat): quantized
    formats store the pools as packed int8 (last dim shrunk by the pack
    factor) and add ``k_scale``/``v_scale`` leaves — (num_pages,
    page_size) f32 per-row absmax scales on the SAME page axis, so every
    pool transform (COW, swap, striping, byte accounting) moves scales
    with their pages without knowing about formats.  The read path
    recognizes a quantized cache structurally by the scale leaves."""
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    ax = ("pages", None, "kv_heads", None)
    if not fmt.quantized:
        return {
            "k": ParamSpec((num_pages, page_size, kv, dh), ax, init="zeros"),
            "v": ParamSpec((num_pages, page_size, kv, dh), ax, init="zeros"),
        }
    dp = fmt.packed_feat(dh)
    return {
        "k": ParamSpec((num_pages, page_size, kv, dp), ax, init="zeros",
                       dtype=jnp.int8),
        "v": ParamSpec((num_pages, page_size, kv, dp), ax, init="zeros",
                       dtype=jnp.int8),
        "k_scale": ParamSpec((num_pages, page_size), ("pages", None),
                             init="zeros", dtype=jnp.float32),
        "v_scale": ParamSpec((num_pages, page_size), ("pages", None),
                             init="zeros", dtype=jnp.float32),
    }


def cache_page_format(cache: dict, full_feat: int):
    """Infer a paged cache's storage format STRUCTURALLY, or None for fp.

    A scale leaf beside the pool marks it quantized; the ratio of the
    full feature width to the stored last dim names the bit width.  No
    format context threads through jitted forwards — the cache pytree
    itself is the source of truth (and fp caches take code paths byte-
    identical to the pre-format engine)."""
    key = "k_scale" if "k_scale" in cache else \
        ("ckv_scale" if "ckv_scale" in cache else None)
    if key is None:
        return None
    pool = cache["ckv"] if key == "ckv_scale" else cache["k"]
    return format_for_packed(full_feat, pool.shape[-1])


def _pick_q_chunk(b: int, h: int, skv: int) -> int:
    qc = SCORE_BYTES_BUDGET // max(1, b * h * skv * 4)
    qc = max(16, min(512, qc))
    return 1 << (qc.bit_length() - 1)       # round down to a power of two


def _chunked_attention_local(q, k, v, q0, kv_valid):
    """Exact causal attention, local arrays, query-chunked.

    q: (B, Sq, H, dh) local query slice whose global positions start at q0.
    k, v: (B, Skv, KV, dh) full keys/values.
    kv_valid: number of valid kv rows (int32 scalar).
    """
    b, sq, hq, dh = q.shape
    skv, kv = k.shape[1], k.shape[2]
    g = hq // kv
    qc = _pick_q_chunk(b, hq, skv)
    if sq % qc:
        qc = 1 << ((sq & -sq).bit_length() - 1)   # largest pow2 dividing sq
    nc = sq // qc
    scale = dh ** -0.5
    kpos = jnp.arange(skv, dtype=jnp.int32)

    def chunk(args):
        qx, c0 = args                      # (B, qc, H, dh), chunk global start
        qx = qx.reshape(b, qc, kv, g, dh)
        # operands stay bf16; the MXU accumulates in f32
        # (preferred_element_type) — materializing f32 copies of K/V was
        # the dominant HBM term in the baseline profile (§Perf).
        s = jnp.einsum("bqkgd,bskd->bqkgs", (qx * scale).astype(q.dtype), k,
                       preferred_element_type=jnp.float32)
        qpos = c0 + jnp.arange(qc, dtype=jnp.int32)
        mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < kv_valid)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bqkgs,bskd->bqkgd", p, v,
                       preferred_element_type=jnp.float32)
        return o.reshape(b, qc, hq, v.shape[-1]).astype(q.dtype)

    if nc == 1:
        return chunk((q, q0))
    qr = jnp.moveaxis(q.reshape(b, nc, qc, hq, dh), 1, 0)
    c0s = q0 + jnp.arange(nc, dtype=jnp.int32) * qc
    out = jax.lax.map(jax.checkpoint(chunk), (qr, c0s))
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, hq, v.shape[-1])


def _resume_attention_local(q, k_all, v_all, q0, kv_valid, kv_ok=None):
    """Causal attention of a RESUMED prefill chunk against the slot's full
    cached window (history rows [0, q0) plus the chunk's own rows, which
    the caller has already scattered into the cache).

    q: (B, Sq, H, dh) chunk queries whose global positions are
    ``q0[b] + i``; k_all/v_all: (B, Skv, KV, dh) the slot-ordered logical
    window; q0/kv_valid: (B,) int32.  Rows at or past ``kv_valid[b]``
    (including garbage under unmapped pages) are masked to exact zeros, so
    the result is bitwise the single-pass chunk attention restricted to
    the same key set — resuming changes WHERE keys are read from, never
    what is summed.

    kv_ok: optional (B, Skv) bool residency mask (paged windows:
    :func:`~repro.models.common.page_resident_rows`) ANDed into the
    causal/validity mask — all-True on every legal dispatch, so the AND
    is bit-preserving; see that helper's docstring.

    Queries are processed in SCORE_BYTES_BUDGET-sized chunks (the key
    axis is never split, so every query row still sees one exact softmax
    over the same key set and the result is bitwise chunk-count
    independent): peak score memory is bounded at large ``max_seq``
    instead of materializing the full (B, Sq, H, Skv) tensor.
    """
    b, sq, hq, dh = q.shape
    skv, kv = k_all.shape[1], k_all.shape[2]
    g = hq // kv
    scale = dh ** -0.5
    kpos = jnp.arange(skv, dtype=jnp.int32)

    def chunk(qx, c0):
        qc = qx.shape[1]
        qr = qx.reshape(b, qc, kv, g, dh)
        s = jnp.einsum("bqkgd,bskd->bqkgs", (qr * scale).astype(q.dtype),
                       k_all, preferred_element_type=jnp.float32)
        qpos = q0[:, None] + c0 + jnp.arange(qc, dtype=jnp.int32)[None, :]
        mask = (kpos[None, None, :] <= qpos[:, :, None]) & \
            (kpos[None, None, :] < kv_valid[:, None, None])
        if kv_ok is not None:
            mask = mask & kv_ok[:, None, :]
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bqkgs,bskd->bqkgd", p, v_all,
                       preferred_element_type=jnp.float32)
        return o.reshape(b, qc, hq, v_all.shape[-1]).astype(q.dtype)

    qc = _pick_q_chunk(b, hq, skv)
    if sq <= qc:
        return chunk(q, jnp.int32(0))
    if sq % qc:
        qc = 1 << ((sq & -sq).bit_length() - 1)   # largest pow2 dividing sq
    nc = sq // qc
    qr = jnp.moveaxis(q.reshape(b, nc, qc, hq, dh), 1, 0)
    c0s = jnp.arange(nc, dtype=jnp.int32) * qc
    out = jax.lax.map(lambda a: chunk(a[0], a[1]), (qr, c0s))
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, hq, v_all.shape[-1])


def _decode_attention_local(q, k_loc, v_loc, k0, kv_valid, seq_axes,
                            kv_ok=None):
    """Flash-decoding: partial softmax over the local cache slice, combined
    across the seq mesh axes with a max/denominator reduction.

    kv_ok: optional (B, Skv) bool residency mask ANDed into the validity
    predicate (see :func:`~repro.models.common.page_resident_rows`) —
    all-True on every legal dispatch, so bit-preserving."""
    b, sq, hq, dh = q.shape
    kv = k_loc.shape[2]
    g = hq // kv
    scale = dh ** -0.5
    qx = q.reshape(b, sq, kv, g, dh)
    s = jnp.einsum("bqkgd,bskd->bqkgs", (qx * scale).astype(q.dtype), k_loc,
                   preferred_element_type=jnp.float32)
    kpos = k0 + jnp.arange(k_loc.shape[1], dtype=jnp.int32)
    # kv_valid: scalar or (B,) (continuous batching: per-slot fill levels).
    kv_b = jnp.broadcast_to(jnp.atleast_1d(kv_valid), (b,))
    mask = kpos[None, :] < kv_b[:, None]
    if kv_ok is not None:
        mask = mask & kv_ok
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    # fully-masked shards (cache slice beyond kv_valid) contribute zeros.
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m[..., None]))
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bqkgs,bskd->bqkgd", p.astype(q.dtype), v_loc,
                     preferred_element_type=jnp.float32)
    if seq_axes:
        mg = jax.lax.pmax(m, seq_axes)
        corr = jnp.exp(m - mg)               # 0 for fully-masked shards
        l = jax.lax.psum(l * corr, seq_axes)
        acc = jax.lax.psum(acc * corr[..., None], seq_axes)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, sq, hq, v_loc.shape[-1]).astype(q.dtype)


def _verify_attention_local(q, k_all, v_all, q0, kv_valid, kv_ok=None):
    """Speculative-VERIFY attention: score a block of candidate rows with
    the decode step's OWN computation, one query row at a time.

    q: (B, Sq, H, dh) — the k+1 verify rows of each slot, global positions
    ``q0[b] + i``; k_all/v_all: (B, Skv, KV, dh) the slot-ordered logical
    window (the caller has already scattered the candidate rows in);
    q0/kv_valid: (B,) int32.

    Row ``i`` is :func:`_decode_attention_local` at Sq=1 with validity
    ``min(q0 + i + 1, kv_valid)`` — exactly the ``pos + 1`` a plain
    decode step at position ``q0 + i`` would pass.  The rows go through
    ``lax.map``, NOT one batched (B, Sq, ...) score: XLA reassociates
    the key-axis max/sum reductions differently for different Sq shapes
    (observed: 1-ulp logit drift once ~25 keys are live on the CPU
    backend, even with the op order written out identically), and the
    speculative bit-identity contract needs the verify logits at every
    accepted position to be BITWISE the plain decode logits.  Sharing
    the Sq=1 computation makes that hold by construction instead of by
    op-order mirroring.  (:func:`_resume_attention_local` is softmax-
    then-weight — a bitwise DIFFERENT op order — which is why verify
    does not reuse it on the replicated pool; the striped pool's
    per-page partials share one shard_map body with decode and need no
    twin.)

    Inactive slots (kv_valid 0) hit the decode path's fully-masked-row
    case and contribute zeros.  kv_ok: optional (B, Skv) residency mask,
    passed straight through to the decode computation.
    """
    def row(i):
        o = _decode_attention_local(
            jax.lax.dynamic_slice_in_dim(q, i, 1, axis=1),
            k_all, v_all, jnp.int32(0),
            jnp.minimum(q0 + i + 1, kv_valid), (), kv_ok=kv_ok)
        return o[:, 0]

    out = jax.lax.map(row, jnp.arange(q.shape[1], dtype=jnp.int32))
    return jnp.moveaxis(out, 0, 1)


def _seq_axes_info():
    """(mesh, seq mesh axes tuple) if seq is sharded under current rules."""
    mesh = current_mesh()
    if mesh is None:
        return None, ()
    spec = make_spec((None, "seq"))
    ax = spec[1] if len(spec) > 1 else None
    if ax is None:
        return mesh, ()
    return mesh, (ax,) if isinstance(ax, str) else tuple(ax)


def _axes_size(mesh, axes) -> int:
    return functools.reduce(lambda a, x: a * mesh.shape[x], axes, 1)


# ---------------------------------------------------------------------------
# Sharded page pool: per-logical-page flash partials + pmax/psum combine.
# ---------------------------------------------------------------------------

def paged_pool_axes(num_pages: int):
    """(mesh, mesh axes) the page pool is striped over, or (None, ()).

    The pool is sharded when a rule table maps the 'pages' logical axis
    onto present mesh axes AND the pool page count divides them (pages
    stripe page-aligned: shard ``i`` physically holds global pages
    [i * num_pages/N, (i+1) * num_pages/N) of every layer).  A size-1
    striping still takes the shard_map path, so 1-shard and N-shard pools
    run the same code and stay bit-comparable.  Callers pass the page
    count of a stacked (L, num_pages, ...) pool leaf, ``shape[1]``."""
    mesh, axes = mesh_axes_for("pages")
    if mesh is None or not axes or num_pages % _axes_size(mesh, axes):
        return None, ()
    return mesh, axes


def _pool_page0(mesh, axes, n_local: int):
    """First global page resident on this shard (inside shard_map)."""
    idx = 0
    for ax in axes:
        idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
    return (idx * n_local).astype(jnp.int32)


def _pool_spec(ndim: int) -> P:
    """PartitionSpec striping a stacked (L, num_pages, ...) pool leaf's
    page axis (axis 1): each shard holds 1/N of every layer's pages."""
    ax = make_spec(("pages",))[0]
    return P(None, ax, *([None] * (ndim - 2)))


def _page_partials(q, kw, vw, tbl, qpos, kv_valid):
    """Per-LOGICAL-page flash-decoding partials of ``q`` against a
    gathered (B, P*ps, KV, dh) window.

    ``tbl``: (B, P) shard-local page table — rows under a -1 entry
    (unmapped, or resident on another shard) are masked to exact NEG_INF,
    as are rows failing the causal (``kpos <= qpos``, (B, Sq)) and fill
    (``kpos < kv_valid``, (B,)) predicates.  Returns per-page running max
    ``m`` (B, Sq, KV, G, P), denominator ``l`` (same shape), and weighted
    value sum ``acc`` (..., P, dv).

    Partials are per LOGICAL page, and each logical page is owned by
    exactly ONE shard of a page-striped pool: a cross-shard pmax/psum of
    these arrays only ever merges a page's real partial with exact
    identities (NEG_INF / 0.0), and the final reduction over the page
    axis (:func:`_combine_page_partials`) runs in the same canonical
    order at every shard count — so N-shard logits are bit-identical to
    the 1-shard pool's, not merely close.

    Queries are chunked against SCORE_BYTES_BUDGET like every other
    attention path (key axis untouched — bitwise chunk-independent).
    """
    b, sq, hq, dh = q.shape
    skv = kw.shape[1]
    qc = _pick_q_chunk(b, hq, skv)
    if sq <= qc:
        return _page_partials_chunk(q, kw, vw, tbl, qpos, kv_valid)
    if sq % qc:
        qc = 1 << ((sq & -sq).bit_length() - 1)   # largest pow2 dividing sq
    nc = sq // qc
    qr = jnp.moveaxis(q.reshape(b, nc, qc, hq, dh), 1, 0)
    pr = jnp.moveaxis(qpos.reshape(b, nc, qc), 1, 0)
    m, l, acc = jax.lax.map(
        lambda a: _page_partials_chunk(a[0], kw, vw, tbl, a[1], kv_valid),
        (qr, pr))
    merge = lambda x: jnp.moveaxis(x, 0, 1).reshape(       # noqa: E731
        (b, sq) + x.shape[3:])
    return merge(m), merge(l), merge(acc)


def _page_partials_chunk(q, kw, vw, tbl, qpos, kv_valid):
    b, sq, hq, dh = q.shape
    skv, kv = kw.shape[1], kw.shape[2]
    g = hq // kv
    p = tbl.shape[1]
    ps = skv // p
    scale = dh ** -0.5
    qx = q.reshape(b, sq, kv, g, dh)
    s = jnp.einsum("bqkgd,bskd->bqkgs", (qx * scale).astype(q.dtype), kw,
                   preferred_element_type=jnp.float32)
    kpos = jnp.arange(skv, dtype=jnp.int32)
    res = (tbl >= 0)[:, kpos // ps]                 # (B, Skv) resident rows
    mask = res[:, None, :] & \
        (kpos[None, None, :] <= qpos[:, :, None]) & \
        (kpos[None, None, :] < kv_valid[:, None, None])
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    sp = s.reshape(b, sq, kv, g, p, ps)
    m = jnp.max(sp, axis=-1)                        # (B, Sq, KV, G, P)
    w = jnp.where(sp <= NEG_INF / 2, 0.0, jnp.exp(sp - m[..., None]))
    l = jnp.sum(w, axis=-1)
    vp = vw.reshape(b, p, ps, kv, vw.shape[-1])
    acc = jnp.einsum("bqkgjs,bjskd->bqkgjd", w.astype(q.dtype), vp,
                     preferred_element_type=jnp.float32)
    return m, l, acc


def _combine_page_partials(m, l, acc):
    """Flash-decoding reduction over the LOGICAL page axis.

    Identical code runs after the cross-shard pmax/psum at every shard
    count (including 1), which is what makes sharded paged logits bitwise
    shard-count independent.  Fully-masked pages (and fully-masked slots)
    contribute exact zeros."""
    mg = jnp.max(m, axis=-1)
    corr = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - mg[..., None]))
    lg = jnp.sum(l * corr, axis=-1)
    accg = jnp.sum(acc * corr[..., None], axis=-2)
    return accg / jnp.maximum(lg, 1e-30)[..., None]


def sharded_paged_scatter(pool, pages, rows, t, valid, layer):
    """:func:`paged_scatter` into layer ``layer`` of a stacked (possibly
    page-striped) pool leaf.

    Replicated pool: the plain scatter.  Striped pool: each shard
    translates the global table to its local indices and applies only
    the writes landing on pages it physically holds — the rest are
    dropped locally (they land on their owning shard instead), so no
    cross-shard traffic is issued for a pure cache write."""
    mesh, axes = paged_pool_axes(pool.shape[1])
    if mesh is None:
        return paged_scatter(pool, pages, rows, t, valid, layer)
    pspec = _pool_spec(pool.ndim)

    def body(pl, tbl, rw, tt, ok, li):
        lt = shard_local_pages(tbl, _pool_page0(mesh, axes, pl.shape[1]),
                               pl.shape[1])
        return paged_scatter(pl, lt, rw, tt, ok, li)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(pspec, P(), P(), P(), P(), P()),
                         out_specs=pspec, check_vma=False)(
                             pool, pages, rows, t, valid, layer)


def _paged_flash_striped(cache, pages, k, v, q, t, ok, qpos, kvv, mesh,
                         axes, layer):
    """The one shard_map body both striped GQA paths share: translate
    the table shard-local, scatter the new rows that land here into
    layer ``layer`` of the stacked pool, gather the slot windows out of
    the LOCAL pool slice (non-resident rows are garbage and masked —
    pool reads/writes stay shard-local; the score compute itself is
    still window-shaped per shard), take per-logical-page flash
    partials, pmax/psum them across the stripe, and run the canonical
    page-axis combine.  ``qpos`` (B, Sq) / ``kvv`` (B,) carry the
    causal/fill predicates: decode passes (pos, pos+1), resume passes
    (offset+i, offset+len).  The pool leaves enter sharded on their page
    axis (axis 1) and the layer offset applies to SHARD-LOCAL pages, so
    each shard holds and writes exactly its 1/N of every layer.

    Under :func:`repro.kernels.paged_flash_decode.use_pallas_decode`
    (ServeConfig.use_pallas_decode) the gather + lax partials are
    replaced by the FUSED Pallas kernel — page-table lookup in the
    BlockSpec index maps, one grid program per logical page, no HBM
    window — while this combine stays byte-for-byte the same, so the
    two paths produce bit-identical logits for f32 pools."""
    pspec = _pool_spec(cache["k"].ndim)
    kernel_interpret = decode_kernel_config()

    def body(pk, pv, kn, vn, qq, tbl, tt, okk, qp, kv_, li):
        n_loc = pk.shape[1]
        lt = shard_local_pages(tbl, _pool_page0(mesh, axes, n_loc), n_loc)
        pk = paged_scatter(pk, lt, kn, tt, okk, li)
        pv = paged_scatter(pv, lt, vn, tt, okk, li)
        if kernel_interpret is not None:
            tl, fk, fv = stacked_as_pool(lt, li, pk, pv)
            m, l, acc = paged_flash_decode_partials(
                fk, fv, qq, tl, qp, kv_, interpret=kernel_interpret)
        else:
            m, l, acc = _page_partials(qq, paged_gather(pk, lt, li),
                                       paged_gather(pv, lt, li), lt, qp,
                                       kv_)
        m = jax.lax.pmax(m, axes)
        l = jax.lax.psum(l, axes)
        acc = jax.lax.psum(acc, axes)
        o = _combine_page_partials(m, l, acc)
        b, sq = qq.shape[:2]
        return o.reshape(b, sq, -1, o.shape[-1]).astype(qq.dtype), pk, pv

    o, pk, pv = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, pspec, P(), P(), P(), P(), P(), P(), P(), P(),
                  P()),
        out_specs=(P(), pspec, pspec), check_vma=False)(
            cache["k"], cache["v"], k, v, q, pages, t, ok, qpos, kvv, layer)
    return o, {"k": pk, "v": pv}


def _paged_flash_striped_quant(cache, pages, k, v, q, t, ok, qpos, kvv,
                               mesh, axes, fmt, layer):
    """:func:`_paged_flash_striped` for QUANTIZED pools.

    The new rows are quantized ONCE, outside the shard_map (per-row
    scales depend only on the row's own fp values, so every shard sees
    identical packed bytes); each shard then scatters the packed rows
    and their scales through its local table — the scale pools are
    striped by the same PartitionSpec page axis as the data pools, so a
    row's scale always lives on the shard holding its page.  The read
    side dequantizes the gathered window (lax) or the VMEM page block
    (Pallas) with the identical op sequence, and the pmax/psum +
    canonical combine are byte-for-byte the fp path's — which is what
    keeps quantized logits bitwise shard-count independent too.  Kept
    separate from the fp body so ``kv_format='fp'`` traces are untouched.
    """
    pspec = _pool_spec(cache["k"].ndim)
    sspec = _pool_spec(cache["k_scale"].ndim)
    kernel_interpret = decode_kernel_config()
    kq, ks = fmt.quantize_rows(k)
    vq, vs = fmt.quantize_rows(v)

    def body(pk, pv, pks, pvs, kn, vn, kns, vns, qq, tbl, tt, okk, qp, kv_,
             li):
        n_loc = pk.shape[1]
        lt = shard_local_pages(tbl, _pool_page0(mesh, axes, n_loc), n_loc)
        pk = paged_scatter(pk, lt, kn, tt, okk, li)
        pv = paged_scatter(pv, lt, vn, tt, okk, li)
        pks = paged_scatter(pks, lt, kns, tt, okk, li)
        pvs = paged_scatter(pvs, lt, vns, tt, okk, li)
        if kernel_interpret is not None:
            tl, fk, fv, fks, fvs = stacked_as_pool(lt, li, pk, pv, pks, pvs)
            m, l, acc = paged_flash_decode_partials(
                fk, fv, qq, tl, qp, kv_, k_scale=fks, v_scale=fvs,
                bits=fmt.bits, interpret=kernel_interpret)
        else:
            kw = paged_gather_quant(pk, pks, lt, fmt, qq.dtype, li)
            vw = paged_gather_quant(pv, pvs, lt, fmt, qq.dtype, li)
            m, l, acc = _page_partials(qq, kw, vw, lt, qp, kv_)
        m = jax.lax.pmax(m, axes)
        l = jax.lax.psum(l, axes)
        acc = jax.lax.psum(acc, axes)
        o = _combine_page_partials(m, l, acc)
        b, sq = qq.shape[:2]
        return (o.reshape(b, sq, -1, o.shape[-1]).astype(qq.dtype),
                pk, pv, pks, pvs)

    o, pk, pv, pks, pvs = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, pspec, sspec, sspec,
                  P(), P(), P(), P(), P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(), pspec, pspec, sspec, sspec), check_vma=False)(
            cache["k"], cache["v"], cache["k_scale"], cache["v_scale"],
            kq, vq, ks, vs, q, pages, t, ok, qpos, kvv, layer)
    return o, {"k": pk, "v": pv, "k_scale": pks, "v_scale": pvs}


def _paged_attend(local, q, k, v, cache, pages, t, ok, qpos, kvv, layer):
    """Write the new rows into layer ``layer`` of the stacked paged pool,
    then attend over each slot's window: on a replicated pool (no rules
    context / TP rules / indivisible pool) gather the windows back and
    run ``local(q, kw, vw, kv_ok)``; on a page-striped one, the shared
    striped shard_map body (per-page flash partials + pmax/psum).
    Returns (output, new cache)."""
    fmt = cache_page_format(cache, q.shape[-1])
    mesh, axes = paged_pool_axes(cache["k"].shape[1])
    if mesh is not None:
        if fmt is None:
            return _paged_flash_striped(cache, pages, k, v, q, t, ok, qpos,
                                        kvv, mesh, axes, layer)
        return _paged_flash_striped_quant(cache, pages, k, v, q, t, ok,
                                          qpos, kvv, mesh, axes, fmt, layer)
    if fmt is None:
        new_cache = {"k": paged_scatter(cache["k"], pages, k, t, ok, layer),
                     "v": paged_scatter(cache["v"], pages, v, t, ok, layer)}
        kw = paged_gather(new_cache["k"], pages, layer)
        vw = paged_gather(new_cache["v"], pages, layer)
    else:
        pk, pks = paged_scatter_quant(cache["k"], cache["k_scale"], pages, k,
                                      t, ok, fmt, layer)
        pv, pvs = paged_scatter_quant(cache["v"], cache["v_scale"], pages, v,
                                      t, ok, fmt, layer)
        new_cache = {"k": pk, "v": pv, "k_scale": pks, "v_scale": pvs}
        kw = paged_gather_quant(pk, pks, pages, fmt, q.dtype, layer)
        vw = paged_gather_quant(pv, pvs, pages, fmt, q.dtype, layer)
    kv_ok = page_resident_rows(pages, cache["k"].shape[2])
    return local(q, kw, vw, kv_ok), new_cache


def _paged_decode(q, k, v, cache, pages, pos_b, layer):
    """One decode step against the paged pool: scatter this token's K/V
    through the table, then attend over the slot's logical window —
    bit-identical to the contiguous layout at equal window lengths on a
    replicated pool; the same pmax/psum flash-decoding reduction
    ``decode_sdpa`` uses on a striped one."""
    t = pos_b[:, None]
    return _paged_attend(
        lambda q_, kw, vw, kv_ok: _decode_attention_local(
            q_, kw, vw, jnp.int32(0), pos_b + 1, (), kv_ok=kv_ok),
        q, k, v, cache, pages, t, t >= 0, t, pos_b + 1, layer)


def _paged_resume(q, k, v, cache, pages, t, ok, off_b, len_b, layer):
    """Resumable-chunk attention against the paged pool: scatter the
    chunk's K/V at rows [offset, offset+len), then attend the chunk
    queries over the slot's whole cached window."""
    qpos = off_b[:, None] + jnp.arange(q.shape[1], dtype=jnp.int32)[None]
    return _paged_attend(
        lambda q_, kw, vw, kv_ok: _resume_attention_local(
            q_, kw, vw, off_b, off_b + len_b, kv_ok=kv_ok),
        q, k, v, cache, pages, t, ok, qpos, off_b + len_b, layer)


def _paged_verify(q, k, v, cache, pages, t, ok, off_b, len_b, layer):
    """Speculative VERIFY against the paged pool: scatter the candidate
    rows at [offset, offset + len), then score every row with DECODE-
    order numerics.

    The shape is :func:`_paged_resume`'s; the numerics are
    :func:`_paged_decode`'s.  On the STRIPED pool the two already share
    one shard_map body (per-page flash partials + the pmax/psum combine,
    parameterized only by per-row query positions), so verify delegates
    to it exactly like resume does and is bitwise the decode path row by
    row.  Only the REPLICATED pool needs a dedicated scorer
    (:func:`_verify_attention_local`), because there resume uses the
    softmax-order local path while decode uses flash order."""
    qpos = off_b[:, None] + jnp.arange(q.shape[1], dtype=jnp.int32)[None]
    return _paged_attend(
        lambda q_, kw, vw, kv_ok: _verify_attention_local(
            q_, kw, vw, off_b, off_b + len_b, kv_ok=kv_ok),
        q, k, v, cache, pages, t, ok, qpos, off_b + len_b, layer)


def _batch_spec(mesh, b: int):
    """Batch mesh axes, or None when the batch doesn't divide them."""
    spec = make_spec(("batch",))
    ax = spec[0] if len(spec) else None
    if ax is None:
        return None
    axes = (ax,) if isinstance(ax, str) else tuple(ax)
    return ax if b % _axes_size(mesh, axes) == 0 else None


def sdpa(q, k, v, *, kv_valid) -> jax.Array:
    """Causal SDPA for q/k/v of equal seq length (train/prefill).

    q: (B, S, H, dh), k/v: (B, S, KV, dh), both seq-sharded per the rules.
    """
    mesh, seq_axes = _seq_axes_info()
    if not seq_axes or q.shape[1] % _axes_size(mesh, seq_axes):
        return _chunked_attention_local(
            q, k, v, jnp.int32(0), kv_valid)

    bspec = _batch_spec(mesh, q.shape[0])
    qkv_spec = P(bspec, make_spec((None, "seq"))[1], None, None)

    def local_fn(q_l, k_l, v_l):
        idx = 0
        for ax in seq_axes:
            idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        s_loc = q_l.shape[1]
        q0 = (idx * s_loc).astype(jnp.int32)
        kf = jax.lax.all_gather(k_l, seq_axes, axis=1, tiled=True)
        vf = jax.lax.all_gather(v_l, seq_axes, axis=1, tiled=True)
        return _chunked_attention_local(q_l, kf, vf, q0, kv_valid)

    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=(qkv_spec, qkv_spec, qkv_spec),
        out_specs=qkv_spec, check_vma=False)(q, k, v)


def decode_sdpa(q, k_cache, v_cache, *, kv_valid) -> jax.Array:
    """Single-step attention against a (possibly seq-sharded) KV cache."""
    mesh, seq_axes = _seq_axes_info()
    if not seq_axes or k_cache.shape[1] % _axes_size(mesh, seq_axes):
        return _decode_attention_local(
            q, k_cache, v_cache, jnp.int32(0), kv_valid, ())

    bspec = _batch_spec(mesh, q.shape[0])
    sspec = make_spec((None, "seq"))[1]
    q_spec = P(bspec, None, None, None)
    c_spec = P(bspec, sspec, None, None)

    def local_fn(q_l, k_l, v_l):
        idx = 0
        for ax in seq_axes:
            idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        k0 = (idx * k_l.shape[1]).astype(jnp.int32)
        return _decode_attention_local(q_l, k_l, v_l, k0, kv_valid, seq_axes)

    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=(q_spec, c_spec, c_spec),
        out_specs=q_spec, check_vma=False)(q, k_cache, v_cache)


def cache_fill(cache: dict, k_new, v_new, lengths) -> dict:
    """Write a whole prompt chunk into rows [0, len) of each slot's cache.

    k_new/v_new: (B, S, KV, dh) chunk keys/values; ``lengths``: (B,) valid
    token counts per slot (0 = slot not being admitted -> no write).  The
    write is a pad-and-select, so it is elementwise over the cache buffer
    and lowers correctly under any cache sharding without a shard_map.
    Rows >= len keep their old contents (they are masked by kv_valid at
    decode time), so admission never perturbs another slot's region.
    """
    cap, s = cache["k"].shape[1], k_new.shape[1]
    len_b = chunk_lengths(lengths, cache["k"].shape[0])
    mask = chunk_valid_mask(len_b, cap)[:, :, None, None]  # (B, cap, 1, 1)
    pad = [(0, 0), (0, cap - s), (0, 0), (0, 0)]

    def put(buf, val):
        out = jnp.where(mask, jnp.pad(val.astype(buf.dtype), pad), buf)
        return lshard(out, "cache_batch", "cache_seq", "kv_heads", None)

    return {"k": put(cache["k"], k_new), "v": put(cache["v"], v_new)}


def cache_update(cache: dict, k_new, v_new, index) -> dict:
    """Write one token's K/V at ``index`` into a (possibly sharded) cache.

    ``index``: scalar or (B,) per-slot positions; negative = no write
    (inactive serving slot)."""
    mesh, seq_axes = _seq_axes_info()

    def write_local(buf, val, k0):
        bsz = buf.shape[0]
        idx_b = jnp.broadcast_to(jnp.atleast_1d(index), (bsz,))
        li = idx_b - k0
        inb = (li >= 0) & (li < buf.shape[1])
        li_c = jnp.clip(li, 0, buf.shape[1] - 1)
        rows = jnp.take_along_axis(
            buf, li_c[:, None, None, None], axis=1)       # (B,1,KV,dh)
        new = jnp.where(inb[:, None, None, None], val.astype(buf.dtype),
                        rows)
        return buf.at[jnp.arange(bsz), li_c].set(new[:, 0])

    if not seq_axes or cache["k"].shape[1] % _axes_size(mesh, seq_axes):
        return {"k": write_local(cache["k"], k_new, 0),
                "v": write_local(cache["v"], v_new, 0)}

    bspec = _batch_spec(mesh, cache["k"].shape[0])
    sspec = make_spec((None, "seq"))[1]
    c_spec = P(bspec, sspec, None, None)
    n_spec = P(bspec, None, None, None)

    def local_fn(kb, vb, kn, vn):
        idx = 0
        for ax in seq_axes:
            idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        k0 = idx * kb.shape[1]
        return write_local(kb, kn, k0), write_local(vb, vn, k0)

    k2, v2 = jax.shard_map(
        local_fn, mesh=mesh, in_specs=(c_spec, c_spec, n_spec, n_spec),
        out_specs=(c_spec, c_spec), check_vma=False)(
            cache["k"], cache["v"], k_new, v_new)
    return {"k": k2, "v": v2}


def apply_attention(p: dict, x: jax.Array, cfg, *, cache: Optional[dict],
                    mode: str, pos: jax.Array,
                    pages: Optional[jax.Array] = None,
                    offset: Optional[jax.Array] = None,
                    layer: Optional[jax.Array] = None,
                    ) -> Tuple[jax.Array, Optional[dict]]:
    """Full attention sublayer: QKV proj, RoPE, SDPA, out proj.

    mode: 'train' (no cache), 'prefill' (emit cache), 'decode' (use cache),
    'chunk' (single-pass chunked prefill into an existing slot'd cache),
    'verify' (speculative draft verification: like a resumable chunk, but
    scored with decode-order numerics so each row's logits are bitwise a
    plain decode step's at that position; requires pages + offset).
    pos: scalar int32 — first position of ``x`` in the sequence; in 'chunk'
    mode a (B,) vector of valid prompt lengths (0 = inactive slot) for a
    right-padded chunk whose tokens sit at positions [0, len); in 'decode'
    mode a (B,) vector of per-slot positions (-1 = inactive slot).
    pages: optional (B, P) int32 page table (paged KV cache, serving): the
    cache is then the layer scan's whole stacked (L, num_pages, page_size,
    KV, dh) pool, ``layer`` names this layer's slice, and chunk/decode
    writes scatter through the table into that slice in place; decode
    gathers the slot's logical window back before attention (bit-identical
    math to the contiguous layout — only the storage addressing changes).
    offset: optional (B,) int32 — RESUMABLE chunk mode: each slot's chunk
    tokens sit at positions [offset, offset + len) and attend over the
    already-cached history rows [0, offset) too, so a prompt longer than
    one chunk fills across several dispatches (continuous batching).
    None keeps the single-pass chunk path (tokens at [0, len)).
    """
    b, s, d = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(x, p["wq"], cfg.quant, p.get("bq"))
    k = dense(x, p["wk"], cfg.quant, p.get("bk"))
    v = dense(x, p["wv"], cfg.quant, p.get("bv"))
    q = q.reshape(b, s, h, dh)
    k = k.reshape(b, s, kv, dh)
    v = v.reshape(b, s, kv, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    off_b = None
    if mode in ("chunk", "verify") and offset is not None:
        off_b = broadcast_offset(offset, b)
        positions = off_b[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    elif mode == "chunk":
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    else:
        positions = jnp.atleast_1d(pos)[:, None] + \
            jnp.arange(s, dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(jnp.maximum(positions, 0), (b, s))
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = lshard(q, "batch", "seq", "heads", None)
    k = lshard(k, "batch", "seq", "kv_heads", None)
    v = lshard(v, "batch", "seq", "kv_heads", None)

    new_cache = None
    if mode == "train":
        o = sdpa(q, k, v, kv_valid=jnp.int32(s))
    elif mode == "prefill":
        o = sdpa(q, k, v, kv_valid=jnp.int32(s))
        cap = cache["k"].shape[1]
        pad = [(0, 0), (0, cap - s), (0, 0), (0, 0)]
        new_cache = {
            "k": lshard(jnp.pad(k.astype(cache["k"].dtype), pad),
                        "cache_batch", "cache_seq", "kv_heads", None),
            "v": lshard(jnp.pad(v.astype(cache["v"].dtype), pad),
                        "cache_batch", "cache_seq", "kv_heads", None),
        }
    elif mode == "chunk" and off_b is not None:
        # resumable chunk: scatter the chunk's K/V at rows
        # [offset, offset + len), then attend the chunk queries over the
        # slot's WHOLE cached window (history + this chunk) with absolute
        # causal masking — the key set per query is exactly the
        # single-pass one, so logits stay bit-identical.
        len_b = chunk_lengths(pos, b)
        ok = chunk_valid_mask(len_b, s)
        t = off_b[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        if pages is not None:
            o, new_cache = _paged_resume(q, k, v, cache, pages, t, ok,
                                         off_b, len_b, layer)
        else:
            new_cache = {"k": contig_scatter(cache["k"], k, t, ok),
                         "v": contig_scatter(cache["v"], v, t, ok)}
            o = _resume_attention_local(q, new_cache["k"], new_cache["v"],
                                        off_b, off_b + len_b)
    elif mode == "chunk" and pages is not None and \
            cache_page_format(cache, dh) is not None:
        # quantized pool, fresh chunk: run it as a resume at offset 0 —
        # every K/V read then goes through the quantized cache, so the
        # numerics are UNIFORM across chunkings: a prompt admitted fresh,
        # resumed mid-way, or resumed after a shared prefix sees the same
        # dequantized rows and emits bitwise-identical logits (the fp
        # path keeps the sdpa fast path below, where this is bit-exact
        # anyway because nothing is re-read through the cache).
        len_b = chunk_lengths(pos, b)
        ok = chunk_valid_mask(len_b, s)
        t = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        o, new_cache = _paged_resume(q, k, v, cache, pages, t, ok,
                                     jnp.zeros((b,), jnp.int32), len_b,
                                     layer)
    elif mode == "chunk":
        # one causal pass over the whole padded chunk; padded queries sit
        # after every valid token so they never leak into valid outputs,
        # and their own outputs are discarded by the caller.
        o = sdpa(q, k, v, kv_valid=jnp.int32(s))
        if pages is not None:
            t = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
            ok = chunk_valid_mask(chunk_lengths(pos, b), s)
            new_cache = {
                "k": sharded_paged_scatter(cache["k"], pages, k, t, ok,
                                           layer),
                "v": sharded_paged_scatter(cache["v"], pages, v, t, ok,
                                           layer)}
        else:
            new_cache = cache_fill(cache, k, v, pos)
    elif mode == "verify":
        # speculative draft/verify: the chunk rows are the slot's last
        # committed token + k draft proposals at rows [offset, offset+len);
        # every row is scored with DECODE-order numerics under its own
        # causal mask, so the logits at any accepted position are bitwise
        # what a plain decode step there would have produced.
        if pages is None or off_b is None:
            raise ValueError("mode='verify' needs a paged cache and offsets")
        len_b = chunk_lengths(pos, b)
        ok = chunk_valid_mask(len_b, s)
        t = off_b[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        o, new_cache = _paged_verify(q, k, v, cache, pages, t, ok,
                                     off_b, len_b, layer)
    elif mode == "decode":
        assert s == 1
        if pages is not None:
            pos_b = jnp.broadcast_to(jnp.atleast_1d(pos), (b,))
            o, new_cache = _paged_decode(q, k, v, cache, pages, pos_b,
                                         layer)
        else:
            new_cache = cache_update(cache, k, v, pos)
            o = decode_sdpa(q, new_cache["k"], new_cache["v"],
                            kv_valid=pos + 1)
    else:
        raise ValueError(mode)
    o = lshard(o, "batch", "seq", "heads", None)
    y = dense(o.reshape(b, s, h * dh), p["wo"], cfg.quant)
    return y, new_cache
