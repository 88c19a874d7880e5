"""Multi-head Latent Attention (DeepSeek-V2) with compressed KV cache.

Prefill/train use the naive (expanded) form and share the context-parallel
SDPA from models/attention.py.  Decode uses the *absorbed* form: the
up-projections W_UK / W_UV are folded into the query/output sides so
attention runs directly against the compressed (kv_lora + rope) cache —
the cache stores 576 floats per token instead of 2*H*dh = 4096, which is
the technique's serving win and composes with the paper's sub-byte
quantization on every projection.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.core.pageformat import FP
from repro.distributed.sharding import lshard
from repro.kernels.paged_flash_decode import (decode_kernel_config,
                                              mla_paged_decode_partials)
from repro.models.attention import (NEG_INF, _combine_page_partials,
                                    _page_partials, _pool_page0, _pool_spec,
                                    _resume_attention_local,
                                    cache_page_format, paged_pool_axes,
                                    sdpa, sharded_paged_scatter)
from repro.models.common import (ParamSpec, broadcast_offset, chunk_lengths,
                                 chunk_valid_mask, contig_scatter, dense,
                                 page_resident_rows, paged_gather,
                                 paged_gather_quant, paged_scatter,
                                 paged_scatter_quant, rms_norm, rope,
                                 shard_local_pages, stacked_as_pool,
                                 yarn_get_mscale)


def mla_dims(cfg):
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return dn, dr, dv


def score_dim(cfg) -> float:
    """The d whose 1/sqrt(d) scales the attention scores: qk_nope +
    qk_rope, or under YaRN that over mscale(factor, mscale_all_dim)**4, so
    that the scale takes the published model's factor mscale**2."""
    dn, dr, _ = mla_dims(cfg)
    ys = cfg.rope_scaling
    if ys is None:
        return dn + dr
    return (dn + dr) / yarn_get_mscale(ys.factor, ys.mscale_all_dim) ** 4


def score_scale(cfg):
    """The expanded-form attention's score scale; None (1/sqrt(head dim),
    the attention default) without rope scaling."""
    return None if cfg.rope_scaling is None else score_dim(cfg) ** -0.5


def mla_specs(cfg) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    r = cfg.kv_lora_rank
    dn, dr, dv = mla_dims(cfg)
    return {
        "w_q": ParamSpec((d, h * (dn + dr)), ("embed", "heads"), quantize=True),
        "w_dkv": ParamSpec((d, r + dr), ("embed", "kv_lora"), quantize=True),
        "kv_norm": ParamSpec((r,), (None,), init="ones", dtype=jnp.float32),
        "w_uk": ParamSpec((r, h * dn), ("kv_lora", "heads"), quantize=True),
        "w_uv": ParamSpec((r, h * dv), ("kv_lora", "heads"), quantize=True),
        "w_o": ParamSpec((h * dv, d), ("heads", "embed"), quantize=True),
    }


def mla_cache_spec(cfg, batch: int, capacity: int):
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    return {
        "ckv": ParamSpec((batch, capacity, r + dr),
                         ("cache_batch", "cache_seq", None), init="zeros"),
    }


def latent_width(r: int, dr: int) -> int:
    """Stored width of a latent row in an fp page pool: r + dr, rounded
    up to whole 128-lane tiles once it spans more than one.  A pool whose
    rows end in a part-tile (576 = 4.5 tiles at DeepSeek-V2's widths) is
    laid out pages-minor on the TPU, and each step then copies the whole
    stacked pool into and out of the layer scan; the columns past r + dr
    hold zeros and are never read."""
    w = r + dr
    return w if w <= 128 else -(-w // 128) * 128


def _fit(entry, pool):
    """``entry`` rows zero-padded to an fp pool's stored width."""
    pad = pool.shape[-1] - entry.shape[-1]
    return entry if pad == 0 else \
        jnp.pad(entry, [(0, 0)] * (entry.ndim - 1) + [(0, pad)])


def paged_mla_cache_spec(cfg, num_pages: int, page_size: int, fmt=FP):
    """Paged layout for the compressed cache: a (num_pages, page_size,
    r+dr) pool per layer, addressed through the engine's per-slot page
    table and striped page-aligned over the seq mesh axes when a rule
    table maps 'pages' (see attention.paged_kv_cache_spec).  Quantized
    ``fmt``: the pool stores packed int8 latent rows (one absmax scale
    per row spanning the c_kv AND k_rope halves) with a pool-shaped
    ``ckv_scale`` leaf riding the same page axis.  fp rows are stored
    :func:`latent_width` wide."""
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    if not fmt.quantized:
        return {
            "ckv": ParamSpec((num_pages, page_size, latent_width(r, dr)),
                             ("pages", None, None), init="zeros"),
        }
    return {
        "ckv": ParamSpec((num_pages, page_size, fmt.packed_feat(r + dr)),
                         ("pages", None, None), init="zeros",
                         dtype=jnp.int8),
        "ckv_scale": ParamSpec((num_pages, page_size), ("pages", None),
                               init="zeros", dtype=jnp.float32),
    }


def _compress(p, x, cfg):
    """x -> (c_kv normalized (B,S,r), k_rope roped (B,S,dr))."""
    r = cfg.kv_lora_rank
    ckv_full = dense(x, p["w_dkv"], cfg.quant)
    c_kv, k_r = ckv_full[..., :r], ckv_full[..., r:]
    return rms_norm(c_kv, p["kv_norm"]), k_r


def _mla_window_partials(buf, qc, qr, lt, pb, r, scale_dim):
    """Lax per-logical-page flash partials of absorbed queries against a
    gathered (and, for quantized pools, already-dequantized) compressed
    window — the exact op sequence the fused MLA kernel mirrors."""
    b, w = buf.shape[:2]
    p_ = lt.shape[1]
    ps = w // p_
    c_all, kr_all = buf[..., :r], buf[..., r:r + qr.shape[-1]]
    sc = jnp.einsum("bqhr,bsr->bqhs", qc, c_all,
                    preferred_element_type=jnp.float32)
    sc += jnp.einsum("bqhd,bsd->bqhs", qr, kr_all,
                     preferred_element_type=jnp.float32)
    sc = sc * (scale_dim ** -0.5)
    kpos = jnp.arange(w, dtype=jnp.int32)
    res = (lt >= 0)[:, kpos // ps]      # (B, W) resident rows
    mask = res[:, None, :] & \
        (kpos[None, None, :] <= pb[:, None, None])
    sc = jnp.where(mask[:, :, None, :], sc, NEG_INF)
    scp = sc.reshape(b, 1, sc.shape[2], p_, ps)
    m = jnp.max(scp, axis=-1)           # (B, 1, H, P)
    wgt = jnp.where(scp <= NEG_INF / 2, 0.0,
                    jnp.exp(scp - m[..., None]))
    l = jnp.sum(wgt, axis=-1)
    acc = jnp.einsum("bqhjs,bjsr->bqhjr", wgt.astype(qc.dtype),
                     c_all.reshape(b, p_, ps, r),
                     preferred_element_type=jnp.float32)
    return m, l, acc


def _mla_paged_decode(q_c, q_rope, entry, cache, pages, pos_b, r,
                      scale_dim, fmt, layer):
    """Absorbed-form decode against a PAGE-STRIPED compressed pool.

    Each shard scatters/gathers only its resident pages and computes
    per-logical-page flash partials — here the weighted sum runs in the
    COMPRESSED space (ctx partials are (B, 1, H, P, r)), so the
    cross-shard psum moves r floats per head per page, not dv per key
    row.  Same bitwise shard-count independence argument as
    attention._page_partials.  Returns (ctx_c f32 (B,1,H,r), new cache).

    Under ``use_pallas_decode`` the gather + inline partials are
    replaced by the fused compressed-space Pallas kernel
    (:func:`repro.kernels.paged_flash_decode.mla_paged_decode_partials`)
    — same partials, same combine, bit-identical f32 logits.

    Quantized ``fmt``: the entry row is quantized once outside the
    shard_map, packed bytes + scale scatter through the same local
    table (the ckv_scale pool is striped by the same page axis), and
    the read side dequantizes the window (lax) / the VMEM page block
    (kernel) with the identical op sequence.  ``layer`` picks the layer
    of the stacked pool, which enters sharded on its page axis (axis 1).
    """
    pool = cache["ckv"]
    mesh, axes = paged_pool_axes(pool.shape[1])
    pspec = _pool_spec(pool.ndim)
    kernel_interpret = decode_kernel_config()

    if fmt is None:
        def body(pl, en, qc, qr, tbl, pb, li):
            n_loc = pl.shape[1]
            lt = shard_local_pages(tbl, _pool_page0(mesh, axes, n_loc),
                                   n_loc)
            pl = paged_scatter(pl, lt, en, pb[:, None], (pb >= 0)[:, None],
                               li)
            if kernel_interpret is not None:
                tl, fp = stacked_as_pool(lt, li, pl)
                m, l, acc = mla_paged_decode_partials(
                    fp, qc, qr, tl, pb, r, scale_dim,
                    interpret=kernel_interpret)
            else:
                # slot window, local pages only
                buf = paged_gather(pl, lt, li)
                m, l, acc = _mla_window_partials(buf, qc, qr, lt, pb, r,
                                                 scale_dim)
            m = jax.lax.pmax(m, axes)
            l = jax.lax.psum(l, axes)
            acc = jax.lax.psum(acc, axes)
            return _combine_page_partials(m, l, acc), pl

        ctx_c, pl = jax.shard_map(
            body, mesh=mesh, in_specs=(pspec, P(), P(), P(), P(), P(), P()),
            out_specs=(P(), pspec), check_vma=False)(
                pool, _fit(entry, pool), q_c, q_rope, pages, pos_b, layer)
        return ctx_c, {"ckv": pl}

    sspec = _pool_spec(cache["ckv_scale"].ndim)
    eq, es = fmt.quantize_rows(entry)

    def body_q(pl, pls, en, ens, qc, qr, tbl, pb, li):
        n_loc = pl.shape[1]
        lt = shard_local_pages(tbl, _pool_page0(mesh, axes, n_loc), n_loc)
        pl = paged_scatter(pl, lt, en, pb[:, None], (pb >= 0)[:, None], li)
        pls = paged_scatter(pls, lt, ens, pb[:, None], (pb >= 0)[:, None],
                            li)
        if kernel_interpret is not None:
            tl, fp, fps = stacked_as_pool(lt, li, pl, pls)
            m, l, acc = mla_paged_decode_partials(
                fp, qc, qr, tl, pb, r, scale_dim, scale_pool=fps,
                bits=fmt.bits, interpret=kernel_interpret)
        else:
            buf = paged_gather_quant(pl, pls, lt, fmt, qc.dtype, li)
            m, l, acc = _mla_window_partials(buf, qc, qr, lt, pb, r,
                                             scale_dim)
        m = jax.lax.pmax(m, axes)
        l = jax.lax.psum(l, axes)
        acc = jax.lax.psum(acc, axes)
        return _combine_page_partials(m, l, acc), pl, pls

    ctx_c, pl, pls = jax.shard_map(
        body_q, mesh=mesh,
        in_specs=(pspec, sspec, P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(), pspec, sspec), check_vma=False)(
            pool, cache["ckv_scale"], eq, es, q_c, q_rope, pages, pos_b,
            layer)
    return ctx_c, {"ckv": pl, "ckv_scale": pls}


def _mla_paged_resume(p, qq, entry, cache, pages, t, ok, off_b, len_b, cfg,
                      dims, fmt, layer):
    """Resumable-chunk MLA against the paged compressed pool: scatter the
    chunk's compressed entries, expand the slot's cached window back
    through W_UK/W_UV, attend with absolute causal masking.  Replicated
    pool: the local expand + exact-softmax path (bit-identical to the
    contiguous layout).  Page-striped pool: each shard expands only its
    resident pages and the shards combine per-logical-page flash partials
    with pmax/psum (see attention._page_partials).  Quantized ``fmt``:
    entries quantize once before the write and every read dequantizes
    from the pool (including this chunk's own rows), so the chunk
    schedule cannot change which bytes a row contributes.  ``layer``
    picks the layer of the stacked pool, as in :func:`_mla_paged_decode`."""
    b, h, r, dn, dr, dv = dims
    pool = cache["ckv"]
    mesh, axes = paged_pool_axes(pool.shape[1])

    def expand_window(buf, w_uk, w_uv):
        w = buf.shape[1]
        c_all, kr_all = buf[..., :r], buf[..., r:r + dr]
        k_nope_w = dense(c_all, w_uk, cfg.quant).reshape(b, w, h, dn)
        v_w = dense(c_all, w_uv, cfg.quant).reshape(b, w, h, dv)
        k_full = jnp.concatenate(
            [k_nope_w, jnp.broadcast_to(kr_all[:, :, None, :],
                                        (b, w, h, dr))], axis=-1)
        return k_full, v_w

    if mesh is None:
        if fmt is None:
            new_cache = {"ckv": paged_scatter(pool, pages, _fit(entry, pool),
                                              t, ok, layer)}
            buf = paged_gather(new_cache["ckv"], pages, layer)
        else:
            pl, pls = paged_scatter_quant(pool, cache["ckv_scale"], pages,
                                          entry, t, ok, fmt, layer)
            new_cache = {"ckv": pl, "ckv_scale": pls}
            buf = paged_gather_quant(pl, pls, pages, fmt, entry.dtype,
                                     layer)
        k_full, v_w = expand_window(buf, p["w_uk"], p["w_uv"])
        o = _resume_attention_local(
            qq, k_full, v_w, off_b, off_b + len_b,
            kv_ok=page_resident_rows(pages, pool.shape[2]),
            scale=score_scale(cfg))
        return o, new_cache

    pspec = _pool_spec(pool.ndim)

    if fmt is None:
        def body(pl, en, q_, tbl, tt, okk, q0, kvv, w_uk, w_uv, li):
            n_loc = pl.shape[1]
            lt = shard_local_pages(tbl, _pool_page0(mesh, axes, n_loc),
                                   n_loc)
            pl = paged_scatter(pl, lt, en, tt, okk, li)
            buf = paged_gather(pl, lt, li)
            k_full, v_w = expand_window(buf, w_uk, w_uv)
            qpos = q0[:, None] + \
                jnp.arange(q_.shape[1], dtype=jnp.int32)[None]
            m, l, acc = _page_partials(q_, k_full, v_w, lt, qpos, kvv,
                                       score_scale(cfg))
            m = jax.lax.pmax(m, axes)
            l = jax.lax.psum(l, axes)
            acc = jax.lax.psum(acc, axes)
            o = _combine_page_partials(m, l, acc)
            return o.reshape(b, q_.shape[1], h, dv).astype(q_.dtype), pl

        o, pl = jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspec, P(), P(), P(), P(), P(), P(), P(), P(), P(),
                      P()),
            out_specs=(P(), pspec), check_vma=False)(
                pool, _fit(entry, pool), qq, pages, t, ok, off_b,
                off_b + len_b, p["w_uk"], p["w_uv"], layer)
        return o, {"ckv": pl}

    sspec = _pool_spec(cache["ckv_scale"].ndim)
    eq, es = fmt.quantize_rows(entry)

    def body_q(pl, pls, en, ens, q_, tbl, tt, okk, q0, kvv, w_uk, w_uv,
               li):
        n_loc = pl.shape[1]
        lt = shard_local_pages(tbl, _pool_page0(mesh, axes, n_loc), n_loc)
        pl = paged_scatter(pl, lt, en, tt, okk, li)
        pls = paged_scatter(pls, lt, ens, tt, okk, li)
        buf = paged_gather_quant(pl, pls, lt, fmt, entry.dtype, li)
        k_full, v_w = expand_window(buf, w_uk, w_uv)
        qpos = q0[:, None] + jnp.arange(q_.shape[1], dtype=jnp.int32)[None]
        m, l, acc = _page_partials(q_, k_full, v_w, lt, qpos, kvv,
                                   score_scale(cfg))
        m = jax.lax.pmax(m, axes)
        l = jax.lax.psum(l, axes)
        acc = jax.lax.psum(acc, axes)
        o = _combine_page_partials(m, l, acc)
        return o.reshape(b, q_.shape[1], h, dv).astype(q_.dtype), pl, pls

    o, pl, pls = jax.shard_map(
        body_q, mesh=mesh,
        in_specs=(pspec, sspec, P(), P(), P(), P(), P(), P(), P(), P(),
                  P(), P(), P()),
        out_specs=(P(), pspec, sspec), check_vma=False)(
            pool, cache["ckv_scale"], eq, es, qq, pages, t, ok, off_b,
            off_b + len_b, p["w_uk"], p["w_uv"], layer)
    return o, {"ckv": pl, "ckv_scale": pls}


def apply_mla(p: dict, x: jax.Array, cfg, *, cache: Optional[dict],
              mode: str, pos,
              pages: Optional[jax.Array] = None,
              offset: Optional[jax.Array] = None,
              layer: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, Optional[dict]]:
    """MLA sublayer; the modes, ``pages``, ``offset`` and ``layer`` (the
    stacked paged pool's layer index) are as in
    :func:`repro.models.attention.apply_attention`."""
    b, s, d = x.shape
    h = cfg.n_heads
    r = cfg.kv_lora_rank
    dn, dr, dv = mla_dims(cfg)
    scale_dim = score_dim(cfg)

    q = dense(x, p["w_q"], cfg.quant).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    off_b = None
    if mode == "chunk" and offset is not None:
        # resumable chunk: tokens sit at [offset, offset + len) per slot.
        off_b = broadcast_offset(offset, b)
        positions = off_b[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    elif mode == "chunk":
        # chunked prefill: tokens sit at positions [0, len) per slot.
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    else:
        positions = jnp.atleast_1d(pos)[:, None] + \
            jnp.arange(s, dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(jnp.maximum(positions, 0), (b, s))
    q_rope = rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)

    c_kv, k_r = _compress(p, x, cfg)
    k_rope = rope(k_r[:, :, None, :], positions, cfg.rope_theta,
                  cfg.rope_scaling)                             # (B,S,1,dr)

    new_cache = None
    if mode == "chunk" and off_b is not None:
        # resumable chunk: scatter the compressed entries at rows
        # [offset, offset + len), then EXPAND the slot's whole cached
        # window (history + this chunk) back through W_UK/W_UV and run the
        # naive-form attention with absolute causal masking — the same key
        # set per query as the single-pass chunk, read from the cache.
        len_b = chunk_lengths(pos, b)
        ok = chunk_valid_mask(len_b, s)
        t = off_b[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        entry = jnp.concatenate([c_kv, k_rope[:, :, 0, :]], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        if pages is not None:
            o, new_cache = _mla_paged_resume(
                p, qq, entry, cache, pages, t, ok, off_b, len_b, cfg,
                (b, h, r, dn, dr, dv), cache_page_format(cache, r + dr),
                layer)
        else:
            new_cache = {"ckv": contig_scatter(cache["ckv"], entry, t, ok)}
            buf = new_cache["ckv"]
            w = buf.shape[1]
            c_all, kr_all = buf[..., :r], buf[..., r:r + dr]
            k_nope_w = dense(c_all, p["w_uk"], cfg.quant).reshape(b, w, h, dn)
            v_w = dense(c_all, p["w_uv"], cfg.quant).reshape(b, w, h, dv)
            k_full = jnp.concatenate(
                [k_nope_w, jnp.broadcast_to(kr_all[:, :, None, :],
                                            (b, w, h, dr))], axis=-1)
            o = _resume_attention_local(qq, k_full, v_w, off_b, off_b + len_b,
                                        scale=score_scale(cfg))
    elif mode == "chunk" and pages is not None and \
            cache_page_format(cache, r + dr) is not None:
        # quantized pool, fresh chunk: route through the resume path at
        # offset 0 so every compressed read — including this chunk's own
        # rows — comes back dequantized from the pool.  This makes
        # quantized logits invariant to the chunking / prefix-sharing /
        # swap schedule: a row's stored bytes depend only on its own fp
        # values.  The fp format keeps the expanded fast path below.
        len_b = chunk_lengths(pos, b)
        ok = chunk_valid_mask(len_b, s)
        t = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        entry = jnp.concatenate([c_kv, k_rope[:, :, 0, :]], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        o, new_cache = _mla_paged_resume(
            p, qq, entry, cache, pages, t, ok,
            jnp.zeros((b,), jnp.int32), len_b, cfg,
            (b, h, r, dn, dr, dv), cache_page_format(cache, r + dr), layer)
    elif mode in ("train", "prefill", "chunk"):
        # naive (expanded) form + shared context-parallel SDPA.
        k_nope = dense(c_kv, p["w_uk"], cfg.quant).reshape(b, s, h, dn)
        v = dense(c_kv, p["w_uv"], cfg.quant).reshape(b, s, h, dv)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        qq = lshard(qq, "batch", "seq", "heads", None)
        k = lshard(k, "batch", "seq", "heads", None)
        v = lshard(v, "batch", "seq", "heads", None)
        o = sdpa(qq, k, v, kv_valid=jnp.int32(s), scale=score_scale(cfg))
        if mode == "prefill":
            entry = jnp.concatenate([c_kv, k_rope[:, :, 0, :]], axis=-1)
            cap = cache["ckv"].shape[1]
            entry = jnp.pad(entry.astype(cache["ckv"].dtype),
                            ((0, 0), (0, cap - s), (0, 0)))
            new_cache = {"ckv": lshard(entry, "cache_batch", "cache_seq", None)}
        elif mode == "chunk":
            # masked chunk write into rows [0, len) of each slot's
            # compressed cache; len == 0 slots keep their region untouched.
            entry = jnp.concatenate([c_kv, k_rope[:, :, 0, :]], axis=-1)
            if pages is not None:
                t = jnp.broadcast_to(
                    jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
                ok = chunk_valid_mask(chunk_lengths(pos, b), s)
                new_cache = {"ckv": sharded_paged_scatter(
                    cache["ckv"], pages, _fit(entry, cache["ckv"]), t, ok,
                    layer)}
            else:
                buf = cache["ckv"]
                cap = buf.shape[1]
                mask = chunk_valid_mask(chunk_lengths(pos, b), cap)[:, :, None]
                entry = jnp.pad(entry.astype(buf.dtype),
                                ((0, 0), (0, cap - s), (0, 0)))
                buf = jnp.where(mask, entry, buf)
                new_cache = {"ckv": lshard(buf, "cache_batch", "cache_seq",
                                           None)}
    elif mode == "decode":
        assert s == 1
        entry = jnp.concatenate([c_kv, k_rope[:, :, 0, :]], axis=-1)
        # per-slot write at `pos` (negative = inactive slot, no write).
        pos_b = jnp.broadcast_to(jnp.atleast_1d(pos), (b,))
        if pages is not None and \
                paged_pool_axes(cache["ckv"].shape[1])[0] is not None:
            # page-striped pool: shard-local scatter/gather + the
            # cross-shard flash-decoding combine, in compressed space.
            w_uk = p["w_uk"].reshape(r, h, dn)
            q_c = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                             w_uk.astype(jnp.float32))
            ctx_c, new_cache = _mla_paged_decode(
                q_c.astype(x.dtype), q_rope, entry, cache, pages,
                pos_b, r, scale_dim, cache_page_format(cache, r + dr), layer)
            w_uv = p["w_uv"].reshape(r, h, dv)
            o = jnp.einsum("bqhr,rhv->bqhv", ctx_c,
                           w_uv.astype(jnp.float32))
            o = o.astype(x.dtype)
            y = dense(o.reshape(b, s, h * dv), p["w_o"], cfg.quant)
            return y, new_cache
        if pages is not None:
            fmt = cache_page_format(cache, r + dr)
            if fmt is None:
                pool = paged_scatter(cache["ckv"], pages,
                                     _fit(entry, cache["ckv"]),
                                     pos_b[:, None], (pos_b >= 0)[:, None],
                                     layer)
                new_cache = {"ckv": pool}
                # slot-ordered logical window; rows past `pos` are masked
                # below.
                buf = paged_gather(pool, pages, layer)
            else:
                pool, scales = paged_scatter_quant(
                    cache["ckv"], cache["ckv_scale"], pages, entry,
                    pos_b[:, None], (pos_b >= 0)[:, None], fmt, layer)
                new_cache = {"ckv": pool, "ckv_scale": scales}
                buf = paged_gather_quant(pool, scales, pages, fmt,
                                         entry.dtype, layer)
        else:
            buf = cache["ckv"]
            inb = (pos_b >= 0) & (pos_b < buf.shape[1])
            idx = jnp.clip(pos_b, 0, buf.shape[1] - 1)
            rows = jnp.take_along_axis(buf, idx[:, None, None], axis=1)
            new = jnp.where(inb[:, None, None], entry.astype(buf.dtype), rows)
            buf = buf.at[jnp.arange(b), idx].set(new[:, 0])
            buf = lshard(buf, "cache_batch", "cache_seq", None)
            new_cache = {"ckv": buf}
        c_all, kr_all = buf[..., :r], buf[..., r:r + dr]
        # absorbed queries: q_c = q_nope @ W_UK^T per head -> (B,1,H,r)
        w_uk = p["w_uk"].reshape(r, h, dn)
        q_c = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                         w_uk.astype(jnp.float32))
        sc = jnp.einsum("bqhr,bsr->bqhs", q_c.astype(x.dtype), c_all,
                        preferred_element_type=jnp.float32)
        sc += jnp.einsum("bqhd,bsd->bqhs", q_rope, kr_all,
                         preferred_element_type=jnp.float32)
        sc = sc * (scale_dim ** -0.5)
        kpos = jnp.arange(buf.shape[1], dtype=jnp.int32)
        mask = kpos[None, :] <= pos_b[:, None]
        if pages is not None:
            # residency, ANDed in (all-True on any legal dispatch —
            # see common.page_resident_rows): rows under a host-parked
            # page never reach the softmax.
            mask = mask & page_resident_rows(pages, cache["ckv"].shape[2])
        sc = jnp.where(mask[:, None, None, :], sc, -1e30)
        pr = jax.nn.softmax(sc, axis=-1)
        ctx_c = jnp.einsum("bqhs,bsr->bqhr", pr.astype(x.dtype), c_all,
                          preferred_element_type=jnp.float32)
        w_uv = p["w_uv"].reshape(r, h, dv)
        o = jnp.einsum("bqhr,rhv->bqhv", ctx_c, w_uv.astype(jnp.float32))
        o = o.astype(x.dtype)
    else:
        raise ValueError(mode)

    y = dense(o.reshape(b, s, h * dv), p["w_o"], cfg.quant)
    return y, new_cache
