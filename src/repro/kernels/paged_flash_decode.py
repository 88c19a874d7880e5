"""Fused Pallas paged flash-decoding kernel — the paged-serving hot path.

The sharded paged decode/resume path was composed from generic
primitives: ``paged_gather`` materialized each slot's whole logical
window ``(B, P*ps, KV, dh)`` in HBM before the lax ``_page_partials``
reduction consumed it (models/attention.py).  This module fuses the
page-table translation, the pool-page gather, and the per-logical-page
flash partial into ONE Pallas kernel — the vLLM PagedAttention /
flash-decoding (split-KV) shape:

  * grid ``(B, P)``: each program owns one LOGICAL page of one slot,
  * the per-slot page table rides in as a scalar-prefetch operand, so
    the ``pl.BlockSpec`` index maps resolve ``tbl[b, j]`` and stream the
    mapped POOL page straight into VMEM — the gathered window never
    exists in HBM,
  * non-resident (``tbl[b, j] < 0``), causally-future, and unfilled
    pages are skipped with ``pl.when``: their partials are written as
    the exact flash identities (``m = NEG_INF``, ``l = 0``, ``acc = 0``)
    without touching the pool — decode at position t reads
    ``ceil((t+1)/ps)`` pages, not the slot's whole capacity,
  * each program emits the page's flash partial ``(m, l, acc)`` — the
    caller's cross-shard ``pmax``/``psum`` and the canonical page-axis
    combine (``attention._combine_page_partials``) are UNCHANGED, which
    is what keeps N-shard logits bit-identical to the lax path.

TPU layout.  Mosaic tiles the last two dims of every block by (8, 128)
unless they span the whole array, and its matmuls are 2-D.  So the
kernel works on 2-D row tiles and the wrappers convert at the boundary:

  * GQA queries enter transposed, one ``(dh, Sq*G)`` tile per KV head,
    so the query rows run along lanes, and their positions ride beside
    them as a ``(1, Sq*G)`` row; MLA queries enter as ``(Sq*H, r)`` and
    ``(Sq*H, dr)`` tiles,
  * the per-slot scalars the skip predicate needs (fill bound, last
    query position) are scalar-prefetched into SMEM with the table,
  * outputs put the page axis ahead of the tile, ``(B, P, KV, 1|dv,
    Sq*G)`` (GQA) and ``(B, P, Sq*H, 1|r)`` (MLA), and the wrappers move
    it back to the callers' ``(..., P)`` layout,
  * per-row scale pools are read through a unit middle axis
    ``(N, 1, ps)``.

Reshapes and transposes move values without rounding, so the callers
see exactly what the per-page math produced.

Bit-exactness: per-page scores/weights are the same fp ops in the same
order as ``attention._page_partials_chunk`` (masking with the same
``NEG_INF`` identities, f32 score/acc accumulation via
``preferred_element_type``), so for f32 pools the partials are
BIT-IDENTICAL to the lax path — the parity suite
(tests/test_paged_flash_decode.py) asserts equality, not closeness.
bf16 pools are allclose: XLA picks shape-dependent GEMM strategies for
bf16 dots, so a (ps, dh) page dot may round differently than the fused
(P*ps, dh) window dot.

Quantized pools (``ServeConfig.kv_format`` int8/int4): both kernels take
an optional per-row SCALE pool (``(N, ps)`` f32, addressed through the
same page table as the data pool) plus the storage bit width, and
dequantize the page block inside VMEM — ``unpack`` (shift/mask/concat,
identity for int8) then one f32 multiply by the row scale — before the
identical score/partial math.  The op sequence matches the lax read
path's ``PageFormat.dequantize`` element for element, so the quantized
kernel partials are bitwise equal to the quantized lax partials the same
way the fp ones are; no fp window is materialized in HBM in either mode.

``interpret`` chooses how the kernel runs: ``False`` compiles it with
Mosaic, ``True`` runs it in the Pallas interpreter (the same grid walk,
index-map table lookups and ``pl.when`` skips, executed as XLA ops —
how the CPU test suite checks the kernel logic), and ``None`` compiles
on a TPU backend and interprets on any other.

Serving wires this behind ``ServeConfig.use_pallas_decode``: the engine
enters :func:`use_pallas_decode` around its jitted dispatches and the
striped attention paths consult :func:`decode_kernel_config` at trace
time (models/attention.py, models/mla.py).
"""
from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import pack_factor, unpack

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# The trace-time knob: ServingEngine enters this context around its jitted
# dispatches; the striped attention paths read it while tracing.
# ---------------------------------------------------------------------------

_state = threading.local()


@contextlib.contextmanager
def use_pallas_decode(enabled: bool = True, interpret: bool | None = None):
    """Route page-striped paged decode/resume through the fused kernel.

    ``interpret=None`` keeps the choice of an enclosing context, and
    with none resolves from the backend at trace time (compiled on TPU,
    the Pallas interpreter elsewhere).  Nesting restores the previous
    state on exit."""
    prev = getattr(_state, "cfg", None)
    if interpret is None and prev is not None:
        interpret = prev[1]
    _state.cfg = (enabled, interpret)
    try:
        yield
    finally:
        _state.cfg = prev


def decode_kernel_config():
    """None = lax path; otherwise the ``interpret`` flag to run with."""
    cfg = getattr(_state, "cfg", None)
    if cfg is None or not cfg[0]:
        return None
    return _resolve_interpret(cfg[1])


def _resolve_interpret(interpret: bool | None) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _call(kernel, grid_spec, out_shape, operands, interpret):
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret)(*operands)


def _write_identities(m_ref, l_ref, acc_ref):
    """A skipped page's partial: the exact flash identities."""
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
    l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
    acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)


def _dequant(packed, s_ref, bits, dtype):
    """(ps, w) packed page rows -> (ps, w * 8 // bits) rows of ``dtype``:
    unpack, then one f32 multiply by the row scale — the op sequence of
    ``PageFormat.dequantize``.  ``s_ref`` holds the page's (1, ps) row
    scales; the select-and-sum turns them into a (ps, 1) column exactly
    (every sum adds one scale to zeros)."""
    ps = s_ref.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (ps, ps), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (ps, ps), 1))
    col = jnp.sum(jnp.where(eye, s_ref[0], 0.0), axis=1, keepdims=True)
    return (unpack(packed, bits, axis=-1).astype(jnp.float32)
            * col).astype(dtype)


def _scale_spec(ps):
    """BlockSpec of a per-row scale pool viewed as (N, 1, ps)."""
    return pl.BlockSpec((1, 1, ps), lambda b_, j, t, *_: (
        jnp.maximum(t[b_, j], 0), 0, 0))


# ---------------------------------------------------------------------------
# GQA: per-logical-page partials of q against the (N, ps, KV, dh) pool.
# ---------------------------------------------------------------------------

def _gqa_page_kernel(tbl_ref, qmax_ref, kvv_ref, q_ref, k_ref, v_ref, *refs,
                     kv, ps, scale, bits):
    if bits is None:
        qp_ref, m_ref, l_ref, acc_ref = refs
    else:
        ks_ref, vs_ref, qp_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    k0 = j * ps                         # first logical row of the page
    kvs = kvv_ref[b]                    # filled-row bound of slot b
    # A page participates iff it is resident on this shard AND at least
    # one of its rows passes the causal/fill predicates.  Skipped pages
    # write the exact flash identities the lax path computes for them.
    active = (tbl_ref[b, j] >= 0) & (k0 <= qmax_ref[b]) & (k0 < kvs)

    @pl.when(active)
    def _():
        qt = q_ref[0]                   # (KV, dh, Sq*G) queries, transposed
        kb, vt = [], []                 # the mapped page, per head
        for h in range(kv):
            kh, vh = k_ref[0, :, h, :], v_ref[0, :, h, :]   # (ps, dh|dv)
            if bits is not None:
                kh = _dequant(kh, ks_ref, bits, qt.dtype)
                vh = _dequant(vh, vs_ref, bits, qt.dtype)
            kb.append(kh)
            vt.append(vh.T)
        kb, vt = jnp.stack(kb), jnp.stack(vt)   # (KV, ps, dh), (KV, dv, ps)
        # scores key-major, (KV, ps, Sq*G): the query rows run along lanes
        s = jnp.einsum("ksd,kdq->ksq", kb, (qt * scale).astype(qt.dtype),
                       preferred_element_type=jnp.float32)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape[1:], 0)
        mask = (kpos <= qp_ref[0]) & (kpos < kvs)   # qp_ref[0]: (1, rows)
        s = jnp.where(mask, s, NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)       # (KV, 1, Sq*G)
        w = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m))
        m_ref[0, 0] = m
        l_ref[0, 0] = jnp.sum(w, axis=1, keepdims=True)
        acc_ref[0, 0] = jnp.einsum("kds,ksq->kdq", vt, w.astype(qt.dtype),
                                   preferred_element_type=jnp.float32)

    @pl.when(~active)
    def _():
        _write_identities(m_ref, l_ref, acc_ref)


def paged_flash_decode_partials(k_pool, v_pool, q, tbl, qpos, kv_valid, *,
                                k_scale=None, v_scale=None,
                                bits: int | None = None,
                                interpret: bool | None = None):
    """Fused per-logical-page flash partials against the paged KV pool.

    Drop-in for ``_page_partials(q, paged_gather(k_pool, tbl),
    paged_gather(v_pool, tbl), tbl, qpos, kv_valid)`` without the HBM
    window:  k_pool/v_pool ``(N, ps, KV, dh|dv)`` (the shard-LOCAL pool
    slice inside shard_map), ``tbl`` (B, P) local page table (-1 =
    unmapped / other shard), ``qpos`` (B, Sq) query positions, and
    ``kv_valid`` (B,) filled-row bounds.  Returns f32 ``m``/``l``
    (B, Sq, KV, G, P) and ``acc`` (B, Sq, KV, G, P, dv) — bit-identical
    to the lax path for f32 pools (see module docstring).

    QUANTIZED pools: pass ``k_scale``/``v_scale`` ((N, ps) f32 per-row
    scale pools, striped like the data pools) and ``bits`` (8 or 4; the
    pools then hold packed int8 with last dim ``dh * bits // 8``).  The
    scale blocks ride the SAME table-indexed BlockSpec as the data pages
    and the block is dequantized in VMEM; the softmax scale and the
    ``acc`` width use the FULL feature dims, matching the lax dequant
    path exactly."""
    n, ps, kv, dh = k_pool.shape
    dv = v_pool.shape[-1]
    if bits is not None:
        dh, dv = dh * pack_factor(bits), dv * pack_factor(bits)
    b, sq, hq, _ = q.shape
    p = tbl.shape[1]
    g = hq // kv
    rows = sq * g
    # (B, Sq, KV*G, dh) -> one (Sq*G, dh) row tile per KV head; row
    # i*G + gg is query i of group member gg, at position qpos[b, i].
    qt = q.reshape(b, sq, kv, g, dh).transpose(0, 2, 4, 1, 3).reshape(
        b, kv, dh, rows)
    qrow = jnp.repeat(qpos, g, axis=1)[:, None, :]
    pool_idx = lambda b_, j, t, *_: (jnp.maximum(t[b_, j], 0), 0, 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, kv, dh, rows), lambda b_, j, *_: (b_, 0, 0, 0)),
        pl.BlockSpec((1, ps, kv, k_pool.shape[-1]), pool_idx),
        pl.BlockSpec((1, ps, kv, v_pool.shape[-1]), pool_idx),
    ]
    operands = [qt, k_pool, v_pool]
    if bits is not None:
        in_specs += [_scale_spec(ps), _scale_spec(ps)]
        operands += [k_scale.reshape(n, 1, ps), v_scale.reshape(n, 1, ps)]
    in_specs.append(pl.BlockSpec((1, 1, rows), lambda b_, j, *_: (b_, 0, 0)))
    operands.append(qrow)
    row_spec = pl.BlockSpec((1, 1, kv, 1, rows),
                            lambda b_, j, *_: (b_, j, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, p),
        in_specs=in_specs,
        out_specs=[row_spec, row_spec,
                   pl.BlockSpec((1, 1, kv, dv, rows),
                                lambda b_, j, *_: (b_, j, 0, 0, 0))])
    kernel = functools.partial(_gqa_page_kernel, kv=kv, ps=ps,
                               scale=dh ** -0.5, bits=bits)
    row_shape = jax.ShapeDtypeStruct((b, p, kv, 1, rows), jnp.float32)
    m, l, acc = _call(
        kernel, grid_spec,
        [row_shape, row_shape,
         jax.ShapeDtypeStruct((b, p, kv, dv, rows), jnp.float32)],
        [tbl, jnp.max(qpos, axis=1), kv_valid.astype(jnp.int32), *operands],
        _resolve_interpret(interpret))
    # back to the callers' (B, Sq, KV, G, P[, dv]) layout
    m, l = (x.reshape(b, p, kv, sq, g).transpose(0, 3, 2, 4, 1)
            for x in (m, l))
    acc = acc.reshape(b, p, kv, dv, sq, g).transpose(0, 4, 2, 5, 1, 3)
    return m, l, acc


# ---------------------------------------------------------------------------
# MLA: compressed-space partials against the (N, ps, r+dr) latent pool.
# ---------------------------------------------------------------------------

def _mla_page_kernel(tbl_ref, pos_ref, pool_ref, *refs, ps, r, dr, scale,
                     bits):
    if bits is None:
        qc_ref, qr_ref, m_ref, l_ref, acc_ref = refs
    else:
        s_ref, qc_ref, qr_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    k0 = j * ps
    pb = pos_ref[b]                     # slot position (-1 = inactive)
    active = (tbl_ref[b, j] >= 0) & (k0 <= pb)

    @pl.when(active)
    def _():
        qc = qc_ref[0]                  # (Sq*H, r) absorbed queries
        qr = qr_ref[0]                  # (Sq*H, dr)
        blk = pool_ref[0]               # (ps, r+dr) — the mapped page
        if bits is not None:            # one scale spans c_kv and k_rope
            blk = _dequant(blk, s_ref, bits, qc.dtype)
        c, kr = blk[:, :r], blk[:, r:r + dr]
        nt = (((1,), (1,)), ((), ()))
        sc = jax.lax.dot_general(qc, c, nt,
                                 preferred_element_type=jnp.float32)
        sc += jax.lax.dot_general(qr, kr, nt,
                                  preferred_element_type=jnp.float32)
        sc = sc * scale
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(kpos <= pb, sc, NEG_INF)
        m = jnp.max(sc, axis=-1, keepdims=True)
        w = jnp.where(sc <= NEG_INF / 2, 0.0, jnp.exp(sc - m))
        m_ref[0, 0] = m
        l_ref[0, 0] = jnp.sum(w, axis=-1, keepdims=True)
        acc_ref[0, 0] = jnp.dot(w.astype(qc.dtype), c,
                                preferred_element_type=jnp.float32)

    @pl.when(~active)
    def _():
        _write_identities(m_ref, l_ref, acc_ref)


def mla_paged_decode_partials(pool, q_c, q_rope, tbl, pos_b, r, scale_dim, *,
                              scale_pool=None, bits: int | None = None,
                              interpret: bool | None = None):
    """Fused compressed-space page partials for MLA absorbed decode.

    Replaces the gather + inline partials in ``mla._mla_paged_decode``:
    ``pool`` (N, ps, >= r+dr) shard-local latent pool, ``q_c`` (B, Sq, H, r)
    absorbed queries, ``q_rope`` (B, Sq, H, dr), ``tbl`` (B, P) local
    table, ``pos_b`` (B,) slot positions.  The weighted sum stays in the
    COMPRESSED space — ``acc`` is (B, Sq, H, P, r) — so the caller's
    cross-shard psum still moves r floats per head per page.  Returns
    f32 ``(m, l, acc)`` bit-identical to the lax body for f32 pools.

    QUANTIZED pools: pass ``scale_pool`` ((N, ps) f32) and ``bits``; the
    pool then stores packed int8 rows of width ``(r+dr) * bits // 8``,
    dequantized in VMEM before the split at ``r``."""
    n, ps, _ = pool.shape
    b, sq, h, _ = q_c.shape
    dr = q_rope.shape[-1]           # fp rows may be stored wider (zeros)
    p = tbl.shape[1]
    rows = sq * h
    in_specs = [pl.BlockSpec((1, ps, pool.shape[-1]), lambda b_, j, t, *_: (
        jnp.maximum(t[b_, j], 0), 0, 0))]
    operands = [pool]
    if bits is not None:
        in_specs.append(_scale_spec(ps))
        operands.append(scale_pool.reshape(n, 1, ps))
    in_specs += [
        pl.BlockSpec((1, rows, r), lambda b_, j, *_: (b_, 0, 0)),
        pl.BlockSpec((1, rows, dr), lambda b_, j, *_: (b_, 0, 0)),
    ]
    operands += [q_c.reshape(b, rows, r), q_rope.reshape(b, rows, dr)]
    row_spec = pl.BlockSpec((1, 1, rows, 1), lambda b_, j, *_: (b_, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, p),
        in_specs=in_specs,
        out_specs=[row_spec, row_spec,
                   pl.BlockSpec((1, 1, rows, r),
                                lambda b_, j, *_: (b_, j, 0, 0))])
    kernel = functools.partial(_mla_page_kernel, ps=ps, r=r, dr=dr,
                               scale=scale_dim ** -0.5, bits=bits)
    row_shape = jax.ShapeDtypeStruct((b, p, rows, 1), jnp.float32)
    m, l, acc = _call(
        kernel, grid_spec,
        [row_shape, row_shape,
         jax.ShapeDtypeStruct((b, p, rows, r), jnp.float32)],
        [tbl, pos_b.astype(jnp.int32), *operands],
        _resolve_interpret(interpret))
    # back to the callers' (B, Sq, H, P[, r]) layout
    m, l = (x.reshape(b, p, sq, h).transpose(0, 2, 3, 1) for x in (m, l))
    acc = acc.reshape(b, p, sq, h, r).transpose(0, 2, 3, 1, 4)
    return m, l, acc
