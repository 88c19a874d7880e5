"""Causal flash attention (forward) — the §Perf kernel-level lever.

The roofline profiles (EXPERIMENTS.md §Perf HC-2/HC-3) show the jnp
attention path bounded by f32 score-chain HBM traffic (~4-6 passes over
(B, Sq, H, Skv) blocks per layer).  This kernel keeps scores in VMEM:

  * grid (B, H, Sq/bq): each program owns one query block of one head,
  * K/V for that (batch, kv-head) live as VMEM blocks; the kernel walks
    them in `bk`-sized windows with the online-softmax recurrence
    (running max / denominator), never materializing scores to HBM,
  * causal skipping: the window loop stops at the query block's diagonal
    (the masked-future half is never computed — the jnp path spends 2x
    FLOPs there),
  * GQA: kv-head index = q-head // group, resolved in the BlockSpec
    index maps (no KV replication in HBM).

HBM traffic becomes q + k + v + o exactly; validated against the model's
SDPA oracle in interpret mode (tests/test_flash_attention.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int,
                  scale: float, causal: bool, kv_valid: int):
    iq = pl.program_id(2)
    q = q_ref[0, :, 0, :]                          # (bq, dh)
    skv = k_ref.shape[1]
    q0 = iq * bq

    # causal: only windows up to the block diagonal participate.
    hi = jnp.minimum(q0 + bq, kv_valid) if causal else kv_valid
    n_win = pl.cdiv(skv, bk) if not causal else pl.cdiv(
        jnp.minimum(q0 + bq, skv), bk)

    def body(w, carry):
        m, l, acc = carry
        k0 = w * bk
        k = k_ref[0, pl.dslice(k0, bk), 0, :]      # (bk, dh)
        v = v_ref[0, pl.dslice(k0, bk), 0, :]
        s = jax.lax.dot_general(
            (q * scale).astype(q.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bq, bk)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        mask = kpos < hi
        if causal:
            mask = mask & (kpos <= qpos)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[:, None]))
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_new = acc * corr[:, None] + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, q_ref.shape[3]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_win, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0, :, 0, :] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bq", "bk", "causal", "kv_valid", "interpret"))
def flash_attention(q, k, v, *, bq: int = 256, bk: int = 256,
                    causal: bool = True, kv_valid: int | None = None,
                    interpret: bool = False):
    """q: (B, Sq, H, dh); k/v: (B, Skv, KV, dh) with H % KV == 0.

    Returns (B, Sq, H, dh) in q.dtype.  Sq must divide by bq and Skv by bk
    (callers pad; the model path guarantees 128-multiples).
    """
    b, sq, h, dh = q.shape
    skv, kv = k.shape[1], k.shape[2]
    g = h // kv
    kv_valid = skv if kv_valid is None else kv_valid
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0
    grid = (b, h, sq // bq)
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, scale=dh ** -0.5, causal=causal,
        kv_valid=kv_valid)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, dh), lambda ib, ih, iq: (ib, iq, ih, 0)),
            pl.BlockSpec((1, skv, 1, dh),
                         lambda ib, ih, iq, g=g: (ib, 0, ih // g, 0)),
            pl.BlockSpec((1, skv, 1, dh),
                         lambda ib, ih, iq, g=g: (ib, 0, ih // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, dh),
                               lambda ib, ih, iq: (ib, iq, ih, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq, h, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
