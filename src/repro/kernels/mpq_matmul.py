"""Mixed-precision quantized matmul — Flex-V's dotp unit as a Pallas kernel.

The silicon keeps sub-byte operands packed in registers and expands lanes in
the Slicer&Router (paper Fig. 6/7) so the dot-product units always see full
words.  The TPU-native re-derivation (DESIGN.md §2-C1):

  * packed operand tiles stream HBM -> VMEM through the BlockSpec pipeline
    (double-buffered by the Pallas emitter = DORY's DMA overlap),
  * lanes are expanded *inside VMEM* with shift/mask + block concat
    (repro.core.packing.unpack — the Slicer&Router),
  * the MXU consumes the expanded int8 words with int32 accumulation
    (`preferred_element_type`), or bf16 words for the weight-only path,
  * the operand *format* (a_bits, w_bits) is static kernel state, mirroring
    the CSR-driven "dynamic bit-scalable execution": one kernel body, six
    formats (Table IV).

Grid is (M/bm, N/bn, K/bk) with the contraction innermost and a VMEM
accumulator scratch, so each (i, j) output tile sees its K tiles in order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import pack_factor, unpack


def _int_kernel(x_ref, w_ref, xs_ref, ws_ref, out_ref, acc_ref, *,
                a_bits: int, w_bits: int, n_k: int):
    """int{8,4,2} x int{8,4,2} -> f32, per-row x per-channel dequant."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if pack_factor(a_bits) > 1:
        x = unpack(x, a_bits, axis=1)          # (bm, bk) int8
    w = w_ref[...]
    if pack_factor(w_bits) > 1:
        w = unpack(w, w_bits, axis=0)          # (bk, bn) int8
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _done():
        out = acc_ref[...].astype(jnp.float32) * xs_ref[...] * ws_ref[...]
        out_ref[...] = out.astype(out_ref.dtype)


def _wo_kernel(x_ref, w_ref, ws_ref, out_ref, acc_ref, *,
               w_bits: int, n_k: int):
    """bf16 x packed int{8,4,2} -> bf16; scale applied after accumulation."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]
    if pack_factor(w_bits) > 1:
        w = unpack(w, w_bits, axis=0)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w.astype(x_ref.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        out_ref[...] = (acc_ref[...] * ws_ref[...]).astype(out_ref.dtype)


# grid (M/bm, N/bn, K/bk): output tiles are independent, the contraction
# is sequential into the accumulator scratch.
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(
    jax.jit, static_argnames=("a_bits", "w_bits", "bm", "bk", "bn",
                              "out_dtype", "interpret"))
def mpq_matmul_kernel(x_q, x_scale, w_packed, w_scale, *, a_bits: int,
                      w_bits: int, bm: int, bk: int, bn: int,
                      out_dtype=jnp.float32, interpret: bool = False):
    """Integer-path pallas_call.  Shapes (already padded to tiles):

    x_q (M, K//fa) int8 packed, x_scale (M, 1) f32,
    w_packed (K//fw, N) int8, w_scale (1, N) f32  ->  (M, N) out_dtype.
    """
    fa, fw = pack_factor(a_bits), pack_factor(w_bits)
    m, n = x_q.shape[0], w_packed.shape[1]
    k = w_packed.shape[0] * fw
    assert x_q.shape[1] * fa == k, (x_q.shape, w_packed.shape, a_bits, w_bits)
    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(
        _int_kernel, a_bits=a_bits, w_bits=w_bits, n_k=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk // fa), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // fw, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(x_q, w_packed, x_scale, w_scale)


@functools.partial(
    jax.jit, static_argnames=("w_bits", "bm", "bk", "bn", "out_dtype",
                              "interpret"))
def wo_matmul_kernel(x, w_packed, w_scale, *, w_bits: int, bm: int, bk: int,
                     bn: int, out_dtype=None, interpret: bool = False):
    """Weight-only pallas_call: x (M, K) bf16/f32, w_packed (K//fw, N) int8,
    w_scale (1, N) f32 -> (M, N)."""
    out_dtype = out_dtype or x.dtype
    fw = pack_factor(w_bits)
    m, n = x.shape[0], w_packed.shape[1]
    k = x.shape[1]
    assert w_packed.shape[0] * fw == k
    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(_wo_kernel, w_bits=w_bits, n_k=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // fw, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(x, w_packed, w_scale)
