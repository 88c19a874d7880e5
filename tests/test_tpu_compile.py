"""Compile-only checks for TPU v5e: nothing here runs, everything compiles.

The TPU compiler is installed beside the CPU backend, so a described
``v5e:2x2`` topology lets the chip's own compiler see the main serving
path at its real widths: the fused paged-decode kernel (qwen2.5-3b GQA
and deepseek-v2-lite MLA pools), the weight-only w4 matmul kernel, and
the whole 36-layer qwen2.5-3b paged decode step from abstract shapes, and
that its decode and resumed-prefill steps write a donated paged pool in
place (no pool-sized copy, update or allocation).
Mosaic refuses here what the Pallas interpreter accepts (block shapes not
tiled by (8, 128), dots it cannot lower, too much VMEM), so these tests
catch a kernel that would fail on the chip at no chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""
import contextlib
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.distributed.sharding import use_rules
from repro.kernels import paged_flash_decode as K
from repro.kernels.mpq_matmul import wo_matmul_kernel
from repro.models import abstract_params
from repro.models.model import abstract_paged_cache
from repro.train.step import (make_paged_chunked_prefill_step,
                              make_paged_decode_step)

# serving shapes: 8 slots, 64 logical pages of 16 rows (1024-row window),
# a 1024-page pool.
B, P, N, PS = 8, 64, 1024, 16
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # noqa: BLE001 - any failure skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _gqa_case(dtype, bits, sq):
    """qwen2.5-3b widths: 2 KV heads, 8 query heads per KV head, dh 128."""
    kv, g, dh = 2, 8, 128
    w = dh if bits is None else dh * bits // 8
    pool_dt = dtype if bits is None else jnp.int8
    shapes = [((N, PS, kv, w), pool_dt), ((N, PS, kv, w), pool_dt),
              ((B, sq, kv * g, dh), dtype), ((B, P), jnp.int32),
              ((B, sq), jnp.int32), ((B,), jnp.int32)]
    if bits is None:
        def fn(kp, vp, q, t, qpos, kvv):
            return K.paged_flash_decode_partials(kp, vp, q, t, qpos, kvv,
                                                 interpret=False)
    else:
        shapes += [((N, PS), jnp.float32)] * 2

        def fn(kp, vp, q, t, qpos, kvv, ks, vs):
            return K.paged_flash_decode_partials(
                kp, vp, q, t, qpos, kvv, k_scale=ks, v_scale=vs, bits=bits,
                interpret=False)
    return fn, shapes


def _mla_case(dtype, bits, sq):
    """deepseek-v2-lite widths: latent rank 512, rope 64, 16 heads."""
    r, dr, h = 512, 64, 16
    w = (r + dr) if bits is None else (r + dr) * bits // 8
    pool_dt = dtype if bits is None else jnp.int8
    shapes = [((N, PS, w), pool_dt), ((B, sq, h, r), dtype),
              ((B, sq, h, dr), dtype), ((B, P), jnp.int32), ((B,), jnp.int32)]
    if bits is None:
        def fn(pool, qc, qr, t, pos):
            return K.mla_paged_decode_partials(pool, qc, qr, t, pos, r,
                                               128 + dr, interpret=False)
    else:
        shapes.append(((N, PS), jnp.float32))

        def fn(pool, qc, qr, t, pos, sp):
            return K.mla_paged_decode_partials(
                pool, qc, qr, t, pos, r, 128 + dr, scale_pool=sp, bits=bits,
                interpret=False)
    return fn, shapes


@pytest.mark.parametrize("family,dtype,bits,sq", [
    ("gqa", jnp.bfloat16, None, 1),
    ("gqa", jnp.bfloat16, None, 16),    # a resumed prefill chunk
    ("gqa", jnp.float32, None, 1),
    ("gqa", jnp.bfloat16, 8, 1),
    ("gqa", jnp.bfloat16, 4, 1),
    ("mla", jnp.bfloat16, None, 1),
    ("mla", jnp.bfloat16, 8, 1),
], ids=["gqa-bf16", "gqa-bf16-chunk", "gqa-f32", "gqa-int8", "gqa-int4",
        "mla-bf16", "mla-int8"])
def test_paged_decode_kernel_compiles(one_chip, family, dtype, bits, sq):
    fn, shapes = (_gqa_case if family == "gqa" else _mla_case)(
        dtype, bits, sq)
    compiled = _compile(fn, *(jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                              for s, d in shapes))
    assert "tpu_custom_call" in compiled.as_text()


def test_wo_matmul_w4_compiles(one_chip):
    """qwen2.5-3b's MLP up projection, 2048 -> 11008, w4 weight-only."""
    m, k, n, bits = 128, 2048, 11008, 4
    shapes = [((m, k), jnp.bfloat16), ((k * bits // 8, n), jnp.int8),
              ((1, n), jnp.float32)]

    def fn(x, w, s):
        return wo_matmul_kernel(x, w, s, w_bits=bits, bm=128, bk=512,
                                bn=256)
    compiled = _compile(fn, *(jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                              for s, d in shapes))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", [False, True], ids=["lax", "pallas"])
def test_qwen_paged_decode_step_compiles(topo, one_chip, kernel):
    """The full-width qwen2.5-3b decode step on one chip: bf16 weights plus
    a paged pool fit the chip's 16 GB.  With the kernel on, the step runs
    under a one-device page-striped mesh, as the engine does."""
    cfg = get_config("qwen2.5-3b")
    params = _shapes(abstract_params(cfg), one_chip)
    cache = _shapes(abstract_paged_cache(cfg, B, N, PS), one_chip)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    pages = jax.ShapeDtypeStruct((B, P), jnp.int32, sharding=one_chip)
    step = make_paged_decode_step(cfg)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    with use_rules(mesh, "fsdp_sp"), \
            K.use_pallas_decode(enabled=kernel, interpret=False):
        compiled = _compile(step, params, cache, tok, pos, pages)
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES, mem


# one HLO instruction: name = dtype[dims]{layout} opcode(
_HLO_OP = re.compile(r"%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def _ops_of_size(hlo: str, n_elements: int):
    """(opcode, line) of every instruction of ``hlo`` whose array result
    holds ``n_elements`` elements, in any shape; a custom call is named
    by its target."""
    ops = []
    for line in hlo.splitlines():
        m = _HLO_OP.search(line)
        if not m or math.prod(
                int(d) for d in m.group(2).split(",") if d) != n_elements:
            continue
        op = m.group(3)
        target = re.search(r'custom_call_target="([^"]+)"', line)
        ops.append((target.group(1) if target else op, line.strip()))
    return ops


@pytest.mark.parametrize("program", ["decode", "resumed_prefill"])
@pytest.mark.parametrize("striped", [False, True],
                         ids=["replicated", "fsdp_sp"])
def test_qwen_paged_pool_written_in_place(topo, one_chip, program, striped):
    """The layer scan carries the stacked paged pool and each layer
    scatters only its new rows into it, so with the cache donated (as
    the engine jits its steps) the full-width qwen2.5-3b decode and
    resumed-prefill steps hold no copy, dynamic-update-slice or
    allocated buffer the size of a pool leaf, and decode needs
    (almost) no temporaries beside it."""
    cfg = get_config("qwen2.5-3b")
    params = _shapes(abstract_params(cfg), one_chip)
    cache = _shapes(abstract_paged_cache(cfg, B, N, PS), one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.int32, sharding=one_chip)
    if program == "decode":
        step = make_paged_decode_step(cfg)
        args = (params, cache, i32(B, 1), i32(B), i32(B, P))
    else:
        step = make_paged_chunked_prefill_step(cfg)
        args = (params, cache, i32(B, 128), i32(B), i32(B, P), i32(B))
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    with use_rules(mesh, "fsdp_sp") if striped else contextlib.nullcontext():
        compiled = jax.jit(step, donate_argnums=1).lower(*args).compile()
    leaf = jax.tree.leaves(cache)[0]
    ops = _ops_of_size(compiled.as_text(), math.prod(leaf.shape))
    assert any(op == "parameter" for op, _ in ops), \
        "the pool leaves should be found among the parameters"
    bad = [line for op, line in ops
           if op in ("copy", "dynamic-update-slice", "AllocateBuffer")]
    assert not bad, bad
    if program == "decode":
        pool_bytes = sum(x.size * x.dtype.itemsize
                         for x in jax.tree.leaves(cache))
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 0.05 * pool_bytes, (temp, pool_bytes)
