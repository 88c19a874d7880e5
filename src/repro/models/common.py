"""Declarative parameters + shared layers for the model zoo.

Parameters are declared as trees of :class:`ParamSpec` (shape + logical
sharding axes + init), which can be

  * materialized   -> real arrays (smoke tests, examples, training),
  * abstracted     -> jax.ShapeDtypeStruct (the multi-pod dry-run lowers
                      train/serve steps against 34B-parameter models with
                      ZERO host allocation),
  * sharded        -> NamedSharding via the logical rule table in
                      repro.distributed.sharding.

Every matmul weight is a plain (in, out) array; layers that want heads
reshape afterwards.  Quantization ("the CSR", DESIGN.md §3) is applied by
``dense``: QAT fake-quant in training mode, packed sub-byte kernels when a
leaf has been converted to a PackedWeight for serving.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import QuantConfig, fake_quant_activation, fake_quant_weight
from repro.distributed.sharding import lshard
from repro.kernels.ops import PackedWeight, quantized_matmul


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | embed
    scale: Optional[float] = None  # stddev override (default 1/sqrt(fan_in))
    dtype: Any = None              # override model dtype (norms stay f32)
    quantize: bool = False         # eligible for sub-byte packing (serving)
    stacked: int = 0               # leading scan-stacked dims to skip in fan-in

    def fan_in(self) -> int:
        core = self.shape[self.stacked:]
        axes = self.axes[self.stacked:]
        # leading batch-like dims (expert banks, per-head recurrences) do
        # not contribute to fan-in.
        while len(core) > 1 and axes and axes[0] in ("expert", "heads",
                                                     "layers"):
            core, axes = core[1:], axes[1:]
        if len(core) <= 1:
            return core[-1]
        import math
        return math.prod(core[:-1])


def is_spec_tree_leaf(x) -> bool:
    return isinstance(x, ParamSpec)


def stack_spec(spec: ParamSpec, n: int) -> ParamSpec:
    return dataclasses.replace(
        spec, shape=(n,) + spec.shape, axes=("layers",) + spec.axes,
        stacked=spec.stacked + 1)


def stack_specs(tree, n: int):
    return jax.tree.map(lambda s: stack_spec(s, n), tree,
                        is_leaf=is_spec_tree_leaf)


def materialize(tree, key: jax.Array, dtype=jnp.bfloat16):
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_spec_tree_leaf)
    keys = jax.random.split(key, len(leaves))
    out = []
    for spec, k in zip(leaves, keys):
        dt = spec.dtype or dtype
        if spec.init == "zeros":
            v = jnp.zeros(spec.shape, dt)
        elif spec.init == "ones":
            v = jnp.ones(spec.shape, dt)
        else:
            std = spec.scale if spec.scale is not None else spec.fan_in() ** -0.5
            if spec.init == "embed":
                std = 1.0
            v = (jax.random.normal(k, spec.shape, jnp.float32) * std).astype(dt)
        out.append(v)
    return jax.tree.unflatten(treedef, out)


def abstract(tree, dtype=jnp.bfloat16):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or dtype), tree,
        is_leaf=is_spec_tree_leaf)


def spec_axes(tree):
    return jax.tree.map(lambda s: s.axes, tree, is_leaf=is_spec_tree_leaf)


def param_count(tree) -> int:
    import math
    return sum(math.prod(s.shape) for s in
               jax.tree.leaves(tree, is_leaf=is_spec_tree_leaf))


# ---------------------------------------------------------------------------
# Shared layers.
# ---------------------------------------------------------------------------

def dense(x: jax.Array, w, quant: Optional[QuantConfig] = None,
          bias: Optional[jax.Array] = None) -> jax.Array:
    """y = x @ w (+ bias), honouring the quantization mode.

    w is either a raw (K, N) array or a PackedWeight (serving).  QAT mode
    fake-quantizes both operands with STE so online learning trains against
    the deployment arithmetic (paper §VI-C).
    """
    if isinstance(w, PackedWeight):
        assert quant is not None and quant.mode in ("int", "wo")
        y = quantized_matmul(x, w, quant, use_kernel=quant.use_kernel)
    elif quant is not None and quant.mode == "qat":
        wq = fake_quant_weight(w, quant)
        xq = fake_quant_activation(x, quant)
        y = xq @ wq
    elif quant is not None and quant.mode in ("int", "wo"):
        # raw weights but an int/wo config: emulate deployment numerics with
        # fake-quant (used by the dry-run, which lowers the jnp path).
        wq = fake_quant_weight(w, quant)
        if quant.mode == "int":
            x = fake_quant_activation(x, quant)
        y = x @ wq
    else:
        y = x @ w
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def chunk_lengths(pos, batch: int) -> jax.Array:
    """Per-slot valid lengths from a mode='chunk' ``pos`` ((B,) or scalar)."""
    return jnp.broadcast_to(jnp.atleast_1d(pos), (batch,))


def chunk_valid_mask(len_b: jax.Array, seq: int) -> jax.Array:
    """(B, S) True at valid (non-padding) positions of a right-padded
    chunk whose per-slot valid counts are ``len_b``.  The single change
    point for chunked-prefill padding semantics across all families."""
    return jnp.arange(seq, dtype=jnp.int32)[None, :] < len_b[:, None]


def broadcast_offset(offset, batch: int) -> jax.Array:
    """Per-slot start rows from a resumable-chunk ``offset`` ((B,) or
    scalar) — the single change point for offset normalization across
    all families."""
    return jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(offset, jnp.int32)), (batch,))


def verify_greedy_tokens(logits: jax.Array) -> jax.Array:
    """(B, S) greedy token per row of a (B, S, V) speculative-VERIFY
    logits block, argmaxed in f32 — the engine's temperature-0 sampler
    numerics exactly (same upcast, same lowest-index tie break), so a
    draft-vs-target acceptance comparison is decided by the very argmax
    plain greedy decode would have emitted.  The single change point for
    multi-token verify gathering: the serving engine and the drafter
    both read proposals/verdicts through this."""
    return jnp.argmax(logits.astype(jnp.float32), axis=-1)


def contig_scatter(buf: jax.Array, rows: jax.Array, t: jax.Array,
                   valid: jax.Array) -> jax.Array:
    """Scatter per-slot rows into a CONTIGUOUS (B, cap, *rest) cache at
    logical positions ``t`` (B, S); the offset-write analogue of
    :func:`paged_scatter` for resumable chunked prefill against unpaged
    caches.  Invalid or out-of-window writes are DROPPED, so a padded or
    inactive slot never touches the buffer.
    """
    bsz, cap = buf.shape[:2]
    flat = buf.reshape((bsz * cap,) + buf.shape[2:])
    ok = valid & (t >= 0) & (t < cap)
    dest = jnp.where(
        ok, jnp.arange(bsz, dtype=jnp.int32)[:, None] * cap + t, bsz * cap)
    flat = flat.at[dest.reshape(-1)].set(
        rows.astype(buf.dtype).reshape((-1,) + rows.shape[2:]), mode="drop")
    return flat.reshape(buf.shape)


def page_resident_rows(pages: jax.Array, page_size: int) -> jax.Array:
    """(B, P*page_size) bool: True where the logical row's page-table
    entry is mapped.  The RESIDENCY mask for attention over a
    :func:`paged_gather` window — under the two-tiered pool a page may be
    parked on the host (entry -1), and its garbage-gathered rows must
    never reach a softmax.  The serving engine already gates dispatches
    on full residency, so in every legal dispatch this mask is all-True
    over the valid window and the AND below it leaves the attention mask
    — and therefore the logits — bit-identical (defense in depth, not a
    semantic change)."""
    return jnp.repeat(pages >= 0, page_size, axis=1)


def paged_gather(pool: jax.Array, pages: jax.Array,
                 layer=None) -> jax.Array:
    """Gather a slot's logical cache window out of a paged row pool.

    ``pool``: (num_pages, page_size, *rest) physical pages shared by every
    slot; ``pages``: (B, P) int32 per-slot page table (-1 = unmapped).
    Returns (B, P*page_size, *rest) rows in logical order — row ``t`` of
    slot ``b`` lives at physical row ``pages[b, t // page_size] * page_size
    + t % page_size``.  Rows under unmapped entries are garbage (the index
    clamps) and MUST be masked by the caller's validity predicate
    (``kv_valid`` / ``kpos <= pos``), exactly as rows past the fill level
    already are in the contiguous layout.

    ``layer``: with a (traced) layer index, ``pool`` is the STACKED
    (L, num_pages, page_size, *rest) leaf the layer scan carries, and the
    window is read straight out of layer ``layer``'s pages (page
    ``layer * num_pages + page`` of the leaf seen as one run of pages) —
    the same rows ``paged_gather(pool[layer], pages)`` reads, without
    slicing the layer out first.

    This materializes the whole (B, P*page_size, *rest) window in HBM
    before any score math runs.  On the page-striped decode/resume hot
    path, ``ServeConfig.use_pallas_decode`` replaces this gather + the
    partials reduction with the fused kernel in
    :mod:`repro.kernels.paged_flash_decode`, which reads pool pages
    inside the kernel through the page table and never builds the
    window; this function remains the canonical layout definition (and
    the prefill/replicated-pool path).
    """
    lead = 0 if layer is None else 1
    n, ps = pool.shape[lead:lead + 2]
    page = jnp.maximum(pages, 0)
    if layer is not None:
        page = page + layer * n
    if pool.ndim - lead == 3:
        # rows of one feature axis (MLA's latent): take whole pages by
        # index.  Sliced out of the row view, XLA:TPU runs this gather as
        # a loop of one dynamic-update-slice per page.
        win = jnp.take(pool.reshape((-1,) + pool.shape[lead + 1:]), page,
                       axis=0, mode="clip")
        return win.reshape((pages.shape[0], -1) + win.shape[3:])
    flat = pool.reshape((-1,) + pool.shape[lead + 2:])
    # whole pages of ps rows, not single rows: a page is one contiguous
    # block of the pool, and a row of a few KV heads is too small to
    # fetch alone at speed.  Slicing the row view keeps the pool's layout.
    take = jax.vmap(jax.vmap(
        lambda r: jax.lax.dynamic_slice_in_dim(flat, r, ps)))
    win = take(page * ps)
    return win.reshape((pages.shape[0], -1) + win.shape[3:])


def stacked_as_pool(pages: jax.Array, layer, *pools: jax.Array):
    """Layer ``layer`` of stacked (L, num_pages, page_size, *rest) pool
    leaves, for the fused kernels, which take plain page pools: each leaf
    as one (L * num_pages, page_size, *rest) pool (a free reshape), and
    the page table that addresses layer ``layer`` in it (mapped entries
    move by ``layer * num_pages``, -1 stays unmapped).  Returns
    ``(table, *pools)``."""
    n = pools[0].shape[1]
    return (jnp.where(pages >= 0, pages + layer * n, -1),
            *(p.reshape((-1,) + p.shape[2:]) for p in pools))


def shard_local_pages(pages: jax.Array, page0, n_local: int) -> jax.Array:
    """Translate a GLOBAL page table to shard-local physical indices.

    ``pages``: (B, P) global page table (-1 = unmapped); ``page0``: first
    global page resident on this shard; ``n_local``: pages per shard.
    Entries outside [page0, page0 + n_local) — unmapped or resident on
    another shard — become -1, so :func:`paged_scatter` drops their
    writes and :func:`paged_gather` callers mask their rows: each shard
    of a page-striped pool touches exactly the pages it physically
    holds, and a logical page has exactly one owning shard.
    """
    ok = (pages >= page0) & (pages < page0 + n_local)
    return jnp.where(ok, pages - page0, -1)


def paged_scatter(pool: jax.Array, pages: jax.Array, rows: jax.Array,
                  t: jax.Array, valid: jax.Array, layer=None) -> jax.Array:
    """Scatter per-slot rows into a paged pool at logical positions.

    ``pool``: (num_pages, page_size, *rest); ``pages``: (B, P) page table;
    ``rows``: (B, S, *rest) values; ``t``: (B, S) int32 logical positions;
    ``valid``: (B, S) bool.  Writes that are invalid, out of the slot's
    logical window, or land on an unmapped (-1) page-table entry are
    DROPPED — the software analogue of the IOTLB sinking an out-of-window
    AXI write — so an inactive or padded slot never touches the pool.

    ``layer``: ``pool`` is then the stacked (L, num_pages, page_size,
    *rest) leaf and only layer ``layer``'s pages are written (see
    :func:`paged_gather`).  Inside the layer scan the leaf rides in the
    carry, so the scatter updates it in place: a step writes its new rows
    and nothing else of the pool.
    """
    lead = 0 if layer is None else 1
    n, ps = pool.shape[lead:lead + 2]
    flat = pool.reshape((-1,) + pool.shape[lead + 2:])
    p = pages.shape[1]
    page = jnp.take_along_axis(pages, jnp.clip(t // ps, 0, p - 1), axis=1)
    ok = valid & (page >= 0) & (t >= 0) & (t < p * ps)
    if layer is not None:
        page = page + layer * n
    dest = jnp.where(ok, page * ps + t % ps, flat.shape[0])  # OOB = drop
    flat = flat.at[dest.reshape(-1)].set(
        rows.astype(pool.dtype).reshape((-1,) + rows.shape[2:]), mode="drop")
    return flat.reshape(pool.shape)


def paged_scatter_quant(pool: jax.Array, scales: jax.Array,
                        pages: jax.Array, rows: jax.Array, t: jax.Array,
                        valid: jax.Array, fmt, layer=None):
    """:func:`paged_scatter` for a QUANTIZED pool: quantize ``rows`` at
    the write boundary (per-row absmax, packed per ``fmt`` — see
    :mod:`repro.core.pageformat`) and scatter the packed bytes into
    ``pool`` and the f32 row scales into the pool-shaped ``scales`` leaf
    through the SAME page table.  A row's quantized bytes depend only on
    its own fp values, so re-writing identical rows (resume, swap-in,
    COW re-fill) reproduces identical pool bytes regardless of chunking.
    Returns (new_pool, new_scales); ``layer`` as in :func:`paged_scatter`."""
    q, s = fmt.quantize_rows(rows)
    return (paged_scatter(pool, pages, q, t, valid, layer),
            paged_scatter(scales, pages, s, t, valid, layer))


def paged_gather_quant(pool: jax.Array, scales: jax.Array,
                       pages: jax.Array, fmt, dtype, layer=None) -> jax.Array:
    """Gather + dequantize a slot window out of a quantized pool.

    The fp analogue of :func:`paged_gather`: unpacks and rescales the
    gathered (B, W, *rest) rows with their per-row scales.  Rows under
    unmapped table entries are garbage exactly as in the fp layout and
    MUST be masked by the caller's validity predicate.  ``layer`` as in
    :func:`paged_gather`."""
    return fmt.dequantize(paged_gather(pool, pages, layer),
                          paged_gather(scales, pages, layer), dtype)


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * w.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    h = x.astype(jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
    h = (h - mu) * jax.lax.rsqrt(var + eps)
    return (h * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def yarn_get_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor 0.1 * mscale * ln(factor) + 1
    (1 without scaling), as the published DeepSeek-V2 code computes it."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(scaling, dim: int, theta: float):
    """(low, high) rotary dims between which YaRN blends: the dims at
    which ``beta_fast`` and ``beta_slow`` full rotations fit in the
    original context, floored and ceiled into [0, dim - 1]."""
    def at(rotations):
        return dim * math.log(scaling.original_max_position_embeddings /
                              (rotations * 2 * math.pi)) / \
            (2 * math.log(theta))
    return (max(math.floor(at(scaling.beta_fast)), 0),
            min(math.ceil(at(scaling.beta_slow)), dim - 1))


def yarn_inv_freq(dim: int, theta: float, scaling) -> np.ndarray:
    """(dim/2,) float32 YaRN inverse frequencies: the base frequencies
    below the correction range, those of ``theta * factor`` above it, and
    a linear ramp between."""
    base = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    inter = base / np.float32(scaling.factor)
    low, high = yarn_correction_range(scaling, dim, theta)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) /
                   (high - low), 0, 1)
    return (inter * ramp + base * (1 - ramp)).astype(np.float32)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         scaling=None) -> jax.Array:
    """Rotary embedding, NeoX half-split convention; ``scaling`` (a
    config's ``RopeScaling``) switches to YaRN's frequencies and cos/sin
    factor.

    x: (B, S, H, D), positions: (B, S) int32.
    """
    d = x.shape[-1]
    half = d // 2
    if scaling is None:
        freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freq = jnp.asarray(yarn_inv_freq(d, theta, scaling))
    ang = positions[..., None].astype(jnp.float32) * freq  # (B, S, half)
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    if scaling is not None:
        m = yarn_get_mscale(scaling.factor, scaling.mscale) / \
            yarn_get_mscale(scaling.factor, scaling.mscale_all_dim)
        if m != 1.0:
            sin, cos = sin * m, cos * m
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w_gate, w_up, w_down, quant=None) -> jax.Array:
    g = dense(x, w_gate, quant)
    u = dense(x, w_up, quant)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = lshard(h, "batch", "seq", "ffn")
    return dense(h, w_down, quant)


def embed_lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)
