"""LM assembly: embedding -> block program (scan stages) -> head.

The block program (ArchConfig.pattern) is interpreted into lax.scan stages
with stacked parameters, so compile time scales with the number of *distinct*
block kinds, not the number of layers — mandatory for dry-running 34B/60L
models on a 512-device host platform.  Paged pools ride through the scans
as carry and are written in place; every other cache threads through them
as xs/ys (:func:`_apply_stage`).  One forward covers the four lowered
entry points:

  mode='train'    — no cache, remat per scan body
  mode='prefill'  — emits a cache sized ``capacity``
  mode='decode'   — consumes/updates the cache at position ``pos``
  mode='chunk'    — single-pass chunked prefill into an *existing* slot'd
                    cache: ``pos`` is a (B,) vector of valid prompt lengths
                    for a right-padded chunk; slots with length 0 keep
                    their cache/recurrent state bit-for-bit (batched
                    admission never perturbs in-flight requests).  With
                    ``offset`` (a (B,) vector of start rows) the chunk is
                    RESUMABLE: slot tokens sit at rows [offset, offset +
                    len), attention families attend over the cached
                    history [0, offset) too, and recurrent families resume
                    their cached state — prompts longer than one chunk
                    fill across several dispatches (continuous batching)

Cache layouts (serving): the contiguous layout gives every slot a private
(B, capacity, ...) region; the PAGED layout (``init_paged_cache``) replaces
it with a global (num_pages, page_size, ...) pool per attention/MLA layer
plus a per-slot page table ``pages`` (B, P) passed to ``forward`` — logical
cache row ``t`` of slot ``b`` lives at physical row ``pages[b, t //
page_size] * page_size + t % page_size``.  The table is shared by every
layer (each layer owns its own slice of the stacked (L, num_pages, ...)
pool), chunk/decode writes scatter through it, and decode gathers the
slot's logical window back before attention, so paging changes storage
addressing only — the math (and its outputs) is bit-identical to the
contiguous layout.  Under a seq-sharding rule table the pool is
additionally STRIPED page-aligned over the seq mesh axes (logical axis
'pages'): each shard scatters and gathers only the pages it physically
holds and paged decode/resume combine per-logical-page flash partials
across shards with pmax/psum — bit-identical at any shard count
(models/attention.py docstring).
Recurrent families (SSM/xLSTM) keep fixed-size per-slot state and
bypass paging.
"""
from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import lshard
from repro.models import common, moe
from repro.models.blocks import BLOCKS, apply_norm, norm_specs
from repro.models.common import ParamSpec, dense, embed_lookup, stack_specs
from repro.models.config import ArchConfig


def _linear_inner(group) -> List[str]:
    kinds = []
    for kind, count in group:
        kinds.extend([kind] * count)
    return kinds


def _has_shared(cfg) -> bool:
    return any(entry[0] == "group" and any(k == "shared_attn" for k, _ in entry[1])
               for entry in cfg.pattern) or any(
        entry[0] == "scan" and entry[1] == "shared_attn"
        for entry in cfg.pattern)


def param_specs(cfg: ArchConfig) -> dict:
    d, vp = cfg.d_model, cfg.padded_vocab
    specs: dict = {}
    if cfg.input_mode == "tokens":
        specs["embed"] = ParamSpec((vp, d), ("vocab", "embed"), init="embed",
                                   scale=0.02)
    stages = []
    for entry in cfg.pattern:
        if entry[0] == "scan":
            _, kind, count = entry
            if kind == "shared_attn":
                stages.append({})        # params live in specs['shared']
            else:
                stages.append(stack_specs(BLOCKS[kind].specs(cfg), count))
        else:
            _, group, repeats = entry
            st = {}
            for j, kind in enumerate(_linear_inner(group)):
                if kind == "shared_attn":
                    continue
                st[f"b{j}"] = stack_specs(BLOCKS[kind].specs(cfg), repeats)
            stages.append(st)
    specs["stages"] = stages
    if _has_shared(cfg):
        specs["shared"] = BLOCKS["attn_mlp"].specs(cfg)
    specs["final_norm"] = norm_specs(cfg)
    specs["lm_head"] = ParamSpec((d, vp), ("embed", "vocab"), scale=0.02,
                                 quantize=True)
    return specs


def cache_specs(cfg: ArchConfig, batch: int, capacity: int, *,
                num_pages: Optional[int] = None,
                page_size: Optional[int] = None,
                kv_format: str = "fp") -> list:
    """Cache ParamSpec tree; pass ``num_pages``/``page_size`` for the paged
    layout (pageable families get a pool, the rest keep per-slot state).
    ``kv_format`` picks the page STORAGE format (core/pageformat): "fp"
    stores model dtype, "int8"/"int4" store packed rows plus a pool-shaped
    per-row scale leaf.  Paged layout only."""
    from repro.core.pageformat import get_format
    fmt = get_format(kv_format)

    def spec_for(kind):
        block = BLOCKS[kind]
        if num_pages is not None and block.paged_cache_spec is not None:
            return block.paged_cache_spec(cfg, num_pages, page_size,
                                          fmt=fmt)
        return block.cache_spec(cfg, batch, capacity)

    stages = []
    for entry in cfg.pattern:
        if entry[0] == "scan":
            _, kind, count = entry
            cs = spec_for(kind)
            stages.append(None if cs is None else stack_specs(cs, count))
        else:
            _, group, repeats = entry
            st = {}
            for j, kind in enumerate(_linear_inner(group)):
                cs = spec_for(kind)
                if cs is not None:
                    st[f"b{j}"] = stack_specs(cs, repeats)
            stages.append(st)
    return stages


def cache_capacity(cfg: ArchConfig, prompt_len: int) -> int:
    cap = prompt_len + cfg.decode_margin
    return ((cap + 255) // 256) * 256


def _remat(fn, cfg, mode):
    if mode != "train" or cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _apply_stage(kinds, count, stage_p, x, cfg, stage_c, mode, pos, pages,
                 offset, shared):
    """One lax.scan over ``count`` repeats of the blocks ``kinds``, whose
    stacked params and caches are keyed ``b{j}``.

    Paged pools (blocks declaring ``paged_cache_spec``, with a page
    table) ride in the CARRY as whole stacked (count, num_pages, ...)
    leaves, and block ``j`` of repeat ``i`` writes and reads its rows
    through layer index ``i``: the scatter updates the carried buffer in
    place, so a donated pool comes back aliased with no pool-sized copy.
    Through ``xs``/``ys`` the scan would slice out each layer's pool,
    rewrite the whole slice to add a few rows, stack the slices into a
    fresh buffer, and copy that back.  Everything else (recurrent state,
    contiguous caches) is per-slot and small, and stays on ``xs``/``ys``.
    """
    pooled = {f"b{j}" for j, kind in enumerate(kinds)
              if pages is not None and stage_c is not None
              and BLOCKS[kind].paged_cache_spec is not None
              and f"b{j}" in stage_c}
    pools = {k: stage_c[k] for k in pooled}
    rest = None if stage_c is None else \
        {k: c for k, c in stage_c.items() if k not in pooled}
    # serving's expert layers read their banks in place out of the whole
    # stack, at the layer index (``moe.moe_ffn``): a per-layer slice of
    # ``xs`` would be copied out for the grouped-matmul kernel each layer
    stacked = {f"b{j}" for j, kind in enumerate(kinds)
               if mode != "train" and kind.endswith("_moe")}
    banks = {k: {n: stage_p[k]["ffn"][n] for n in moe.BANKS}
             for k in stacked}
    stage_p = {k: {**v, "ffn": {n: w for n, w in v["ffn"].items()
                                if n not in moe.BANKS}}
               if k in stacked else v for k, v in stage_p.items()}

    def body(carry, xs):
        h, aux, pools = carry
        p_map, c_map, i = xs
        pools, new_c = dict(pools), {}
        for j, kind in enumerate(kinds):
            key = f"b{j}"
            p_j = shared if kind == "shared_attn" else p_map[key]
            if key in banks:
                p_j = {**p_j, "ffn": {**p_j["ffn"], **banks[key],
                                      "layer": i}}
            if key in pools:
                h, pools[key], a = BLOCKS[kind].apply(
                    p_j, h, cfg, pools[key], mode, pos, pages, offset, i)
            else:
                c_j = None if c_map is None else c_map.get(key)
                h, c_new, a = BLOCKS[kind].apply(
                    p_j, h, cfg, c_j, mode, pos, pages, offset, None)
                if c_new is not None:
                    new_c[key] = c_new
            aux = aux + a
        return (h, aux, pools), new_c

    # serving calls of an expert layer add up its (3,) pick counts
    moe_counts = mode != "train" and any(k.endswith("_moe") for k in kinds)
    aux0 = jnp.zeros((3,), jnp.float32) if moe_counts else jnp.float32(0)
    (x, aux, pools), c_out = jax.lax.scan(
        _remat(body, cfg, mode), (x, aux0, pools),
        (stage_p, rest, jnp.arange(count, dtype=jnp.int32)))
    return x, {**c_out, **pools}, aux


def forward(params: dict, inputs: jax.Array, cfg: ArchConfig, *,
            cache: Optional[list] = None, mode: str = "train",
            pos: Any = 0, pages: Optional[jax.Array] = None,
            offset: Optional[Any] = None,
            ) -> Tuple[jax.Array, Optional[list], jax.Array]:
    """Returns (logits (B, S, padded_vocab), new_cache, aux): ``aux`` is
    the MoE load-balance loss in training, and in a serving mode of a
    model with experts the f32 (3,) [picks routed to experts held here,
    all picks, held experts with any pick] summed over the layers
    (``moe.moe_ffn``).

    ``pages``: optional (B, P) int32 per-slot page table when ``cache``
    uses the paged layout (see module docstring); None = contiguous.
    ``offset``: optional (B,) int32 start rows for a RESUMABLE chunk
    (mode='chunk' only, see module docstring); None = single-pass."""
    pos = jnp.asarray(pos, jnp.int32)
    if pages is not None:
        pages = jnp.asarray(pages, jnp.int32)
    if offset is not None:
        offset = jnp.asarray(offset, jnp.int32)
    if cfg.input_mode == "tokens":
        x = embed_lookup(params["embed"], inputs)
    else:
        x = inputs.astype(cfg.dtype)
    x = lshard(x, "batch", "seq", None)

    shared = params.get("shared")
    aux_total = jnp.float32(0)
    new_cache: list = []
    for i, entry in enumerate(cfg.pattern):
        stage_p = params["stages"][i]
        stage_c = None if cache is None else cache[i]
        if entry[0] == "scan":
            # one block kind: the same scan, its trees keyed b0.
            _, kind, count = entry
            x, c2, aux = _apply_stage(
                [kind], count, {"b0": stage_p}, x, cfg,
                None if stage_c is None else {"b0": stage_c}, mode, pos,
                pages, offset, shared)
            c2 = c2.get("b0")
        else:
            _, group, count = entry
            x, c2, aux = _apply_stage(
                _linear_inner(group), count, stage_p, x, cfg, stage_c, mode,
                pos, pages, offset, shared)
        new_cache.append(c2)
        aux_total = aux_total + aux

    x = apply_norm(params["final_norm"], x, cfg)
    logits = dense(x, params["lm_head"], cfg.quant)
    if cfg.padded_vocab != cfg.vocab_size:
        # the padding columns only make the vocab axis shard evenly; they
        # name no token, so no sampler or loss may pick them.
        real = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(real, logits, jnp.asarray(-1e30, logits.dtype))
    logits = lshard(logits, "batch", "seq", "vocab")
    return logits, (new_cache if cache is not None else None), aux_total


# ---------------------------------------------------------------------------
# Convenience init/abstract entry points.
# ---------------------------------------------------------------------------

def init_params(cfg: ArchConfig, key: jax.Array):
    return common.materialize(param_specs(cfg), key, cfg.dtype)


def abstract_params(cfg: ArchConfig):
    return common.abstract(param_specs(cfg), cfg.dtype)


def init_cache(cfg: ArchConfig, batch: int, prompt_len: int):
    cap = cache_capacity(cfg, prompt_len)
    specs = cache_specs(cfg, batch, cap)
    return common.materialize(specs, jax.random.PRNGKey(0), cfg.dtype)


def abstract_cache(cfg: ArchConfig, batch: int, prompt_len: int):
    cap = cache_capacity(cfg, prompt_len)
    return common.abstract(cache_specs(cfg, batch, cap), cfg.dtype)


def init_paged_cache(cfg: ArchConfig, batch: int, num_pages: int,
                     page_size: int, kv_format: str = "fp"):
    """Paged serving cache: per-layer (num_pages, page_size, ...) pools for
    attention/MLA, per-slot fixed-size state for recurrent families."""
    specs = cache_specs(cfg, batch, 0, num_pages=num_pages,
                        page_size=page_size, kv_format=kv_format)
    return common.materialize(specs, jax.random.PRNGKey(0), cfg.dtype)


def abstract_paged_cache(cfg: ArchConfig, batch: int, num_pages: int,
                         page_size: int, kv_format: str = "fp"):
    return common.abstract(
        cache_specs(cfg, batch, 0, num_pages=num_pages,
                    page_size=page_size, kv_format=kv_format), cfg.dtype)


def param_count(cfg: ArchConfig) -> int:
    return common.param_count(param_specs(cfg))


def quantize_for_serving(cfg: ArchConfig, params):
    """Convert every quantize-eligible 2D weight into a PackedWeight.

    This is the deployment transform of the paper's technique: sub-byte
    weights leave host memory already packed (repro.core.packing) and are
    expanded only inside the Pallas kernel's VMEM tile.  Stacked (scanned)
    and >2D leaves keep raw weights and run the fake-quant emulation path.
    """
    from repro.kernels.ops import prepare_weight
    from repro.models.common import ParamSpec, is_spec_tree_leaf

    assert cfg.quant is not None and cfg.quant.mode in ("int", "wo"), \
        "quantize_for_serving needs an int/wo QuantConfig"
    specs = param_specs(cfg)
    flat_s, treedef = jax.tree.flatten(specs, is_leaf=is_spec_tree_leaf)
    flat_p = treedef.flatten_up_to(params)
    out = []
    n_packed = 0
    for spec, leaf in zip(flat_s, flat_p):
        if not (isinstance(spec, ParamSpec) and spec.quantize):
            out.append(leaf)
            continue
        if leaf.ndim == 2 and spec.stacked == 0:
            out.append(prepare_weight(leaf, cfg.quant))
            n_packed += 1
        elif leaf.ndim == 3 and spec.stacked == 1:
            # scan-stacked weights: pack per layer; lax.scan slices the
            # PackedWeight pytree leaves so block bodies see 2D weights.
            out.append(jax.vmap(
                lambda w: prepare_weight(w, cfg.quant))(leaf))
            n_packed += 1
        else:
            out.append(leaf)   # >2D expert banks: fake-quant emulation
    return jax.tree.unflatten(treedef, out), n_packed
