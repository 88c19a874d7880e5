"""Residual blocks: the units the LM's block program composes.

Every block has the same interface:
  specs(cfg)                                  -> ParamSpec tree
  apply(p, x, cfg, cache, mode, pos, pages,
        offset, layer)                        -> (x', new_cache, aux_loss)
  cache_spec(cfg, batch, capacity)            -> ParamSpec tree or None
  paged_cache_spec(cfg, num_pages, page_size,
                   fmt=pageformat.FP)        -> ParamSpec tree or None

``pages`` is the serving engine's (B, P) page table when the KV cache is
paged (attention families only); recurrent families keep fixed-size
per-slot state and ignore it.  ``offset`` is the (B,) start row of a
RESUMABLE chunk (mode='chunk'): attention families scatter/attend at
absolute rows [offset, offset + len), recurrent families resume their
cached state when offset > 0; None keeps the single-pass chunk path.
``layer`` is the layer scan's index when ``cache`` is a paged pool: the
cache is then the whole stacked (L, num_pages, ...) pool, and the block
writes and reads only layer ``layer``'s pages, in place
(models/model.py); recurrent families get None and ignore it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import lshard
from repro.models import mla, moe, ssm, xlstm
from repro.models.attention import (apply_attention, attn_specs,
                                     kv_cache_spec, paged_kv_cache_spec)
from repro.models.common import (ParamSpec, chunk_lengths, chunk_valid_mask,
                                 dense, layer_norm, rms_norm)


def norm_specs(cfg) -> dict:
    d = cfg.d_model
    s = {"w": ParamSpec((d,), (None,), init="ones", dtype=jnp.float32)}
    if cfg.norm == "layer":
        s["b"] = ParamSpec((d,), (None,), init="zeros", dtype=jnp.float32)
    return s


def apply_norm(p: dict, x, cfg):
    if cfg.norm == "layer":
        return layer_norm(x, p["w"], p["b"])
    return rms_norm(x, p["w"])


def mlp_specs(cfg) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp_act == "gelu":
        return {"w_up": ParamSpec((d, f), ("embed", "ffn"), quantize=True),
                "w_down": ParamSpec((f, d), ("ffn", "embed"), quantize=True)}
    return {"w_gate": ParamSpec((d, f), ("embed", "ffn"), quantize=True),
            "w_up": ParamSpec((d, f), ("embed", "ffn"), quantize=True),
            "w_down": ParamSpec((f, d), ("ffn", "embed"), quantize=True)}


def apply_mlp(p: dict, x, cfg):
    if cfg.mlp_act == "gelu":
        h = dense(x, p["w_up"], cfg.quant)
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
        h = lshard(h, "batch", "seq", "ffn")
        return dense(h, p["w_down"], cfg.quant)
    g = dense(x, p["w_gate"], cfg.quant)
    u = dense(x, p["w_up"], cfg.quant)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = lshard(h, "batch", "seq", "ffn")
    return dense(h, p["w_down"], cfg.quant)


# --- transformer blocks -----------------------------------------------------

def _attn_block_specs(cfg, ffn: str) -> dict:
    s = {"ln1": norm_specs(cfg), "attn": attn_specs(cfg),
         "ln2": norm_specs(cfg)}
    s["ffn"] = moe.moe_specs(cfg) if ffn == "moe" else mlp_specs(cfg)
    return s


def _chunk_token_mask(x, mode, pos):
    """(B, S) valid-token mask in chunked-prefill mode, else None."""
    if mode != "chunk":
        return None
    b, s = x.shape[:2]
    return chunk_valid_mask(chunk_lengths(pos, b), s)


def _apply_attn_block(p, x, cfg, cache, mode, pos, pages, offset, layer,
                      ffn: str):
    x = lshard(x, "batch", "seq", None)
    a, new_cache = apply_attention(
        p["attn"], apply_norm(p["ln1"], x, cfg), cfg,
        cache=cache, mode=mode, pos=pos, pages=pages, offset=offset,
        layer=layer)
    x = x + a
    h = apply_norm(p["ln2"], x, cfg)
    if ffn == "moe":
        y, aux = moe.moe_ffn(p["ffn"], h, cfg,
                             token_mask=_chunk_token_mask(x, mode, pos))
    else:
        y, aux = apply_mlp(p["ffn"], h, cfg), jnp.float32(0)
    x = lshard(x + y, "batch", "seq", None)
    return x, new_cache, aux


def _mla_block_specs(cfg, ffn: str) -> dict:
    s = {"ln1": norm_specs(cfg), "attn": mla.mla_specs(cfg),
         "ln2": norm_specs(cfg)}
    s["ffn"] = moe.moe_specs(cfg) if ffn == "moe" else mlp_specs(cfg)
    return s


def _apply_mla_block(p, x, cfg, cache, mode, pos, pages, offset, layer,
                     ffn: str):
    x = lshard(x, "batch", "seq", None)
    a, new_cache = mla.apply_mla(
        p["attn"], apply_norm(p["ln1"], x, cfg), cfg,
        cache=cache, mode=mode, pos=pos, pages=pages, offset=offset,
        layer=layer)
    x = x + a
    h = apply_norm(p["ln2"], x, cfg)
    if ffn == "moe":
        y, aux = moe.moe_ffn(p["ffn"], h, cfg,
                             token_mask=_chunk_token_mask(x, mode, pos))
    else:
        y, aux = apply_mlp(p["ffn"], h, cfg), jnp.float32(0)
    x = lshard(x + y, "batch", "seq", None)
    return x, new_cache, aux


def _mamba_block_specs(cfg) -> dict:
    return {"ln": norm_specs(cfg), "mamba": ssm.mamba_specs(cfg)}


def _apply_mamba_block(p, x, cfg, cache, mode, pos, pages, offset,
                       layer):
    del pages, layer    # per-slot fixed-size recurrent state: not paged
    y, new_cache = ssm.apply_mamba(
        p["mamba"], apply_norm(p["ln"], x, cfg), cfg,
        cache=cache, mode=mode, pos=pos, offset=offset)
    return x + y, new_cache, jnp.float32(0)


def _apply_mlstm_block(p, x, cfg, cache, mode, pos, pages, offset,
                       layer):
    del pages, layer    # per-slot fixed-size recurrent state: not paged
    y, new_cache = xlstm.apply_mlstm(p, x, cfg, cache=cache, mode=mode,
                                     pos=pos, offset=offset)
    return y, new_cache, jnp.float32(0)


def _apply_slstm_block(p, x, cfg, cache, mode, pos, pages, offset,
                       layer):
    del pages, layer    # per-slot fixed-size recurrent state: not paged
    y, new_cache = xlstm.apply_slstm(p, x, cfg, cache=cache, mode=mode,
                                     pos=pos, offset=offset)
    return y, new_cache, jnp.float32(0)


class BlockDef:
    def __init__(self, specs, apply, cache_spec=None, paged_cache_spec=None):
        self.specs = specs
        self.apply = apply
        self.cache_spec = cache_spec or (lambda cfg, b, cap: None)
        # None = family has no pageable cache (recurrent / cache-free):
        # the paged layout falls back to its regular cache_spec.
        self.paged_cache_spec = paged_cache_spec


BLOCKS = {
    "attn_mlp": BlockDef(
        lambda cfg: _attn_block_specs(cfg, "mlp"),
        functools.partial(_apply_attn_block, ffn="mlp"),
        lambda cfg, b, cap: kv_cache_spec(cfg, b, cap),
        paged_kv_cache_spec),
    "attn_moe": BlockDef(
        lambda cfg: _attn_block_specs(cfg, "moe"),
        functools.partial(_apply_attn_block, ffn="moe"),
        lambda cfg, b, cap: kv_cache_spec(cfg, b, cap),
        paged_kv_cache_spec),
    "mla_mlp": BlockDef(
        lambda cfg: _mla_block_specs(cfg, "mlp"),
        functools.partial(_apply_mla_block, ffn="mlp"),
        lambda cfg, b, cap: mla.mla_cache_spec(cfg, b, cap),
        mla.paged_mla_cache_spec),
    "mla_moe": BlockDef(
        lambda cfg: _mla_block_specs(cfg, "moe"),
        functools.partial(_apply_mla_block, ffn="moe"),
        lambda cfg, b, cap: mla.mla_cache_spec(cfg, b, cap),
        mla.paged_mla_cache_spec),
    "mamba": BlockDef(
        _mamba_block_specs, _apply_mamba_block,
        lambda cfg, b, cap: ssm.mamba_cache_spec(cfg, b)),
    "mlstm": BlockDef(
        xlstm.mlstm_specs, _apply_mlstm_block,
        lambda cfg, b, cap: xlstm.mlstm_cache_spec(cfg, b)),
    "slstm": BlockDef(
        xlstm.slstm_specs, _apply_slstm_block,
        lambda cfg, b, cap: xlstm.slstm_cache_spec(cfg, b)),
}
# shared-parameter attention block (zamba2): same def, params held once.
BLOCKS["shared_attn"] = BLOCKS["attn_mlp"]
