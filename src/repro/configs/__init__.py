"""Config registry: ``--arch <id>`` resolution + input shapes + reduction.

ARCHS maps the 10 assigned architecture ids, and deepseek-v2-lite (the
published model, where deepseek-v2-lite-16b keeps plain rope), to their
configs; SHAPES maps the 4 assigned input shapes; ``reduce_config``
shrinks any config to a CPU-smoke-testable size *preserving its block
structure* (same pattern kinds, fewer repeats / smaller dims).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict

from repro.models.config import ArchConfig

_MODULES = {
    "xlstm-350m": "xlstm_350m",
    "musicgen-medium": "musicgen_medium",
    "qwen2.5-3b": "qwen2_5_3b",
    "qwen3-8b": "qwen3_8b",
    "stablelm-3b": "stablelm_3b",
    "yi-34b": "yi_34b",
    "chameleon-34b": "chameleon_34b",
    "zamba2-7b": "zamba2_7b",
    "granite-moe-1b-a400m": "granite_moe_1b",
    "deepseek-v2-lite-16b": "deepseek_v2_lite",
    "deepseek-v2-lite": "deepseek_v2_lite",
}


def get_config(name: str) -> ArchConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    # a module may hold several entries by name (``CONFIGS``)
    return getattr(mod, "CONFIGS", {}).get(name, mod.CONFIG)


def all_archs():
    return list(_MODULES)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    step: str            # train | prefill | decode


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def shape_applicable(cfg: ArchConfig, shape: str) -> bool:
    """long_500k runs only for sub-quadratic archs (DESIGN.md §4)."""
    if shape == "long_500k":
        return cfg.sub_quadratic
    return True


def reduce_config(cfg: ArchConfig) -> ArchConfig:
    """Reduced same-family config for CPU smoke tests."""
    pattern = []
    for entry in cfg.pattern:
        if entry[0] == "scan":
            pattern.append(("scan", entry[1], min(entry[2], 2)))
        else:
            group = tuple((k, min(c, 2)) for k, c in entry[1])
            pattern.append(("group", group, min(entry[2], 2)))
    heads = min(cfg.n_heads, 4)
    kv = min(cfg.n_kv_heads, heads)
    kw = dict(
        n_layers=sum(e[2] if e[0] == "scan"
                     else sum(c for _, c in e[1]) * e[2] for e in pattern),
        d_model=128, n_heads=heads, n_kv_heads=kv, head_dim=128 // heads,
        d_ff=min(cfg.d_ff, 256) if cfg.d_ff else 0,
        vocab_size=min(cfg.vocab_size, 512),
        pattern=tuple(pattern),
        ssm_chunk=8,
    )
    if cfg.n_experts:
        n = min(cfg.n_experts, 8)
        lo, hi = cfg.held_experts()
        kw.update(n_experts=n, top_k=min(cfg.top_k, 2),
                  d_ff_expert=min(cfg.d_ff_expert, 64),
                  capacity_factor=4.0,
                  # the same share of the fewer experts, at least one
                  experts_held=None if cfg.experts_held is None else
                  (lo * n // cfg.n_experts,
                   max(hi * n // cfg.n_experts, lo * n // cfg.n_experts + 1)))
    if cfg.kv_lora_rank:
        kw.update(kv_lora_rank=64, qk_nope_dim=32, qk_rope_dim=16,
                  v_head_dim=32)
    if cfg.ssm_state:
        kw.update(ssm_state=16, ssm_headdim=16)
    if cfg.family == "ssm":   # xlstm: heads divide d_model
        kw.update(n_heads=4, n_kv_heads=4, head_dim=32)
    kw["decode_margin"] = 32
    return cfg.with_(**kw)
