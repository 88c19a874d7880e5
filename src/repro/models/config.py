"""ArchConfig: one dataclass describing every assigned architecture.

``pattern`` is the block program interpreted by models/model.py:
  ("scan", kind, count)                      — `count` identical blocks,
      parameters stacked on a leading dim and executed with lax.scan
      (compile time ~ one block, the production scan-over-layers setup);
  ("group", ((kind, count), ...), repeats)   — a repeating heterogeneous
      period (e.g. zamba2's [5 x mamba2, 1 x shared attention]); the period
      body is unrolled once and scanned over `repeats`.

Blocks of kind 'shared_attn' share ONE parameter set across all
occurrences (zamba2's shared transformer block).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

from repro.core.quant import QuantConfig


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN rope scaling in DeepSeek-V2's form (arXiv:2309.00071; the
    published ``DeepseekV2YarnRotaryEmbedding``): rotary frequencies blend
    from ``theta`` to ``theta * factor`` over a linear ramp between the
    correction dims that ``beta_fast`` and ``beta_slow`` rotations over
    ``original_max_position_embeddings`` positions give; cos/sin take the
    factor mscale(factor, mscale) / mscale(factor, mscale_all_dim), and
    latent attention's softmax scale mscale(factor, mscale_all_dim)**2
    (``repro.models.common.yarn_*``)."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    type: str = "yarn"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: int = 0                # 0 -> d_model // n_heads
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    rope_scaling: Optional[RopeScaling] = None   # None: plain rope
    norm: str = "rms"                # rms | layer
    mlp_act: str = "silu_glu"        # silu_glu | gelu

    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25        # training only: serving is dropless
    aux_loss_weight: float = 0.01
    # the contiguous range [lo, hi) of routed experts whose weights this
    # device holds (expert parallelism's share); None = all of them.  The
    # router always scores all n_experts.
    experts_held: Optional[Tuple[int, int]] = None
    norm_topk_prob: bool = True      # renormalise the top-k gates to sum 1

    # MLA (deepseek)
    kv_lora_rank: int = 0
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128

    # SSM / recurrent
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_chunk: int = 256
    conv_dim: int = 4

    # block program; () -> derived from family.
    pattern: Tuple = ()

    input_mode: str = "tokens"       # tokens | embeds (audio/vlm stubs)
    sub_quadratic: bool = False      # eligible for long_500k

    quant: Optional[QuantConfig] = None
    dtype: object = jnp.bfloat16
    remat: str = "full"              # none | full | dots
    decode_margin: int = 4096        # extra KV capacity beyond prompt

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if not self.pattern:
            kind = {"moe": "attn_moe"}.get(self.family, "attn_mlp")
            object.__setattr__(self, "pattern",
                               (("scan", kind, self.n_layers),))
        lo, hi = self.held_experts()
        if self.n_experts and not 0 <= lo < hi <= self.n_experts:
            raise ValueError(f"{self.name}: experts_held {self.experts_held}"
                             f" is no range of the {self.n_experts} experts")

    def held_experts(self) -> Tuple[int, int]:
        """[lo, hi) of the routed experts held here."""
        return self.experts_held or (0, self.n_experts)

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, 256)

    def n_blocks(self) -> int:
        total = 0
        for entry in self.pattern:
            if entry[0] == "scan":
                total += entry[2]
            else:
                total += sum(c for _, c in entry[1]) * entry[2]
        return total

    def with_(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)
