"""deepseek-v2-lite [moe] — MLA kv_lora=512, 2 shared + 64 routed experts
top-6, YaRN [arXiv:2405.04434;
huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json].

27L, d_model=2048, 16 MLA heads (kv_lora_rank=512, nope 128 / rope 64 /
v 128, no q_lora), first layer dense (d_ff=10944), then 64 routed experts
(d_ff=1408, greedy top-6 over a softmax of all 64, gates not
renormalised: norm_topk_prob false, routed_scaling_factor 1) and 2 shared
experts a layer; vocab 102400, untied head.  Rope theta 1e4 with YaRN:
factor 40 over 4096 original positions, beta_fast 32, beta_slow 1,
mscale = mscale_all_dim = 0.707.  Every expert is held here; a share of
them is ``experts_held``.

``deepseek-v2-lite-16b`` is the same shapes with plain rope and top-k
gates renormalised to sum 1: the repository's first entry for this
model, which the benchmark's MLA fixture (``benchmarks/chip/tests/
fixtures/configs/tiny-mla.json``) reads as a model without YaRN.
"""
from repro.models.config import ArchConfig, RopeScaling

CONFIG = ArchConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=10944,
    vocab_size=102400,
    n_experts=64, top_k=6, n_shared_experts=2, d_ff_expert=1408,
    norm_topk_prob=False,
    kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    rope_theta=10000.0,
    rope_scaling=RopeScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    pattern=(("scan", "mla_mlp", 1), ("scan", "mla_moe", 26)),
)

CONFIGS = {
    "deepseek-v2-lite": CONFIG,
    "deepseek-v2-lite-16b": CONFIG.with_(name="deepseek-v2-lite-16b",
                                         norm_topk_prob=True,
                                         rope_scaling=None),
}
