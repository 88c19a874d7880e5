"""Model code of DeepSeek-V2(-Lite): multi-head latent attention with YaRN,
one leading dense layer, then expert layers of routed and shared experts,
of which this chip holds a contiguous share of the routed ones.

``m`` is the ``model`` block of a configuration file: the Hugging Face
keys of deepseek-ai/DeepSeek-V2-Lite, where ``n_routed_experts`` counts
the experts held here, plus ``experts_held`` ([lo, hi) of the published
experts) and ``n_routed_experts_published`` (the router's width).

Reference: arXiv:2405.04434, written from the published equations and
``modeling_deepseek.py``, not from the program.  Each layer:

    h = RMSNorm(x);  x += MLA(h);  h = RMSNorm(x);  x += FFN(h)

MLA in the expanded form without query compression (``q_lora_rank``
null): [q^C; q^R] = W^Q h per head, [c^KV; k^R] = W^DKV h (one matrix,
latent first), c^KV RMS-normed, k^C = W^UK c^KV and v = W^UV c^KV per
head, one rope key k^R shared by the heads, causal softmax of
[q^C; q^R] . [k^C; k^R] times the YaRN softmax scale, W^O.  YaRN
(arXiv:2309.00071, the published ``DeepseekV2YarnRotaryEmbedding``):
inverse frequencies blend from theta's to theta*factor's over a linear
ramp between the correction dims of ``beta_fast`` and ``beta_slow``,
cos/sin take mscale(factor, mscale) / mscale(factor, mscale_all_dim),
and the softmax scale 1/sqrt(nope + rope) takes mscale(factor,
mscale_all_dim)**2.  FFN of the first ``first_k_dense_replace`` layers: a
SwiGLU of ``intermediate_size``.  Of the others: the router's softmax
over all published experts, a greedy top-``num_experts_per_tok``, gates
renormalised only under ``norm_topk_prob`` and times
``routed_scaling_factor``; each held expert adds its gate times its
SwiGLU of ``moe_intermediate_size`` for the tokens that picked it (the
experts held elsewhere add nothing on this chip, as in the program);
the shared experts, one SwiGLU of ``n_shared_experts`` x
``moe_intermediate_size``, add for every token.  Final RMSNorm and the
untied head (``reference``).  Departures, as the program has them: RoPE
rotates halves of the rope dims where the published code first
de-interleaves them, a fixed permutation of W^Q's and W^DKV's rope
columns that leaves random weights random.

Weights: scales follow the program's initialiser (matmul 1/sqrt(fan_in),
router 0.02, embedding 1, head 0.02); norm gains 1 +- 0.1.

Counts: bf16 weights and latent cache (``BYTES``), the work the algorithm
needs: every weight outside the routed experts once a step, the held
experts each step touches, each attended latent row once.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import reference
from counts import BYTES
from reference import mm, rms

# model-block keys -> ArchConfig fields; the program's config must agree.
ARCH_KEYS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads",
             "intermediate_size": "d_ff", "vocab_size": "vocab_size",
             "rope_theta": "rope_theta", "kv_lora_rank": "kv_lora_rank",
             "qk_nope_head_dim": "qk_nope_dim",
             "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_head_dim",
             "moe_intermediate_size": "d_ff_expert",
             "num_experts_per_tok": "top_k",
             "n_shared_experts": "n_shared_experts",
             "norm_topk_prob": "norm_topk_prob"}
ROPE_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale", "mscale_all_dim", "type")


def _stages(m: dict) -> list:
    """Layers of each stage: the leading dense ones, then the expert ones."""
    k = m["first_k_dense_replace"]
    return [k, m["num_hidden_layers"] - k]


def arch(conf: dict):
    """The program's ArchConfig, checked against the ``model`` block (and
    the block against the catalog keys at the file's top level)."""
    from repro.configs import get_config
    m = conf["model"]
    for key, v in conf.items():
        if key in m and m[key] != v:
            raise ValueError(f"{conf['arch']}: model block has {key}="
                             f"{m[key]}, the file {v}")
    over = {k: tuple(tuple(e) for e in v) if k == "pattern" else
            tuple(v) if isinstance(v, list) else v
            for k, v in conf["overrides"].items()}
    a = get_config(conf["arch"]).with_(**over)
    for key, field in ARCH_KEYS.items():
        if getattr(a, field) != m[key]:
            raise ValueError(f"{conf['arch']}: program has {field}="
                             f"{getattr(a, field)}, config {key}={m[key]}")
    lo, hi = m["experts_held"]
    if (a.n_experts, a.held_experts()) != \
            (m["n_routed_experts_published"], (lo, hi)) or \
            hi - lo != m["n_routed_experts"]:
        raise ValueError(f"{conf['arch']}: program routes over "
                         f"{a.n_experts} experts and holds "
                         f"{a.held_experts()}, the config "
                         f"{m['n_routed_experts_published']} and {lo}-{hi}")
    rs = m["rope_scaling"]
    if a.rope_scaling is None or any(
            getattr(a.rope_scaling, k) != rs[k] for k in ROPE_KEYS):
        raise ValueError(f"{conf['arch']}: program's rope scaling "
                         f"{a.rope_scaling}, the config's {rs}")
    want = (("scan", "mla_mlp", _stages(m)[0]),
            ("scan", "mla_moe", _stages(m)[1]))
    if a.pattern != want or a.n_blocks() != m["num_hidden_layers"]:
        raise ValueError(f"{conf['arch']}: program runs {a.pattern}, the "
                         f"reference {want}")
    if m["q_lora_rank"] is not None or m["tie_word_embeddings"] or \
            m["attention_bias"] or m["hidden_act"] != "silu" or \
            m["scoring_func"] != "softmax" or m["topk_method"] != "greedy" \
            or m["n_group"] != 1 or m["moe_layer_freq"] != 1 or \
            m["routed_scaling_factor"] != 1 or a.mlp_act != "silu_glu" \
            or a.norm != "rms" or a.quant:
        raise ValueError(f"{conf['arch']}: not the DeepSeek-V2 form the "
                         "reference implements")
    return a


def leaf_table(m: dict) -> dict:
    """name -> (shape, dtype, kind, scale); ``L/`` leaves are the dense
    stage's layers, ``L1/`` the expert stage's, each stacked; expert
    weights are the held experts only."""
    d, f, h = m["hidden_size"], m["intermediate_size"], m["num_attention_heads"]
    r, dn = m["kv_lora_rank"], m["qk_nope_head_dim"]
    dr, dv = m["qk_rope_head_dim"], m["v_head_dim"]
    fe, eh = m["moe_intermediate_size"], m["n_routed_experts"]
    fs = fe * m["n_shared_experts"]
    vp = -(-m["vocab_size"] // 256) * 256
    t = {
        "embed": ((vp, d), "bf16", "std", 1.0),
        "final_norm/w": ((d,), "f32", "gain", 0.1),
        "lm_head": ((d, vp), "bf16", "std", 0.02),
    }
    for i, n in enumerate(_stages(m)):
        s = "L" + (str(i) if i else "")
        t.update({
            f"{s}/ln1/w": ((n, d), "f32", "gain", 0.1),
            f"{s}/ln2/w": ((n, d), "f32", "gain", 0.1),
            f"{s}/attn/w_q": ((n, d, h * (dn + dr)), "bf16", "std", d ** -0.5),
            f"{s}/attn/w_dkv": ((n, d, r + dr), "bf16", "std", d ** -0.5),
            f"{s}/attn/kv_norm": ((n, r), "f32", "gain", 0.1),
            f"{s}/attn/w_uk": ((n, r, h * dn), "bf16", "std", r ** -0.5),
            f"{s}/attn/w_uv": ((n, r, h * dv), "bf16", "std", r ** -0.5),
            f"{s}/attn/w_o": ((n, h * dv, d), "bf16", "std", (h * dv) ** -0.5),
        })
        if i == 0:
            t.update({
                f"{s}/ffn/w_gate": ((n, d, f), "bf16", "std", d ** -0.5),
                f"{s}/ffn/w_up": ((n, d, f), "bf16", "std", d ** -0.5),
                f"{s}/ffn/w_down": ((n, f, d), "bf16", "std", f ** -0.5),
            })
        else:
            e = m["n_routed_experts_published"]
            t.update({
                f"{s}/ffn/router": ((n, d, e), "bf16", "std", 0.02),
                f"{s}/ffn/w_gate": ((n, eh, d, fe), "bf16", "std", d ** -0.5),
                f"{s}/ffn/w_up": ((n, eh, d, fe), "bf16", "std", d ** -0.5),
                f"{s}/ffn/w_down": ((n, eh, fe, d), "bf16", "std",
                                    fe ** -0.5),
                f"{s}/ffn/shared/w_gate": ((n, d, fs), "bf16", "std",
                                           d ** -0.5),
                f"{s}/ffn/shared/w_up": ((n, d, fs), "bf16", "std",
                                         d ** -0.5),
                f"{s}/ffn/shared/w_down": ((n, fs, d), "bf16", "std",
                                           fs ** -0.5),
            })
    return t


# -- float32 reference ------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """``yarn_get_mscale`` of the published code."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(m: dict) -> np.ndarray:
    """The published ``DeepseekV2YarnRotaryEmbedding.inv_freq`` (float64
    here): ``yarn_find_correction_range`` and ``yarn_linear_ramp_mask``
    over the rope dims."""
    rs, dim, base = m["rope_scaling"], m["qk_rope_head_dim"], m["rope_theta"]

    def corr_dim(rot):
        return dim * math.log(rs["original_max_position_embeddings"] /
                              (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / rs["factor"]
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def softmax_scale(m: dict) -> float:
    rs = m["rope_scaling"]
    s = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    return s * yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def _rope(x, m):
    """x: (S, heads, dr); YaRN frequencies and cos/sin factor, rotating
    halves [0, dr/2) and [dr/2, dr)."""
    rs = m["rope_scaling"]
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * \
        jnp.asarray(yarn_inv_freq(m), jnp.float32)[None, :]
    f = yarn_mscale(rs["factor"], rs["mscale"]) / \
        yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    cos, sin = (jnp.cos(ang) * f)[:, None, :], (jnp.sin(ang) * f)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _swiglu(x, w, quant):
    return mm(jax.nn.silu(mm(x, w["w_gate"], quant)) * mm(x, w["w_up"], quant),
              w["w_down"], quant)


def _mla(m, quant, a, at):
    s = a.shape[0]
    h, r = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    q = mm(a, at["w_q"], quant).reshape(s, h, dn + dr)
    ckr = mm(a, at["w_dkv"], quant)
    c = rms(ckr[:, :r], at["kv_norm"], m["rms_norm_eps"])   # c^KV
    k_r = _rope(ckr[:, None, r:], m)                         # (s, 1, dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], m)], -1)
    k_c = mm(c, at["w_uk"], quant).reshape(s, h, dn)
    v = mm(c, at["w_uv"], quant).reshape(s, h, dv)
    k = jnp.concatenate([k_c, jnp.broadcast_to(k_r, (s, h, dr))], -1)
    sc = jnp.einsum("qhd,shd->hqs", q, k,
                    precision=jax.lax.Precision.HIGHEST) * softmax_scale(m)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqs,shd->qhd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(s, h * dv)
    return mm(o, at["w_o"], quant)


def _gates(m, quant, b, router):
    """(S, published experts) gate of each expert for each token: its
    top-k probability (renormalised under ``norm_topk_prob``, times
    ``routed_scaling_factor``), 0 where the token did not pick it."""
    k = m["num_experts_per_tok"]
    probs = jax.nn.softmax(mm(b, router, quant), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if m["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * m["routed_scaling_factor"]
    e = probs.shape[-1]
    return jnp.sum(jax.nn.one_hot(idx, e) * top[..., None], axis=1)


def _moe(m, quant, b, ff):
    lo, hi = m["experts_held"]
    g = _gates(m, quant, b, ff["router"])[:, lo:hi]           # (S, held)

    def expert(y, w):
        wi, gi = w
        return y + gi[:, None] * _swiglu(b, wi, quant), None

    held = {n: ff[n] for n in ("w_gate", "w_up", "w_down")}
    y, _ = jax.lax.scan(expert, jnp.zeros_like(b), (held, g.T))
    return y + _swiglu(b, ff["shared"], quant)


def _layer(m, quant, moe, x, lw):
    eps = m["rms_norm_eps"]
    x = x + _mla(m, quant, rms(x, lw["ln1"]["w"], eps), lw["attn"])
    b = rms(x, lw["ln2"]["w"], eps)
    return x + (_moe(m, quant, b, lw["ffn"]) if moe
                else _swiglu(b, lw["ffn"], quant))


def hidden(params, tokens, m, quant=None):
    """Final-norm hidden states (S, d) of one sequence: the dense stage,
    then the expert stage, each layer widened in its turn by a scan."""
    x = params["embed"][tokens].astype(jnp.float32)
    for i, stage in enumerate(params["stages"]):
        x, _ = jax.lax.scan(
            lambda c, lw: (_layer(m, quant, i > 0, c, lw), None), x, stage)
    return rms(x, params["final_norm"]["w"], m["rms_norm_eps"])


def served_gaps(params, m: dict, prompt, served, length: int,
                control: bool = False):
    """``reference.served_gaps`` of this decoder."""
    return reference.served_gaps(hidden, params, m, prompt, served, length,
                                 control)


# -- operations and least bytes ---------------------------------------------

def attn_params(m: dict) -> int:
    """MLA matmul weights of one layer."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    r, dn = m["kv_lora_rank"], m["qk_nope_head_dim"]
    dr, dv = m["qk_rope_head_dim"], m["v_head_dim"]
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def expert_params(m: dict) -> int:
    """Weights of one routed expert."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def latent_bytes_per_row(m: dict) -> int:
    """Latent cache bytes one position takes over all layers."""
    return m["num_hidden_layers"] * \
        (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * BYTES


def decode_step(m: dict, rows: int, ctx_rows: int) -> tuple:
    """(FLOPs, least bytes) of one decode step that emits ``rows`` tokens,
    whose rows attend ``ctx_rows`` cached positions in all, in the
    absorbed form that attends the latent cache.  Each row passes every
    weight outside the routed experts (W^UK and W^UV once each), the
    router, and its picks held here (``expected_routing``).  Each attended
    position costs 2 x heads x (2 x kv_lora_rank + rope) per layer.
    Bytes: the weights outside the routed experts once, the held experts
    that ``rows`` rows reach on average, each attended latent row once,
    each new one written once, and the rows' embeddings."""
    L, d = m["num_hidden_layers"], m["hidden_size"]
    h, r, dr = m["num_attention_heads"], m["kv_lora_rank"], m["qk_rope_head_dim"]
    dense_l, moe_l = _stages(m)
    e = m["n_routed_experts_published"]
    head = d * m["vocab_size"]
    shared = expert_params(m) * m["n_shared_experts"]
    outside = L * attn_params(m) + dense_l * 3 * d * m["intermediate_size"] \
        + moe_l * (d * e + shared) + head
    small = L * (2 * d + r) + d          # norm gains, latent norm, final
    picks, touched = expected_routing(m, rows)
    flops = 2 * (rows * outside + picks * expert_params(m)) \
        + 2 * h * (2 * r + dr) * L * ctx_rows
    nbytes = (outside + small + touched * expert_params(m)) * BYTES \
        + latent_bytes_per_row(m) * (ctx_rows + rows) + rows * d * BYTES
    return flops, nbytes


def expected_routing(m: dict, rows: int) -> tuple:
    """(picks routed to the held experts, held experts reached), summed
    over the expert layers, that ``rows`` tokens give on average when
    each picks num_experts_per_tok of the published experts uniformly:
    a held expert misses a row's picks with probability
    C(E-1, k)/C(E, k) = (E - k)/E."""
    e, k = m["n_routed_experts_published"], m["num_experts_per_tok"]
    eh, moe_l = m["n_routed_experts"], _stages(m)[1]
    return (moe_l * rows * k * eh / e,
            moe_l * eh * (1 - ((e - k) / e) ** rows))


def expert_dispatch(m: dict, held_picks: float, touched: float) -> tuple:
    """(FLOPs, least bytes) of the grouped expert matmuls of one dispatch
    that routes ``held_picks`` picks to ``touched`` held experts, both
    summed over the expert layers: each pick is 3 x 2 x hidden x
    moe_intermediate_size; the bytes are each touched expert's weights
    once and each pick's input and output rows."""
    flops = 2 * held_picks * expert_params(m)
    nbytes = (touched * expert_params(m)
              + held_picks * 2 * m["hidden_size"]) * BYTES
    return flops, nbytes

