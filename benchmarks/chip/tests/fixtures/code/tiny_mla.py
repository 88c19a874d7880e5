"""Model code of a DeepSeek-V2 decoder with dense MLPs: multi-head latent
attention (MLA) over leading dense layers in a stage of their own, then
the rest in a second stage.  A fixture: it shows that a second kind of
model needs only new files, and is cut to CPU size by its configuration.

``m`` is the ``model`` block of a configuration file (the Hugging Face
key names of deepseek-ai/DeepSeek-V2-Lite).

Reference: arXiv:2405.04434, section 2.1, in the expanded form, without
compression of the query (``q_lora_rank`` null, as in V2-Lite).  For each
position t, with h_t the RMS-normed input:

    [q_t^C ; q_t^R] = W^Q h_t per head    (nope dims, then rope dims)
    c_t^KV = W^DKV h_t                    (the latent, r = kv_lora_rank)
    k_t^R  = RoPE(W^KR h_t)               (one rope key shared by heads)
    q_t^R  = RoPE(q_t^R)
    k_t^C  = W^UK c_t^KV,  v_t^C = W^UV c_t^KV   (per head)
    o_t    = softmax_j([q^C; q^R]_t . [k^C; k^R]_j / sqrt(nope + rope)) v_j^C
    u_t    = W^O o_t

then a SwiGLU MLP, each sublayer residual, final RMSNorm and the head
(``reference``).  Departures from the paper, each as the published model
code has it: W^DKV and W^KR are one matrix (``w_dkv``, latent columns
first), and the latent is RMS-normed (``kv_norm``) before W^UK and W^UV;
RoPE rotates halves of the rope dims, where the published code first
de-interleaves them, which is a fixed permutation of W^Q's and W^KR's
rope columns and leaves random weights as random; no YaRN scaling of the
rope or of the softmax (the published config has factor 40).  No expert
layer: ``n_routed_experts`` is null, so every layer is a dense MLP.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import reference
from counts import BYTES
from reference import mm, rms, rope

# model-block keys -> ArchConfig fields; the program's config must agree.
ARCH_KEYS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
             "intermediate_size": "d_ff", "vocab_size": "vocab_size",
             "rope_theta": "rope_theta", "kv_lora_rank": "kv_lora_rank",
             "qk_nope_head_dim": "qk_nope_dim",
             "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_head_dim"}


def _stages(m: dict) -> list:
    """Layers of each stage: the leading dense ones, then the rest."""
    k = m["first_k_dense_replace"]
    return [k, m["num_hidden_layers"] - k]


def arch(conf: dict):
    """The program's ArchConfig, checked against the ``model`` block."""
    from repro.configs import get_config
    over = dict(conf["overrides"])
    over["pattern"] = tuple(tuple(e) for e in over.get("pattern", ()))
    a = get_config(conf["arch"]).with_(**over)
    m = conf["model"]
    for key, field in ARCH_KEYS.items():
        if getattr(a, field) != m[key]:
            raise ValueError(f"{conf['arch']}: program has {field}="
                             f"{getattr(a, field)}, config {key}={m[key]}")
    want = tuple(("scan", "mla_mlp", n) for n in _stages(m))
    if a.pattern != want or a.n_blocks() != m["num_hidden_layers"]:
        raise ValueError(f"{conf['arch']}: program runs {a.pattern}, the "
                         f"reference {want}")
    if m["n_routed_experts"] is not None or a.n_experts \
            or m["q_lora_rank"] is not None \
            or m["rope_scaling"] is not None or m["tie_word_embeddings"] \
            or m["attention_bias"] or m["hidden_act"] != "silu" \
            or a.mlp_act != "silu_glu" or a.norm != "rms" or a.quant:
        raise ValueError(f"{conf['arch']}: not the dense-MLP MLA model "
                         "with an untied head that the reference implements")
    return a


def leaf_table(m: dict) -> dict:
    """name -> (shape, dtype, kind, scale); ``L/`` leaves are stage 0's
    layers, ``L1/`` stage 1's, each stacked."""
    d, f, h = m["hidden_size"], m["intermediate_size"], m["num_attention_heads"]
    r, dn = m["kv_lora_rank"], m["qk_nope_head_dim"]
    dr, dv = m["qk_rope_head_dim"], m["v_head_dim"]
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
            f"{s}/ffn/w_gate": ((n, d, f), "bf16", "std", d ** -0.5),
            f"{s}/ffn/w_up": ((n, d, f), "bf16", "std", d ** -0.5),
            f"{s}/ffn/w_down": ((n, f, d), "bf16", "std", f ** -0.5),
        })
    return t


# -- float32 reference ------------------------------------------------------

def _layer(m, quant, x, lw):
    s = x.shape[0]
    h, r = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    at = lw["attn"]
    a = rms(x, lw["ln1"]["w"], eps)
    q = mm(a, at["w_q"], quant).reshape(s, h, dn + dr)
    ckr = mm(a, at["w_dkv"], quant)
    c = rms(ckr[:, :r], at["kv_norm"], eps)                 # c_t^KV
    k_r = rope(ckr[:, None, r:], theta)                     # k_t^R, (s, 1, dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], theta)], -1)
    k_c = mm(c, at["w_uk"], quant).reshape(s, h, dn)
    v = mm(c, at["w_uv"], quant).reshape(s, h, dv)
    k = jnp.concatenate([k_c, jnp.broadcast_to(k_r, (s, h, dr))], -1)
    sc = jnp.einsum("qhd,shd->hqs", q, k,
                    precision=jax.lax.Precision.HIGHEST) * (dn + dr) ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("hqs,shd->qhd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(s, h * dv)
    x = x + mm(o, at["w_o"], quant)
    b = rms(x, lw["ln2"]["w"], eps)
    ff = lw["ffn"]
    up = jax.nn.silu(mm(b, ff["w_gate"], quant)) * mm(b, ff["w_up"], quant)
    return x + mm(up, ff["w_down"], quant)


def hidden(params, tokens, m, quant=None):
    """Final-norm hidden states (S, d) of one sequence, stage by stage."""
    x = params["embed"][tokens].astype(jnp.float32)
    for stage in params["stages"]:
        x, _ = jax.lax.scan(lambda c, lw: (_layer(m, quant, c, lw), None), x,
                            stage)
    return rms(x, params["final_norm"]["w"], m["rms_norm_eps"])


def served_gaps(params, m: dict, prompt, served, length: int,
                control: bool = False):
    """``reference.served_gaps`` of this decoder."""
    return reference.served_gaps(hidden, params, m, prompt, served, length,
                                 control)


# -- operations and least bytes ---------------------------------------------

def layer_params(m: dict) -> int:
    """Matmul weights of one layer."""
    d, f, h = m["hidden_size"], m["intermediate_size"], m["num_attention_heads"]
    r, dn = m["kv_lora_rank"], m["qk_nope_head_dim"]
    dr, dv = m["qk_rope_head_dim"], m["v_head_dim"]
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) \
        + h * dv * d + 3 * d * f


def decode_step(m: dict, rows: int, ctx_rows: int) -> tuple:
    """(FLOPs, least bytes) of one decode step that emits ``rows`` tokens,
    whose rows attend ``ctx_rows`` cached positions in all, in the
    absorbed form that attends the latent cache: each row passes every
    layer's weights (W^UK and W^UV once each, on the query and on the
    output side) and the head; each attended position costs
    2 x heads x (2 x kv_lora_rank + rope) per layer (scores against the
    latent and the rope key, values in the latent).  Bytes: the weights
    once, each attended latent row once, each new one written once, and
    the rows' embeddings."""
    L, d = m["num_hidden_layers"], m["hidden_size"]
    h, r, dr = m["num_attention_heads"], m["kv_lora_rank"], m["qk_rope_head_dim"]
    head = d * m["vocab_size"]
    small = 2 * d + r                   # norm gains, latent norm
    flops = 2 * rows * (L * layer_params(m) + head) \
        + 2 * h * (2 * r + dr) * L * ctx_rows
    weights = (L * (layer_params(m) + small) + d + head) * BYTES
    nbytes = weights + L * (r + dr) * BYTES * (ctx_rows + rows) \
        + rows * d * BYTES
    return flops, nbytes
