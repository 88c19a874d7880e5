"""Model code of a dense GQA decoder (Qwen2 / Qwen3): the program's config,
the weight layout, the float32 reference and the operation counts.

``m`` is the ``model`` block of a configuration file (Hugging Face key
names).

Reference: written from the published architecture, not from the
program: RMSNorm (eps from the config), q/k/v projections with optional
biases, optional per-head RMSNorm of q and k (Qwen3), rotary embedding
on the half-split layout with the config's theta, causal grouped-query
attention scaled by 1/sqrt(head_dim), output projection, SwiGLU MLP,
final RMSNorm, head (``reference``).  The weights are widened one layer
at a time inside a scan, so the reference fits beside them on the chip.

Weights: scales follow the program's initialiser: matmul weights
1/sqrt(fan_in), embedding 1, head 0.02.  Norm gains are 1 +- 0.1 and QKV
biases +- 0.1, so that neither is a no-op the reference could get wrong
unseen.

Counts: weights at 2 bytes (bf16) and so is the KV pool.  These are the
work the algorithm needs, not what the program happens to issue: padding
rows, inactive slots and the embedding table (of which a step reads only
its rows) are left out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import reference
from counts import BYTES
from reference import mm, rms, rope

# model-block keys -> ArchConfig fields; the program's config must agree.
ARCH_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
             "intermediate_size": "d_ff", "vocab_size": "vocab_size",
             "rope_theta": "rope_theta", "qk_norm": "qk_norm",
             "attention_bias": "qkv_bias"}


def arch(conf: dict):
    """The program's ArchConfig, checked against the ``model`` block."""
    from repro.configs import get_config
    # an empty pattern is derived again from the (overridden) layer count
    a = get_config(conf["arch"]).with_(pattern=(), **conf["overrides"])
    m = conf["model"]
    if a.n_blocks() != m["num_hidden_layers"]:
        raise ValueError(f"{conf['arch']}: program runs {a.n_blocks()} "
                         f"layers, config {m['num_hidden_layers']}")
    for key, field in ARCH_KEYS.items():
        if getattr(a, field) != m[key]:
            raise ValueError(f"{conf['arch']}: program has {field}="
                             f"{getattr(a, field)}, config {key}={m[key]}")
    if m["tie_word_embeddings"] or m["hidden_act"] != "silu" or \
            a.mlp_act != "silu_glu" or a.norm != "rms" or \
            a.family != "dense":
        raise ValueError(f"{conf['arch']}: not a dense SwiGLU/RMSNorm model "
                         "with an untied head, which is all the reference "
                         "implements")
    return a


def leaf_table(m: dict) -> dict:
    """name -> (shape, dtype, kind, scale) for the scanned decoder layout
    (``L/`` leaves are the layers of ``stages[0]``, stacked)."""
    d, f = m["hidden_size"], m["intermediate_size"]
    h, kv, dh = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    L, vp = m["num_hidden_layers"], -(-m["vocab_size"] // 256) * 256
    t = {
        "embed": ((vp, d), "bf16", "std", 1.0),
        "final_norm/w": ((d,), "f32", "gain", 0.1),
        "lm_head": ((d, vp), "bf16", "std", 0.02),
        "L/ln1/w": ((L, d), "f32", "gain", 0.1),
        "L/ln2/w": ((L, d), "f32", "gain", 0.1),
        "L/attn/wq": ((L, d, h * dh), "bf16", "std", d ** -0.5),
        "L/attn/wk": ((L, d, kv * dh), "bf16", "std", d ** -0.5),
        "L/attn/wv": ((L, d, kv * dh), "bf16", "std", d ** -0.5),
        "L/attn/wo": ((L, h * dh, d), "bf16", "std", (h * dh) ** -0.5),
        "L/ffn/w_gate": ((L, d, f), "bf16", "std", d ** -0.5),
        "L/ffn/w_up": ((L, d, f), "bf16", "std", d ** -0.5),
        "L/ffn/w_down": ((L, f, d), "bf16", "std", f ** -0.5),
    }
    if m.get("attention_bias"):
        t["L/attn/bq"] = ((L, h * dh), "bf16", "bias", 0.1)
        t["L/attn/bk"] = ((L, kv * dh), "bf16", "bias", 0.1)
        t["L/attn/bv"] = ((L, kv * dh), "bf16", "bias", 0.1)
    if m.get("qk_norm"):
        t["L/attn/q_norm"] = ((L, dh), "f32", "gain", 0.1)
        t["L/attn/k_norm"] = ((L, dh), "f32", "gain", 0.1)
    return t


# -- float32 reference ------------------------------------------------------

def _layer(m, quant, x, lw):
    s = x.shape[0]
    h, kv, dh = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps = m["rms_norm_eps"]
    a = rms(x, lw["ln1"]["w"], eps)
    at = lw["attn"]
    q, k, v = (mm(a, at[n], quant) for n in ("wq", "wk", "wv"))
    if m.get("attention_bias"):
        q = q + at["bq"].astype(jnp.float32)
        k = k + at["bk"].astype(jnp.float32)
        v = v + at["bv"].astype(jnp.float32)
    q, k, v = q.reshape(s, h, dh), k.reshape(s, kv, dh), v.reshape(s, kv, dh)
    if m.get("qk_norm"):
        q = rms(q, at["q_norm"], eps)
        k = rms(k, at["k_norm"], eps)
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    g = h // kv
    q = q.reshape(s, kv, g, dh)
    sc = jnp.einsum("qkgd,skd->kgqs", q, k,
                    precision=jax.lax.Precision.HIGHEST) * dh ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(s, h * dh)
    x = x + mm(o, at["wo"], quant)
    b = rms(x, lw["ln2"]["w"], eps)
    ff = lw["ffn"]
    up = jax.nn.silu(mm(b, ff["w_gate"], quant)) * mm(b, ff["w_up"], quant)
    return x + mm(up, ff["w_down"], quant)


def hidden(params, tokens, m, quant=None):
    """Final-norm hidden states (S, d) of one sequence."""
    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(lambda c, lw: (_layer(m, quant, c, lw), None), x,
                        params["stages"][0])
    return rms(x, params["final_norm"]["w"], m["rms_norm_eps"])


def served_gaps(params, m: dict, prompt, served, length: int,
                control: bool = False):
    """``reference.served_gaps`` of this decoder."""
    return reference.served_gaps(hidden, params, m, prompt, served, length,
                                 control)


# -- operations and least bytes ---------------------------------------------

def layer_params(m: dict) -> int:
    """Matmul weights of one decoder layer."""
    d, f = m["hidden_size"], m["intermediate_size"]
    h, kv, dh = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    return d * h * dh * 2 + d * kv * dh * 2 + 3 * d * f


def layer_small(m: dict) -> int:
    """Per-layer vectors: the two norm gains, q/k-norm gains, QKV biases."""
    d = m["hidden_size"]
    h, kv, dh = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    n = 2 * d
    if m.get("qk_norm"):
        n += 2 * dh
    if m.get("attention_bias"):
        n += (h + 2 * kv) * dh
    return n


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def kv_bytes_per_row(m: dict) -> int:
    """KV pool bytes one cached position takes over all layers."""
    return (m["num_hidden_layers"] * 2 * m["num_key_value_heads"]
            * m["head_dim"] * BYTES)


def weight_bytes(m: dict) -> int:
    """Bytes a step must read once: every layer, the final norm, the head."""
    per_layer = layer_params(m) + layer_small(m)
    return (m["num_hidden_layers"] * per_layer + m["hidden_size"]
            + head_params(m)) * BYTES


def decode_step(m: dict, rows: int, ctx_rows: int) -> tuple:
    """(FLOPs, least bytes) of one decode step that emits ``rows`` tokens,
    whose rows attend ``ctx_rows`` cached positions in all (their own
    included).  Each row passes every layer and the head; attention costs
    2 x 2 x heads x head_dim per attended position and layer (scores and
    values).  Bytes: the weights once, each attended KV row once, each
    new KV row written once, and the rows' embeddings."""
    L, d = m["num_hidden_layers"], m["hidden_size"]
    h, dh = m["num_attention_heads"], m["head_dim"]
    flops = 2 * rows * (L * layer_params(m) + head_params(m)) \
        + 4 * h * dh * L * ctx_rows
    nbytes = weight_bytes(m) + kv_bytes_per_row(m) * (ctx_rows + rows) \
        + rows * d * BYTES
    return flops, nbytes
