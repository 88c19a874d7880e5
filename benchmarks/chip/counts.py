"""Operations and least bytes of one serving step, from shapes alone.

``model`` is the ``model`` block of a configuration file (Hugging Face
key names).  Weights are counted at 2 bytes (bf16) and so is the KV pool.
These are the work the algorithm needs, not what the program happens to
issue: padding rows, inactive slots and the embedding table (of which a
step reads only its rows) are left out.
"""
from __future__ import annotations

BYTES = 2


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


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time of a step on the chip: the larger of its operations over
    the bf16 peak and its bytes over HBM bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
