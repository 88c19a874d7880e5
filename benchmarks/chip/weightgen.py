"""Random weights for a configuration, drawn on the device from the seed.

The benchmark makes the weights itself, in the layout the serving engine
reads, so the plain reference can use them without taking anything the
program made.  One jitted program draws every leaf with the hardware
generator (``rbg``) in float32 and stores it in the type it is served in;
the layout is checked against the program's own abstract parameters.

Scales follow the program's initialiser: matmul weights 1/sqrt(fan_in),
embedding 1, head 0.02.  Norm gains are 1 +- 0.1 and QKV biases +- 0.1,
so that neither is a no-op the reference could get wrong unseen.
"""
from __future__ import annotations

import math

import numpy as np

SQRT3 = math.sqrt(3.0)


def leaf_table(m: dict) -> dict:
    """name -> (shape, dtype, kind, scale) for the scanned decoder layout
    of a dense GQA model (``stages[0]`` holds the layers stacked)."""
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


def _nest(flat: dict) -> dict:
    """Flat names -> the engine's tree: ``L/...`` goes to stages[0]."""
    out: dict = {"stages": [{}]}
    for name, v in flat.items():
        parts = name.split("/")
        node = out["stages"][0] if parts[0] == "L" else out
        parts = parts[1:] if parts[0] == "L" else parts
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def seed_key(seed: int):
    """A 32-bit key from any whole number, 2**31 and beyond included."""
    import jax
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.key(word, impl="rbg")


def draw(m: dict, seed: int):
    """The weight tree, drawn in one jitted program on the default device."""
    import jax
    import jax.numpy as jnp
    table = leaf_table(m)
    dtypes = {"bf16": jnp.bfloat16, "f32": jnp.float32}

    def make(key):
        flat = {}
        for i, (name, (shape, dt, kind, scale)) in enumerate(
                sorted(table.items())):
            u = jax.random.uniform(jax.random.fold_in(key, i), shape,
                                   jnp.float32, -1.0, 1.0)
            if kind == "gain":
                v = 1.0 + scale * u
            elif kind == "bias":
                v = scale * u
            else:       # uniform with standard deviation ``scale``
                v = (scale * SQRT3) * u
            flat[name] = v.astype(dtypes[dt])
        return _nest(flat)

    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))


def check_layout(params, abstract) -> None:
    """Raise unless ``params`` has exactly the program's tree, shapes and
    dtypes (``abstract`` from ``repro.models.abstract_params``)."""
    import jax
    got = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    want = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), abstract)
    if got != want:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"benchmark {got}\nprogram   {want}")
