"""What every model's float32 reference shares, and the comparison that
decides ``correct``.

A model's code (``code/<code>.py``) writes its decoder from the published
architecture as ``hidden(params, tokens, m, quant)``: the final-norm
hidden states (S, d) of one sequence, read from the weights the benchmark
drew (``weightgen``) by name.  This module gives it the float32 pieces
(``mm``, ``rms``, ``rope``), reads the untied head one block of the
vocabulary at a time, and compares.

Every matmul runs at ``precision=highest`` in float32.  The control
(``quant="fp8"``) is the same forward with both operands of every
projection and of the head rounded to float8_e4m3fn (weights per output
column, activations per row, each scaled to the format's largest value):
the step below bf16 a later change could be tempted by.

The number compared is the widest gap, over the served positions of the
sampled requests, by which the served token's reference logit lies
below the reference's best logit at that position (true vocabulary
only).  Greedy serving with no fault reads a gap of rounding size: it
picks the reference's best, or a token tied with it to within bf16
rounding.  For the control the gap is read at the token the lower
precision puts first.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCKS = 16
F8_MAX = 448.0


def mm(x, w, quant):
    """x @ w in float32, or through fp8 for the control."""
    w = w.astype(jnp.float32)
    if quant == "fp8":
        sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / F8_MAX + 1e-30
        sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / F8_MAX + 1e-30
        x = (x / sx).astype(jnp.float8_e4m3fn).astype(jnp.float32) * sx
        w = (w / sw).astype(jnp.float8_e4m3fn).astype(jnp.float32) * sw
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def rms(x, g, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * g.astype(jnp.float32)


def rope(x, theta):
    """x: (S, heads, dh); rotate halves [0, dh/2) and [dh/2, dh)."""
    s, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * \
        jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _scan_head(params, m, hid, quant, fn, init):
    """Fold ``fn(carry, logits_block, col0)`` over the vocabulary blocks;
    columns at or past vocab_size read -inf."""
    w = params["lm_head"]
    vocab, vp = m["vocab_size"], w.shape[1]
    if vp % HEAD_BLOCKS:
        raise ValueError(f"head width {vp} is no multiple of {HEAD_BLOCKS}")
    vb = vp // HEAD_BLOCKS

    def body(carry, i):
        lg = mm(hid, jax.lax.dynamic_slice_in_dim(w, i * vb, vb, 1), quant)
        cols = i * vb + jnp.arange(vb)
        lg = jnp.where(cols[None, :] < vocab, lg, -jnp.inf)
        return fn(carry, lg, i * vb), None

    carry, _ = jax.lax.scan(body, init, jnp.arange(HEAD_BLOCKS))
    return carry


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def gaps(hidden, params, tokens, targets, valid, m_json, control: bool):
    """Per-position gap best - logit[target] of the f32 reference
    ``hidden`` over one padded sequence.  ``targets[i]`` is the token
    served after position i (ignored where not ``valid``).  With
    ``control``, the gap is read at the token the fp8 forward puts first
    instead of at ``targets``.  ``m_json`` is the ``model`` block as JSON
    (hashable, nested groups and all).  Returns (gaps (S,), chosen tokens
    (S,))."""
    m = json.loads(m_json)
    s = tokens.shape[0]
    if control:
        hq = hidden(params, tokens, m, "fp8")

        def amax(c, lg, col0):
            best, arg = c
            j = jnp.argmax(lg, -1)
            v = jnp.max(lg, -1)
            take = v > best
            return jnp.where(take, v, best), jnp.where(take, col0 + j, arg)

        _, targets = _scan_head(params, m, hq, "fp8", amax,
                                (jnp.full((s,), -jnp.inf),
                                 jnp.zeros((s,), jnp.int32)))
    hid = hidden(params, tokens, m, None)

    def best_and_pick(c, lg, col0):
        best, pick = c
        cols = col0 + jnp.arange(lg.shape[1])
        hit = cols[None, :] == targets[:, None]
        pick = jnp.where(hit.any(-1), jnp.sum(jnp.where(hit, lg, 0.0), -1),
                         pick)
        return jnp.maximum(best, jnp.max(lg, -1)), pick

    best, pick = _scan_head(params, m, hid, None, best_and_pick,
                            (jnp.full((s,), -jnp.inf), jnp.full((s,), -jnp.inf)))
    return jnp.where(valid, best - pick, 0.0), targets


def served_gaps(hidden, params, m: dict, prompt, served, length: int,
                control: bool = False):
    """Gaps at each served token of one request: the reference runs over
    prompt + served[:-1], padded to ``length`` rows (one compiled shape
    per cell), and position len(prompt) - 1 + i predicts served[i]."""
    seq = list(prompt) + list(served[:-1])
    if len(seq) > length:
        raise ValueError(f"sequence of {len(seq)} rows over the reference's "
                         f"{length}")
    toks = np.zeros(length, np.int32)
    toks[:len(seq)] = seq
    tgt = np.zeros(length, np.int32)
    valid = np.zeros(length, bool)
    p = len(prompt)
    tgt[p - 1:p - 1 + len(served)] = served
    valid[p - 1:p - 1 + len(served)] = True
    with jax.default_matmul_precision("highest"):
        g, _ = gaps(hidden, params, jnp.asarray(toks), jnp.asarray(tgt),
                    jnp.asarray(valid), json.dumps(m, sort_keys=True),
                    control)
    return np.asarray(g)[valid]
