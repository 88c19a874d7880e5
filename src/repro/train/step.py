"""Train / prefill / decode step builders — the functions the launcher jits.

``make_train_step`` closes over (ArchConfig, AdamWConfig, options) and
returns ``step(state, batch) -> (state, metrics)`` with:

  * masked cross-entropy on vocab-sharded logits (loss math stays on the
    sharded layout; logsumexp/gather reduce via SPMD collectives),
  * MoE auxiliary load-balance loss,
  * optional microbatch gradient accumulation (scan over microbatches),
  * optional int8 error-feedback gradient compression at the reduction
    boundary (repro.distributed.compression),
  * AdamW with f32 master/moments sharded like the parameters.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.compression import compress_grads, init_error_feedback
from repro.distributed.sharding import lshard
from repro.models import forward
from repro.models.config import ArchConfig
from repro.train.optim import (AdamWConfig, OptState, abstract_opt_state,
                               adamw_update, init_opt_state)


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    ef: Any          # error-feedback buffers (None when compression off)


@dataclasses.dataclass(frozen=True)
class StepOptions:
    microbatches: int = 1
    grad_compress_bits: int = 0      # 0 = off, 8 = int8 EF compression


def init_train_state(params, opts: StepOptions = StepOptions()) -> TrainState:
    ef = init_error_feedback(params) if opts.grad_compress_bits else None
    return TrainState(params=params, opt=init_opt_state(params), ef=ef)


def abstract_train_state(abstract_params,
                         opts: StepOptions = StepOptions()) -> TrainState:
    ef = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
        abstract_params) if opts.grad_compress_bits else None
    return TrainState(params=abstract_params,
                      opt=abstract_opt_state(abstract_params), ef=ef)


def lm_loss(params, batch: Dict[str, jax.Array], cfg: ArchConfig):
    """Masked next-token cross entropy + MoE aux loss.

    batch['inputs']: (B, S) int32 tokens or (B, S, D) embeds.
    batch['labels']: (B, S) int32; negative = masked position.
    """
    logits, _, aux = forward(params, batch["inputs"], cfg, mode="train")
    labels = batch["labels"]
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(
        lg, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    n_tok = jnp.maximum(mask.sum(), 1.0)
    xent = jnp.sum((lse - ll) * mask) / n_tok
    loss = xent + cfg.aux_loss_weight * aux
    return loss, {"xent": xent, "aux": aux, "tokens": n_tok}


def make_train_step(cfg: ArchConfig, opt_cfg: AdamWConfig = AdamWConfig(),
                    opts: StepOptions = StepOptions()):
    grad_fn = jax.value_and_grad(lm_loss, has_aux=True)

    def compute_grads(params, batch):
        if opts.microbatches <= 1:
            (loss, aux), grads = grad_fn(params, batch, cfg)
            return loss, aux, grads

        def micro(acc, mb):
            (loss, aux), g = grad_fn(params, mb, cfg)
            acc = jax.tree.map(
                lambda a, x: a + x.astype(jnp.float32), acc, g)
            return acc, (loss, aux)

        split = lambda x: x.reshape(
            opts.microbatches, x.shape[0] // opts.microbatches, *x.shape[1:])
        mbs = jax.tree.map(split, batch)
        zero = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        gacc, (losses, auxs) = jax.lax.scan(micro, zero, mbs)
        grads = jax.tree.map(lambda g: g / opts.microbatches, gacc)
        # metrics cover EVERY microbatch (not just the last one scanned):
        # `loss` averages the per-microbatch losses — exactly the objective
        # the accumulated gradient optimizes — `xent` is token-weighted so
        # it equals the whole-batch cross entropy, `tokens` sums, and any
        # other auxiliary is the plain mean.
        n_tok = auxs["tokens"]
        w = n_tok / jnp.maximum(n_tok.sum(), 1.0)
        aux = {k: (jnp.sum(v * w) if k == "xent"
                   else v.sum() if k == "tokens" else jnp.mean(v))
               for k, v in auxs.items()}
        return jnp.mean(losses), aux, grads

    def step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        loss, aux, grads = compute_grads(state.params, batch)
        ef = state.ef
        if opts.grad_compress_bits:
            grads, ef = compress_grads(grads, ef, opts.grad_compress_bits)
        params, opt, om = adamw_update(opt_cfg, grads, state.opt, cfg.dtype)
        metrics = {"loss": loss, **aux, **om}
        return TrainState(params=params, opt=opt, ef=ef), metrics

    return step


# ---------------------------------------------------------------------------
# Serving steps.  Each returns (out, cache); a model with experts adds a
# third output, the step's int32 (3,) expert pick counts summed over the
# layers: picks routed to the experts held here, all picks (padding
# excluded), and held experts that any pick reached (``forward``'s aux).
# ---------------------------------------------------------------------------

def _served(cfg: ArchConfig, out, cache, aux):
    if not cfg.n_experts:
        return out, cache
    return out, cache, aux.astype(jnp.int32)


def make_prefill_step(cfg: ArchConfig):
    def prefill(params, inputs, cache):
        logits, cache, aux = forward(params, inputs, cfg, cache=cache,
                                     mode="prefill")
        return _served(cfg, logits[:, -1, :], cache, aux)
    return prefill


def make_decode_step(cfg: ArchConfig):
    def decode(params, cache, token, pos):
        """token: (B, 1) ids or (B, 1, D) embeds.

        pos: scalar int32, or — as the serving engine passes it — a (B,)
        int32 vector of per-slot positions where -1 marks an inactive slot
        (no cache write; that row's logits are garbage and ignored)."""
        logits, cache, aux = forward(params, token, cfg, cache=cache,
                                     mode="decode", pos=pos)
        return _served(cfg, logits[:, -1, :], cache, aux)
    return decode


def make_chunked_prefill_step(cfg: ArchConfig):
    """Single-pass chunked prefill for the serving engine.

    Consumes a whole right-padded prompt chunk in ONE forward instead of
    O(prompt_len) per-token decode dispatches — prefill is compute-bound
    (Shaheen Table 4/6), so it should be one large offload, not many tiny
    ones.  Returns the logits at each slot's last valid token (the
    post-prompt prediction) plus the chunk-filled cache.
    """
    def prefill(params, cache, tokens, lengths):
        """tokens: (B, S) right-padded ids; lengths: (B,) valid counts
        (0 = slot not being admitted — its cache region is untouched)."""
        logits, cache, aux = forward(params, tokens, cfg, cache=cache,
                                     mode="chunk", pos=lengths)
        last = jnp.take_along_axis(
            logits, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)
        return _served(cfg, last[:, 0, :], cache, aux)
    return prefill


def make_chunked_prefill_resume_step(cfg: ArchConfig):
    """RESUMABLE chunked prefill into a CONTIGUOUS cache.

    The contiguous twin of :func:`make_paged_chunked_prefill_step`:
    ``offsets`` is the (B,) start row of each slot's chunk, so a prompt
    longer than one chunk fills across several dispatches (rows
    [offset, offset + length), attending the cached history [0, offset)
    too).  The tiered engine's OVERSIZED-context path uses this to
    stream a host-resident contiguous cache through the device chunk by
    chunk.  Returns each slot's last-valid-token logits, like the other
    prefill builders."""
    def prefill(params, cache, tokens, lengths, offsets):
        logits, cache, aux = forward(params, tokens, cfg, cache=cache,
                                     mode="chunk", pos=lengths,
                                     offset=offsets)
        last = jnp.take_along_axis(
            logits, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)
        return _served(cfg, last[:, 0, :], cache, aux)
    return prefill


def make_paged_decode_step(cfg: ArchConfig):
    """Decode against a PAGED cache (models.init_paged_cache): the extra
    ``pages`` (B, P) argument is the engine's per-slot page table mapping
    logical cache rows to pool pages; -1 entries are unmapped."""
    def decode(params, cache, token, pos, pages):
        logits, cache, aux = forward(params, token, cfg, cache=cache,
                                     mode="decode", pos=pos, pages=pages)
        return _served(cfg, logits[:, -1, :], cache, aux)
    return decode


def make_paged_chunked_prefill_step(cfg: ArchConfig):
    """RESUMABLE chunked prefill into a PAGED cache.

    ``offsets`` is the (B,) start row of each slot's chunk: tokens sit at
    cache rows [offset, offset + length) and attend over the cached
    history [0, offset) too, so a prompt longer than one chunk fills
    across several dispatches interleaved with decode (continuous
    batching).  An ALL-fresh wave passes offsets=None (a distinct jit
    trace of the same callable) and keeps the cheaper single-pass chunk
    kernel — no full-window gather.  Returns each slot's
    LAST-valid-token logits — the post-prompt prediction when this chunk
    finishes the prompt, intermediate (discarded) logits otherwise."""
    def prefill(params, cache, tokens, lengths, pages, offsets):
        logits, cache, aux = forward(params, tokens, cfg, cache=cache,
                                     mode="chunk", pos=lengths, pages=pages,
                                     offset=offsets)
        last = jnp.take_along_axis(
            logits, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)
        return _served(cfg, last[:, 0, :], cache, aux)
    return prefill


def make_paged_verify_step(cfg: ArchConfig):
    """Speculative VERIFY against a PAGED cache.

    Scores a (B, S) block of candidate tokens — each slot's last committed
    token followed by its draft proposals — at cache rows
    [offset, offset + length) in ONE dispatch, and returns the FULL
    (B, S, V) logits: the verifier needs every position's argmax (row i's
    logits decide whether draft token i+1 is accepted and what to emit if
    it is not), unlike the prefill builders which gather only the last
    valid row.  ``mode='verify'`` runs attention in DECODE-order flash
    numerics with a per-row causal mask, so the logits at every valid row
    are BITWISE the logits plain greedy decode would produce at that
    position — the speculative-decoding bit-identity contract.  Slots not
    in the round pass length 0 (no cache write, garbage logits ignored).
    """
    def verify(params, cache, tokens, lengths, pages, offsets):
        logits, cache, aux = forward(params, tokens, cfg, cache=cache,
                                     mode="verify", pos=lengths, pages=pages,
                                     offset=offsets)
        return _served(cfg, logits, cache, aux)
    return verify
