#!/usr/bin/env python3
"""Serve qwen2.5-3b at full published width on a TPU, and check the result.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the page-striped pool only

One chip runs three phases in this one process:

  serve      ``ServingEngine`` with a paged bf16 pool serves six requests
             (prompts of 19 to 300 tokens, the longest over three prefill
             chunks) through ``submit()`` / ``tick()`` / ``drain()``, twice
             on two engines; both runs must emit the same tokens;
  reference  each request's first-token logits against a plain
             ``models.forward`` of its prompt, which shares only the
             weights with the engine;
  kernel     the same requests through the fused Pallas paged-decode
             kernel, compiled (``interpret=False``), on a size-1
             page-striped mesh, against the lax path on the same mesh.

``--chips 4`` runs only the path that spans chips: the same requests with
the page pool striped over a (1, 4) ("data", "model") mesh, against a
(1, 1) striped run in the same process.

Weights are random from ``--seed``; nothing is downloaded.  Any failed
check raises, so the script exits non-zero; only a run where every phase
passed prints its last line, one JSON object naming the device.  There is
no CPU fallback: without a TPU the script exits non-zero before it builds
anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2.5-3b"
PROMPT_LENS = (19, 45, 83, 130, 201, 300)
SERVE = dict(max_batch=4, max_prompt=128, max_new_tokens=32, max_seq=336,
             page_size=16, record_logits=True)

# Logit tolerances, relative to the largest reference logit magnitude.
# Both sides compute in bf16 (unit roundoff 2**-9) but in different
# orders: the engine prefills in 128-token chunks against the paged pool,
# the reference runs one causal attention over the whole prompt, and the
# kernel reduces each 16-row page where the lax path reduces the whole
# window.  Each of the 36 layers re-rounds its activations, so the
# final hidden state can move by some tens of roundoffs; 5% of the logit
# scale (about 25 roundoffs) bounds that and still fails on a wrong
# mask, page, head or scale, which moves logits by the order of the
# logits themselves.
TOL_REFERENCE = 0.05
TOL_KERNEL = 0.05
# The striped pool merges each page's partial with exact identities
# (NEG_INF / 0) across shards, but under "fsdp_sp" the (1, 4) mesh also
# splits the FFN and head contractions over "model" and sums the chips'
# partial products, so the bf16 rounding order differs as above.
TOL_STRIPED = 0.05
# Tokens are not compared one for one: random weights leave near-ties
# between the top logits, and a run may pick either side of a tie.  Logits
# are compared at every step where both runs were fed the same tokens, and
# a logit error within a tolerance already bounds the top-two gap of any
# step where the chosen tokens differ to twice that tolerance.


def log(msg: str) -> None:
    print(msg, flush=True)


def check_device(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, found {len(devs)}")
    log(f"device: {devs[0].device_kind} x{len(devs)}")
    return devs


def make_prompts(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).tolist() for n in PROMPT_LENS]


def serve(cfg, params, prompts, label, **overrides):
    """One engine, one full run; returns (requests, engine)."""
    from repro.serve import Request, ServeConfig, ServingEngine
    sc = ServeConfig(**dict(SERVE, **overrides))
    eng = ServingEngine(cfg, params, sc)
    t0 = time.perf_counter()
    eng.warmup()
    t1 = time.perf_counter()
    reqs = [Request(i, list(p)) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.tick()
    eng.drain()
    t2 = time.perf_counter()
    for r in reqs:
        assert r.done and not r.failed, (label, r.rid)
        assert len(r.out_tokens) == sc.max_new_tokens, (label, r.rid)
        assert all(0 <= t < cfg.vocab_size for t in r.out_tokens), \
            (label, r.rid, r.out_tokens)
        assert len(r.logits) == sc.max_new_tokens, (label, r.rid)
    n_tok = sum(len(r.out_tokens) for r in reqs)
    log(f"{label}: compile+warmup {t1 - t0:.1f}s, served {len(reqs)} "
        f"requests / {n_tok} tokens in {t2 - t1:.1f}s (host clock, "
        f"includes first-shape dispatch), {eng.tick_no} ticks")
    return reqs, eng


def rel_err(got, want, vocab: int) -> float:
    got = np.asarray(got, np.float32)[..., :vocab]
    want = np.asarray(want, np.float32)[..., :vocab]
    assert np.isfinite(got).all() and np.isfinite(want).all()
    return float(np.abs(got - want).max() / np.abs(want).max())


def compare_streams(a, b, vocab: int):
    """Largest relative logit error over the steps both runs fed the same
    tokens (up to and including the first step whose argmax differs),
    and the share of emitted tokens that agree."""
    worst, same, total = 0.0, 0, 0
    for ra, rb in zip(a, b):
        n = len(ra.out_tokens)
        diff = [i for i in range(n) if ra.out_tokens[i] != rb.out_tokens[i]]
        upto = diff[0] + 1 if diff else n
        for k in range(upto):
            worst = max(worst, rel_err(rb.logits[k], ra.logits[k], vocab))
        same += n - len(diff)
        total += n
    return worst, same / total


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


def reference_first_logits(cfg, params, prompts):
    """First-token logits of each prompt from one plain causal forward of
    the right-padded batch (causal masking keeps padding out of every
    row at or before the prompt's last token)."""
    import jax
    import jax.numpy as jnp
    from repro.models import forward
    width = max(len(p) for p in prompts)
    toks = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    last = np.array([len(p) - 1 for p in prompts], np.int32)

    @jax.jit
    def first_logits(params, toks, last):
        logits, _, _ = forward(params, toks, cfg, mode="train")
        return logits[jnp.arange(toks.shape[0]), last]

    t0 = time.perf_counter()
    out = np.asarray(first_logits(params, toks, last), np.float32)
    log(f"reference: plain forward of {toks.shape} in "
        f"{time.perf_counter() - t0:.1f}s (compile included)")
    return out


def make_params(cfg, seed: int):
    import jax
    from repro.models import init_params
    t0 = time.perf_counter()
    # one jitted program: eagerly, every leaf's draw compiles on its own.
    params = jax.block_until_ready(jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed)))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, vocab {cfg.vocab_size}, "
        f"{n_bytes / 2**30:.2f} GiB of random weights in "
        f"{time.perf_counter() - t0:.1f}s")
    return params


def one_chip(cfg, seed, prompts, dev):
    from repro.distributed.sharding import use_rules
    from repro.kernels.paged_flash_decode import use_pallas_decode
    from repro.launch.mesh import make_test_mesh

    params = make_params(cfg, seed)
    # serve, twice: the second engine must emit the same tokens.
    run1, eng = serve(cfg, params, prompts, "serve run 1")
    del eng
    run2, eng = serve(cfg, params, prompts, "serve run 2")
    del eng
    for a, b in zip(run1, run2):
        assert a.out_tokens == b.out_tokens, ("repeat run differs", a.rid)
    log("serve: repeated run emitted identical tokens")

    # reference: first-token logits against a plain forward.
    ref = reference_first_logits(cfg, params, prompts)
    err = max(rel_err(r.logits[0], ref[i], cfg.vocab_size)
              for i, r in enumerate(run1))
    agree = np.mean([r.out_tokens[0] == int(np.argmax(
        ref[i][:cfg.vocab_size])) for i, r in enumerate(run1)])
    log(f"reference: first-token logit error {err:.3e} of the logit scale "
        f"(tolerance {TOL_REFERENCE}), first tokens agree {agree:.2f}")
    assert err <= TOL_REFERENCE, err

    # kernel: compiled Pallas decode vs lax on the same size-1 striped mesh.
    with use_rules(make_test_mesh((1, 1)), "fsdp_sp"):
        lax_run, eng = serve(cfg, params, prompts, "striped lax")
        del eng
        with use_pallas_decode(interpret=False):
            kern_run, eng = serve(cfg, params, prompts, "striped kernel",
                                  use_pallas_decode=True)
            del eng
    err, share = compare_streams(lax_run, kern_run, cfg.vocab_size)
    log(f"kernel: logit error vs lax {err:.3e} of the logit scale "
        f"(tolerance {TOL_KERNEL}), tokens agree {share:.3f}")
    assert err <= TOL_KERNEL, err
    log(f"peak device memory: {peak_bytes(dev)}")


def four_chips(cfg, seed, prompts, devs):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.distributed.sharding import use_rules

    mesh1 = Mesh(np.array(devs[:1]).reshape(1, 1), ("data", "model"))
    mesh4 = Mesh(np.array(devs[:4]).reshape(1, 4), ("data", "model"))
    # one replicated copy per chip; the 1-chip run reads device 0's copy.
    params4 = jax.device_put(make_params(cfg, seed),
                             NamedSharding(mesh4, PartitionSpec()))
    params1 = jax.tree.map(lambda x: next(
        s.data for s in x.addressable_shards if s.device == devs[0]),
        params4)
    with use_rules(mesh1, "fsdp_sp"):
        run1, eng1 = serve(cfg, params1, prompts, "striped 1 chip")
        bytes1 = eng1.pool_bytes_per_shard()
        del eng1
    with use_rules(mesh4, "fsdp_sp"):
        run4, eng4 = serve(cfg, params4, prompts, "striped 4 chips")
    assert eng4.pool_shards == 4, eng4.pool_shards
    assert eng4.pool_bytes_per_shard() * 4 == bytes1, \
        (eng4.pool_bytes_per_shard(), bytes1)
    flat = jax.tree.leaves(eng4.cache)
    pooled = [leaf for leaf, p in zip(flat, eng4._pooled) if p]
    assert pooled
    for leaf in pooled:
        assert leaf.sharding.device_set == set(devs[:4]), leaf.sharding
        shard_bytes = {s.device: s.data.nbytes for s in leaf.addressable_shards}
        assert len(shard_bytes) == 4, shard_bytes
        assert all(b * 4 == leaf.nbytes for b in shard_bytes.values()), \
            (leaf.shape, shard_bytes)
    log(f"striped pool: {len(pooled)} leaves on 4 devices, "
        f"{eng4.pool_bytes_per_shard() / 2**20:.1f} MiB per shard "
        f"vs {bytes1 / 2**20:.1f} MiB on one chip")
    err, share = compare_streams(run1, run4, cfg.vocab_size)
    bitwise = all(np.array_equal(np.asarray(a.logits), np.asarray(b.logits))
                  for a, b in zip(run1, run4))
    log(f"striped: logit error 4 vs 1 chip {err:.3e} of the logit scale "
        f"(tolerance {TOL_STRIPED}), bitwise equal {bitwise}, "
        f"tokens agree {share:.3f}")
    assert err <= TOL_STRIPED, err
    for d in devs[:4]:
        log(f"peak device memory {d.id}: {peak_bytes(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = check_device(args.chips)
    import jax
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)
    prompts = make_prompts(cfg.vocab_size, args.seed)
    if args.chips == 4:
        four_chips(cfg, args.seed, prompts, devs)
    else:
        one_chip(cfg, args.seed, prompts, devs[0])
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
