"""Serving launcher: session-API requests against any assigned arch.

Submits a mixed-priority batch through the session surface
(``submit() -> RequestHandle``), streams the highest-priority request's
tokens as decode ticks emit them, drains the rest, and reports per-
request TTFT (in engine ticks) plus the scheduler's deadline ledger.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduce \
      --quant w4a16 --requests 6

``--replicas N`` (N > 1) serves the same traffic through the replica
router instead of a bare engine: N engine replicas behind the wire
boundary, prefix-affinity placement, cross-replica migration — the
session surface (submit/stream/drain) is unchanged.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import all_archs, get_config, reduce_config
from repro.core.quant import QuantConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.models.model import quantize_for_serving
from repro.serve import (Request, Router, RouterConfig, ServeConfig,
                         ServingEngine)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=all_archs())
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--quant", default="none",
                    choices=["none", "w8a8", "w4a16", "w2a16", "w4a8"])
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=2)
    ap.add_argument("--kv-bits", type=int, default=0, choices=[0, 8, 4],
                    help="KV pool page storage: 0 = model dtype (the "
                    "bit-exact default), 8/4 = int8/int4 pages with "
                    "per-row scales (ServeConfig.kv_format)")
    ap.add_argument("--spec-draft", default=None, metavar="ARCH",
                    help="speculative decoding: 'self' (the target "
                    "drafts for itself — the deterministic showcase) or "
                    "an arch name whose REDUCED config drafts; emitted "
                    "tokens stay bit-identical to plain greedy decode")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per engine tick, all "
                    "verified in one dispatch (--spec-draft)")
    ap.add_argument("--spec-draft-pages", type=int, default=None,
                    help="draft pool page budget; too few degrades "
                    "slots to plain decode instead of failing "
                    "(--spec-draft)")
    ap.add_argument("--ttft-deadline", type=int, default=8,
                    help="deadline (engine ticks) stamped on the "
                    "high-priority half of the requests")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas; > 1 serves through the "
                    "replica router (prefix-affinity placement, "
                    "wire-format boundary, cross-replica migration)")
    ap.add_argument("--routing", default="affinity",
                    choices=["affinity", "least_loaded", "random"],
                    help="router placement policy (--replicas > 1)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduce_config(cfg)
    if cfg.input_mode != "tokens":
        raise SystemExit(f"{cfg.name} has a stub frontend (embeds input); "
                         "serve a token arch instead")
    params = init_params(cfg, jax.random.PRNGKey(0))
    if args.quant != "none":
        w = int(args.quant[1])
        mode = "wo" if args.quant.endswith("a16") else "int"
        a = 16 if mode == "wo" else int(args.quant.split("a")[1])
        q = QuantConfig(mode=mode, a_bits=8 if a == 16 else a, w_bits=w,
                        use_kernel=False)
        cfg = cfg.with_(quant=q)
        params, n = quantize_for_serving(cfg, params)
        print(f"serving with {args.quant}: packed {n} tensors")

    key = jax.random.PRNGKey(1)
    reqs = []
    for i in range(args.requests):
        # independent keys for the length draw and the token draw —
        # reusing one key correlates prompt length with its content.
        key, k_len, k_tok = jax.random.split(key, 3)
        n = int(jax.random.randint(k_len, (), 2, 9))
        # odd rids are the deadline-critical class (navigation-style
        # traffic); even rids are best-effort bulk work.
        prio, deadline = (1, args.ttft_deadline) if i % 2 else (0, None)
        reqs.append(Request(
            i, [int(t) for t in jax.random.randint(k_tok, (n,), 0,
                                                   cfg.vocab_size)],
            priority=prio, ttft_deadline=deadline))
    kv_format = "fp" if args.kv_bits == 0 else f"int{args.kv_bits}"
    sc = ServeConfig(max_batch=args.max_batch, max_prompt=32,
                     max_new_tokens=args.max_new_tokens,
                     kv_format=kv_format, spec_draft=args.spec_draft,
                     spec_k=args.spec_k,
                     spec_draft_pages=args.spec_draft_pages)
    if args.replicas > 1:
        sess = Router(cfg, params, sc,
                      RouterConfig(replicas=args.replicas,
                                   routing=args.routing))
        first_eng = sess.replicas[0].eng
        print(f"router: {args.replicas} replicas, "
              f"routing={args.routing}")
    else:
        sess = first_eng = ServingEngine(cfg, params, sc)
    if kv_format != "fp":
        print(f"KV pool pages stored as {kv_format} "
              f"({first_eng.pool_bytes_per_shard() / 1e3:.1f}KB "
              f"pool/shard{'/replica' if args.replicas > 1 else ''})")
    handles = [sess.submit(r) for r in reqs]

    # stream the first high-priority request token by token (this drives
    # engine/router ticks, so everything else keeps decoding beneath)...
    demo = next((h for h in handles if h.req.priority > 0), handles[0])
    print(f"streaming req {demo.req.rid}: ", end="", flush=True)
    for tok in demo.stream():
        print(tok, end=" ", flush=True)
    print()
    # ...then finish the rest and close the session.
    sess.drain()

    for h in handles:
        r = h.req
        tag = f" prio={r.priority}"
        if args.replicas > 1:
            tag += f" replica={h.replica}"
        if r.ttft_deadline is not None:
            tag += (f" ttft={r.ttft_ticks}t/"
                    f"{r.ttft_deadline}t "
                    f"{'MISS' if r.deadline_miss else 'hit'}")
        print(f"req {r.rid}: {len(r.prompt)} prompt -> {r.out_tokens}"
              f"  [{h.status}{tag}]")
    if args.replicas > 1:
        st = sess.stats()
        hits = sum(s["deadline_hits"] for s in st["per_replica"])
        misses = sum(s["deadline_misses"] for s in st["per_replica"])
        print(f"deadline ledger: {hits} hit / {misses} miss")
        print(f"router: assigned={st['assigned']} "
              f"prefix_hits={st['n_prefix_hits']}/{st['n_routed']} "
              f"migrations={st['n_migrations']}")
    else:
        print(f"deadline ledger: {sess.sched.deadline_hits} hit / "
              f"{sess.sched.deadline_misses} miss")
    if args.spec_draft:
        engines = ([r.eng for r in sess.replicas] if args.replicas > 1
                   else [sess])
        for i, eng in enumerate(engines):
            st = eng.spec_stats()
            tag = f"replica {i}: " if args.replicas > 1 else ""
            print(f"spec {tag}{st['spec_rounds']} rounds, "
                  f"{st['draft_accepted']}/{st['draft_tokens']} drafts "
                  f"accepted ({st['acceptance_rate']:.2f}), "
                  f"{st['spec_disabled']} slots degraded")


if __name__ == "__main__":
    main()
