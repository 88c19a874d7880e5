"""Serving throughput: chunked prefill TTFT + paged-KV capacity sharing.

Measures, on host CPU, what the serving rework buys on the hot path
(ROADMAP north-star: as fast as the hardware allows under heavy traffic):

  * TTFT — time from admission to the first sampled token.  The seed path
    paid one jitted decode dispatch per prompt token; the chunked path is
    ONE ``mode='chunk'`` forward for the whole padded prompt (and one for
    the whole admission wave when several slots are free).
  * tokens/s — end-to-end generated-token throughput of a full ``run``.
  * paged KV capacity — at the SAME cache-row budget, the paged engine
    (global page pool + per-slot page tables) admits strictly more
    concurrent mixed-length requests than the contiguous layout, whose
    every slot statically owns ``max_prompt + max_new_tokens`` rows, while
    emitting identical tokens.  Reports admitted concurrency and cache
    capacity utilization (valid rows / rows reserved).
  * continuous batching — staggered arrivals of mixed long+short prompts
    (long ones exceed the chunk budget and fill via RESUMABLE prefill,
    interleaved with decode); TTFT p50/p95 and tokens/s, and the same
    overcommitted pool driven with preemption='swap' vs 'terminate':
    swap sustains strictly higher concurrency with ZERO lost requests.
  * sharded page pool — the same engine with the pool page-striped over
    a 1-shard vs an 8-shard seq mesh (8 host devices, subprocess):
    per-shard pool bytes must be ~1/N of the replicated layout while the
    emitted tokens stay identical, and decode tokens/s is reported for
    both (on host CPU the collectives cost more than the striping saves
    — the win at this scale is MEMORY; the combine exists so a
    production-sized pool never has to replicate onto every chip).
  * tiered page pool — a pinned host tier behind the device pool:
    an oversized context (>= 4x the device pool) completes where the
    single-tier baseline capacity-faults, and a slotted workload under
    eviction pressure reports the fraction of decode ticks stalled on
    host->device page transfers (must stay < 10% at the auto prefetch
    depth) with tokens bit-identical to an all-resident pool.
  * replica router — N engine replicas behind the wire-format router:
    prefix-affinity vs random placement on shared-prompt traffic
    (affinity must win on prefix hit rate AND engine-level shared
    admissions without regressing aggregate tokens per engine tick —
    wall-clock tokens/s is reported alongside), 1- vs N-replica
    aggregate throughput on disjoint traffic, and the cross-replica
    migration count on a deliberately saturated replica (> 0: parked
    work moves to idle capacity instead of queueing).
  * speculative decoding — draft/verify rounds vs the plain engine:
    the self-draft leg (acceptance 1.0 by construction) gates tokens
    per engine tick at >= 1.5x plain decode on EXACT tick counts, and
    a foreign untrained drafter prices acceptance rate and draft
    dispatch overhead — with every leg's emitted streams asserted
    bit-identical to the baseline.
  * mixed-priority sessions — staggered arrivals through the session API
    (``submit()``/``tick()``): deadline-critical short requests landing
    behind a queue of best-effort long prompts.  At the SAME pool
    budget, priority-aware admission must beat FIFO (identical requests,
    priorities zeroed) on high-priority TTFT p95 (deterministic engine
    ticks) and on TTFT-deadline hit rate.

The sharded section also drives the pool with ``use_pallas_decode`` on
and off (f32 pool so the contract is BITWISE): emitted tokens must be
identical across all four (shards x decode-path) runs, and decode
tokens/s is reported for each.  Off-TPU the Pallas path runs through
the interpreter, which emulates the per-page grid programs (block
copies included) — the host-CPU comparison prices that emulation, not
the compiled kernel; the fusion's DMA/HBM saving prices in on TPU.

Swept over batch sizes and weight configs (bf16 vs packed w4), CSV via
benchmarks/common.emit:  serve/<cfg>,<us>,<derived-metrics>.
``--smoke`` runs a tiny configuration end-to-end (CI: make bench-smoke)
and asserts every section still completes, so this file cannot rot.

Headline numbers (TTFT p50/p95, concurrency at the fixed pool, decode
tokens/s per shard count and decode path) are also persisted as JSON to
``BENCH_serve.json`` at the repo root (override with the
``BENCH_SERVE_JSON`` env var; CI uploads it as an artifact).
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core.quant import QuantConfig
from repro.models import ArchConfig, init_params
from repro.models.model import quantize_for_serving
from repro.serve import Request, ServeConfig, ServingEngine
from repro.train.step import make_chunked_prefill_step, make_decode_step

MAX_PROMPT = 64
MAX_NEW = 8

# headline metrics accumulated by the sections below and persisted as
# BENCH_serve.json by run() — machine-readable counterpart of the CSV.
_BENCH: dict = {}


def _cfg(quant=None) -> ArchConfig:
    return ArchConfig(name="thr", family="dense", n_layers=2, d_model=128,
                      n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=256,
                      decode_margin=32, quant=quant)


def _prompts(n: int, length: int, vocab: int):
    key = jax.random.PRNGKey(7)
    toks = jax.random.randint(key, (n, length), 0, vocab)
    return [[int(t) for t in row] for row in toks]


def _per_token_prefill_us(eng: ServingEngine, prompt, iters: int = 3):
    """TTFT of the seed strategy: prompt fed one token per decode tick."""
    decode = jax.jit(make_decode_step(eng.cfg))
    bsz = eng.sc.max_batch

    def once():
        cache = eng.cache
        logits = None
        for t, tok in enumerate(prompt):
            pos_v = jnp.full((bsz,), -1, jnp.int32).at[0].set(t)
            tok_b = jnp.zeros((bsz, 1), jnp.int32).at[0, 0].set(tok)
            logits, cache = decode(eng.params, cache, tok_b, pos_v)
        return jnp.argmax(logits[0])

    jax.block_until_ready(once())               # compile
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(once())
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2] * 1e6


def _chunked_prefill_us(eng: ServingEngine, prompt, iters: int = 3):
    """TTFT of the chunked strategy: one prefill dispatch."""
    bsz, sp = eng.sc.max_batch, eng.sc.max_prompt
    toks = jnp.zeros((bsz, sp), jnp.int32
                     ).at[0, :len(prompt)].set(jnp.asarray(prompt))
    lens = jnp.zeros((bsz,), jnp.int32).at[0].set(len(prompt))
    # non-donating jit so the engine cache can be reused across iters.
    prefill = jax.jit(make_chunked_prefill_step(eng.cfg))

    def once():
        logits, _ = prefill(eng.params, eng.cache, toks, lens)
        return jnp.argmax(logits[0])

    jax.block_until_ready(once())               # compile
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(once())
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2] * 1e6


def _mixed_prompts(vocab: int):
    """Mixed short/long prompts: the workload where static contiguous
    windows waste most of their reservation."""
    lengths = [4, 6, 8, 12, 4, 8, 16, 6, 32, 4, 8, 48]
    key = jax.random.PRNGKey(11)
    out = []
    for i, n in enumerate(lengths):
        key, k = jax.random.split(key)
        out.append([int(t) for t in jax.random.randint(k, (n,), 0, vocab)])
    return out


def _paged_capacity(cfg, params):
    """Same pool budget, paged vs contiguous: concurrency + utilization.

    Pool budget: 128 cache rows = 8 pages x 16 rows.  The contiguous
    layout spends ``max_prompt + max_new_tokens`` = 72 rows per slot, so
    128 rows fund exactly ONE slot; the paged engine funds up to 8 slots
    whose pages are claimed at admission, grown on demand during decode,
    and freed on completion.  Both engines must emit identical tokens."""
    page_size, num_pages = 16, 8
    pool_rows = page_size * num_pages
    cap_per_slot = MAX_PROMPT + MAX_NEW                   # 72 rows
    contig_slots = max(1, pool_rows // cap_per_slot)      # 1 slot
    prompts = _mixed_prompts(cfg.vocab_size)

    eng_c = ServingEngine(cfg, params, ServeConfig(
        max_batch=contig_slots, max_prompt=MAX_PROMPT,
        max_new_tokens=MAX_NEW, paged=False))
    out_c = eng_c.run([Request(i, list(p)) for i, p in enumerate(prompts)])
    toks_c = {r.rid: r.out_tokens for r in out_c}

    eng_p = ServingEngine(cfg, params, ServeConfig(
        max_batch=num_pages, max_prompt=MAX_PROMPT, max_new_tokens=MAX_NEW,
        paged=True, page_size=page_size, num_pages=num_pages))
    pending = [Request(100 + i, list(p)) for i, p in enumerate(prompts)]
    rid0 = 100
    used_rows = reserved_rows = ticks = 0
    t0 = time.perf_counter()
    while pending or any(s is not None for s in eng_p.slots):
        eng_p.admit_many(pending)
        used_rows += sum(int(eng_p.positions[i])
                         for i, s in enumerate(eng_p.slots) if s is not None)
        reserved_rows += eng_p.pages_in_use() * page_size
        ticks += 1
        eng_p.step()
    dt = time.perf_counter() - t0
    toks_p = {r.rid - rid0: r.out_tokens for r in eng_p.completed}

    assert toks_p == toks_c, "paged tokens diverge from contiguous"
    assert eng_p.peak_active > contig_slots, \
        "paged engine admitted no more than the contiguous budget"
    util = used_rows / max(reserved_rows, 1)
    _BENCH["concurrency"] = {
        "pool_rows": pool_rows,
        "contiguous_slots": contig_slots,
        "paged_peak": eng_p.peak_active,
        "utilization_pct": round(util * 100, 1),
    }
    emit("serve/paged_concurrency", eng_p.peak_active,
         f"pool_rows={pool_rows};contiguous_slots={contig_slots};"
         f"paged_peak_concurrency={eng_p.peak_active};"
         f"requests={len(prompts)};identical_tokens=1")
    emit("serve/paged_utilization", util * 100,
         f"valid_rows_over_reserved_pct={util * 100:.0f};"
         f"ticks={ticks};run_us={dt * 1e6:.0f}")


def _staggered_prompts(vocab: int, n: int, chunk: int):
    """Mixed workload for the continuous-batching section: half short
    prompts, half LONG ones that exceed the prefill chunk budget and can
    only be served via resumable chunked prefill."""
    key = jax.random.PRNGKey(23)
    out = []
    for i in range(n):
        key, k = jax.random.split(key)
        ln = 4 + (i % 3) * 2 if i % 2 == 0 else chunk + 8 + (i % 3) * chunk
        out.append([int(t) for t in jax.random.randint(k, (ln,), 0, vocab)])
    return out


def _drive_staggered(cfg, params, sc, prompts, per_tick: int = 2):
    """Tick the engine by hand, injecting ``per_tick`` arrivals per tick;
    returns (per-request TTFT list, stats dict)."""
    eng = ServingEngine(cfg, params, sc)
    eng.warmup()        # TTFT must measure serving, not XLA compilation
    reqs = [Request(i, list(p)) for i, p in enumerate(prompts)]
    pending, made = [], 0
    t_arrive, t_first = {}, {}
    ticks = 0
    t0 = time.perf_counter()
    while made < len(reqs) or pending or eng.sched.active() \
            or eng.sched.swapped:
        now = time.perf_counter()
        while made < len(reqs) and made < (ticks + 1) * per_tick:
            pending.append(reqs[made])
            t_arrive[made] = now
            made += 1
        eng.admit_many(pending)
        eng.step()
        now = time.perf_counter()
        for r in reqs:
            if r.rid not in t_first and r.out_tokens:
                t_first[r.rid] = now
        ticks += 1
    dt = time.perf_counter() - t0
    done = [r for r in reqs if r.done and not r.failed]
    ttft = sorted(t_first[r.rid] - t_arrive[r.rid] for r in done
                  if r.rid in t_first)
    return ttft, {
        "eng": eng, "ticks": ticks, "run_s": dt,
        "completed": len(done),
        "failed": sum(r.failed for r in reqs),
        "gen_tokens": sum(len(r.out_tokens) for r in done),
        "sustained": eng.active_ticks / max(ticks, 1),
    }


def _continuous_batching(cfg, params, n_requests: int = 12):
    """Staggered arrivals against a deliberately OVERCOMMITTED pool: the
    worst-case growth of the admitted set exceeds the pool, so decode
    must either preempt (swap) or kill requests (terminate).  Asserts
    swap loses nothing and sustains strictly more concurrency."""
    chunk, page_size, max_new = 16, 8, 16
    prompts = _staggered_prompts(cfg.vocab_size, n_requests, chunk)
    longest = max(len(p) for p in prompts)
    max_seq = longest + max_new
    # pool: enough to ADMIT aggressively under overcommit, far short of
    # everyone's worst case.
    num_pages = max(2 * (-(-max_seq // page_size)), 3 * n_requests // 2)
    base = dict(max_batch=6, max_prompt=chunk, max_new_tokens=max_new,
                max_seq=max_seq, page_size=page_size, num_pages=num_pages,
                reserve_decode_pages=False)

    ttft, swap = _drive_staggered(
        cfg, params, ServeConfig(preemption="swap", **base), prompts)
    _, term = _drive_staggered(
        cfg, params, ServeConfig(preemption="terminate",
                                 strict_iotlb=False, **base), prompts)

    assert swap["failed"] == 0, "preemption must lose no request"
    assert swap["completed"] == len(prompts)
    assert term["failed"] > 0, "termination at this pool should be lossy"
    assert swap["sustained"] > term["sustained"], \
        "swap must sustain strictly higher concurrency than termination"
    eng = swap["eng"]
    p50 = ttft[len(ttft) // 2] * 1e6
    p95 = ttft[min(len(ttft) - 1, int(len(ttft) * 0.95))] * 1e6
    _BENCH["ttft"] = {"p50_us": round(p50), "p95_us": round(p95),
                      "requests": len(prompts)}
    emit("serve/cb_ttft", p50,
         f"ttft_p50_us={p50:.0f};ttft_p95_us={p95:.0f};"
         f"requests={len(prompts)};long_prompts_gt_chunk="
         f"{sum(len(p) > chunk for p in prompts)}")
    emit("serve/cb_preemption", swap["sustained"],
         f"sustained_concurrency_swap={swap['sustained']:.2f};"
         f"sustained_concurrency_terminate={term['sustained']:.2f};"
         f"completed_swap={swap['completed']};"
         f"completed_terminate={term['completed']};"
         f"failed_terminate={term['failed']};"
         f"preemptions={eng.n_preemptions};swap_ins={eng.n_swap_ins};"
         f"tok_per_s={swap['gen_tokens'] / swap['run_s']:.1f}")


def _priority_workload(vocab: int, n_low: int, n_high: int, chunk: int):
    """Best-effort LONG prompts (several prefill chunks each) plus
    deadline-critical SHORT ones — the paper's navigation-vs-bulk mix."""
    key = jax.random.PRNGKey(31)
    lows, highs = [], []
    for i in range(n_low):
        key, k = jax.random.split(key)
        ln = 2 * chunk + 4 + (i % 3) * 4
        lows.append([int(t) for t in jax.random.randint(k, (ln,), 0, vocab)])
    for _ in range(n_high):
        key, k = jax.random.split(key)
        highs.append([int(t) for t in jax.random.randint(k, (4,), 0, vocab)])
    return lows, highs


def _drive_sessions(cfg, params, sc, plan):
    """Session-API driver: ``plan`` is [(arrival_tick, Request)], sorted.
    Submissions land when the engine clock reaches their arrival tick;
    the caller only ever calls submit() and tick()."""
    eng = ServingEngine(cfg, params, sc)
    eng.warmup()
    todo = list(plan)
    t0 = time.perf_counter()
    while todo or eng.sched.has_work():
        while todo and todo[0][0] <= eng.tick_no:
            eng.submit(todo.pop(0)[1])
        eng.tick()
    return eng, time.perf_counter() - t0


def _mixed_priority(cfg, params, n_low: int = 8, n_high: int = 4):
    """Priority-aware vs FIFO at the same pool budget.  High-priority
    short requests arrive AFTER a queue of long best-effort prompts has
    formed; awareness lets them jump the pending queue (never the
    resident slots — admission only fills free slots, so the comparison
    is pure policy).  TTFT is measured in engine ticks: deterministic,
    machine-independent."""
    chunk, page_size, max_new, deadline = 8, 8, 12, 20
    lows, highs = _priority_workload(cfg.vocab_size, n_low, n_high, chunk)
    max_seq = max(len(p) for p in lows + highs) + max_new
    base = dict(max_batch=2, max_prompt=chunk, max_new_tokens=max_new,
                max_seq=max_seq, page_size=page_size)

    def plan(aware):
        entries = [(i, Request(i, list(p))) for i, p in enumerate(lows)]
        entries += [(2 + 2 * j, Request(100 + j, list(p),
                                        priority=2 if aware else 0,
                                        ttft_deadline=deadline))
                    for j, p in enumerate(highs)]
        return sorted(entries, key=lambda e: e[0])   # stable: lows first

    def drive(aware):
        eng, dt = _drive_sessions(cfg, params, ServeConfig(**base),
                                  plan(aware))
        hi = [r for r in eng.completed if r.rid >= 100
              and r.ttft_ticks is not None]
        assert len(hi) == n_high, "every high-priority request completes"
        ttft = sorted(r.ttft_ticks for r in hi)
        return {
            "dt": dt,
            "p50": ttft[len(ttft) // 2],
            "p95": ttft[min(len(ttft) - 1, int(len(ttft) * 0.95))],
            "hits": eng.sched.deadline_hits,
            "misses": eng.sched.deadline_misses,
        }

    aw, ff = drive(True), drive(False)
    assert aw["p95"] < ff["p95"], \
        "priority-aware scheduling must beat FIFO on high-prio TTFT p95"
    assert aw["hits"] > ff["hits"], \
        "priority-aware scheduling must beat FIFO on deadline hit-rate"
    rate = lambda d: d["hits"] / max(d["hits"] + d["misses"], 1)   # noqa: E731
    emit("serve/priority_ttft", aw["p95"],
         f"hi_ttft_p50_ticks_aware={aw['p50']};"
         f"hi_ttft_p95_ticks_aware={aw['p95']};"
         f"hi_ttft_p50_ticks_fifo={ff['p50']};"
         f"hi_ttft_p95_ticks_fifo={ff['p95']};"
         f"low={n_low};high={n_high};run_us={aw['dt'] * 1e6:.0f}")
    emit("serve/priority_deadlines", rate(aw) * 100,
         f"hit_rate_aware_pct={rate(aw) * 100:.0f};"
         f"hit_rate_fifo_pct={rate(ff) * 100:.0f};"
         f"deadline_ticks={deadline}")


_SHARDED_POOL_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time
import jax, jax.numpy as jnp
from repro.models import ArchConfig, init_params
from repro.serve import Request, ServeConfig, ServingEngine
from repro.distributed.sharding import use_rules
from repro.launch.mesh import make_test_mesh

N_REQ = {n_req}
# f32 pool: the lax-vs-Pallas decode comparison below asserts BITWISE
# identical tokens, a contract the kernel only makes for f32 (bf16 GEMM
# strategies are shape-dependent in XLA).
cfg = ArchConfig(name="thr", family="dense", n_layers=2, d_model=128,
                 n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=256,
                 decode_margin=32, dtype=jnp.float32)
params = init_params(cfg, jax.random.PRNGKey(0))
keys = jax.random.split(jax.random.PRNGKey(7), N_REQ)
prompts = [[int(t) for t in jax.random.randint(k, (6,), 0, cfg.vocab_size)]
           for k in keys]
got = {{}}
for shards, shape in ((1, (8, 1)), (8, (1, 8))):
    for mode in ("lax", "pallas"):
        best = None
        for _ in range(2):              # best-of-2: CPU timing is noisy
            mesh = make_test_mesh(shape, ("data", "model"))
            with use_rules(mesh, "fsdp_sp"):
                eng = ServingEngine(cfg, params, ServeConfig(
                    max_batch=4, max_prompt=8, max_new_tokens={max_new},
                    page_size=8, num_pages=32,
                    use_pallas_decode=(mode == "pallas")))
                eng.warmup()
                t0 = time.perf_counter()
                out = eng.run([Request(i, list(p))
                               for i, p in enumerate(prompts)])
                dt = time.perf_counter() - t0
            toks_map = {{r.rid: r.out_tokens for r in out}}
            assert got.setdefault((shards, mode), toks_map) == toks_map
            best = dt if best is None else min(best, dt)
        toks = sum(len(t) for t in got[shards, mode].values())
        print(f"SHARDS={{shards}} MODE={{mode}} "
              f"POOL_BYTES_PER_SHARD={{eng.pool_bytes_per_shard()}} "
              f"TOK_PER_S={{toks / best:.1f}} GEN={{toks}}")
ref = got[1, "lax"]
for key, toks in got.items():
    assert toks == ref, ("tokens diverged from 1-shard lax", key)
"""


def _sharded_pool(smoke: bool):
    """Page-striped pool at 1 vs 8 shards, lax vs fused-Pallas decode.
    Runs in a subprocess: the striping needs an 8-device host platform
    and THIS process's device count locked at first jax init.  Asserts
    all four runs emit identical tokens and the 1/N per-shard memory
    split; reports decode tokens/s for every (shards, mode) cell."""
    import subprocess
    code = _SHARDED_POOL_SCRIPT.format(n_req=4 if smoke else 12,
                                       max_new=8 if smoke else 32)
    # the child runs on 8 host CPU devices: left to its default backend it
    # would try to take an accelerator this process already holds.
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=1800,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-3000:]
    rows = {}
    for line in r.stdout.splitlines():
        if line.startswith("SHARDS="):
            kv = dict(part.split("=") for part in line.split())
            rows[int(kv["SHARDS"]), kv["MODE"]] = kv
    assert sorted(rows) == [(1, "lax"), (1, "pallas"),
                            (8, "lax"), (8, "pallas")], r.stdout
    b1 = int(rows[1, "lax"]["POOL_BYTES_PER_SHARD"])
    b8 = int(rows[8, "lax"]["POOL_BYTES_PER_SHARD"])
    assert b8 * 8 == b1, "per-shard pool memory must be 1/8 at 8 shards"
    _BENCH["decode_tok_per_s"] = {
        f"{shards}shard": {mode: float(rows[shards, mode]["TOK_PER_S"])
                           for mode in ("lax", "pallas")}
        for shards in (1, 8)}
    emit("serve/sharded_pool_bytes", b8,
         f"per_shard_bytes_1shard={b1};per_shard_bytes_8shard={b8};"
         f"ratio={b1 // b8}x;identical_tokens=1")
    for shards in (1, 8):
        emit(f"serve/sharded_pool_decode_{shards}shard",
             float(rows[shards, "pallas"]["TOK_PER_S"]),
             f"tok_per_s_lax={rows[shards, 'lax']['TOK_PER_S']};"
             f"tok_per_s_pallas={rows[shards, 'pallas']['TOK_PER_S']};"
             f"gen_tokens={rows[shards, 'pallas']['GEN']}")


def _quantized_pool(smoke: bool):
    """Page storage formats at a fixed pool BYTE budget.

    The fp reference pool is the capacity section's 128 rows (8 pages x
    16); quantized engines get however many pages fit in the SAME bytes
    (engine._page_nbytes prices packed rows + their f32 row scales), so
    the comparison is memory-honest: int8 rows cost ~1/3.8 of f32 rows,
    int4 ~1/7 — int4 must admit >= 4x the fp resident concurrency on a
    one-page-per-request workload.  f32 model so the byte ratios (and
    the fp logits the error budget is measured against) are exact.

    A second, ample-pool pass records first-token logits per format: the
    first emitted token sees an identical prompt history in every
    format, so its max |logit error| vs fp is the format's approximation
    cost, reported (with argmax agreement) in BENCH_serve.json."""
    page_size, fp_pages = 16, 8
    max_new = 4 if smoke else 8
    cfg = ArchConfig(name="thrq", family="dense", n_layers=2, d_model=128,
                     n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=256,
                     decode_margin=32, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    n_req = 32
    prompts = _prompts(n_req, 8, cfg.vocab_size)   # 1 page per request

    def engine(kvf, num_pages, **kw):
        return ServingEngine(cfg, params, ServeConfig(
            max_batch=n_req, max_prompt=16, max_new_tokens=max_new,
            page_size=page_size, num_pages=num_pages, kv_format=kvf, **kw))

    page_bytes = {kvf: engine(kvf, fp_pages)._page_nbytes
                  for kvf in ("fp", "int8", "int4")}
    budget = fp_pages * page_bytes["fp"]

    formats = {}
    for kvf in ("fp", "int8", "int4"):
        n_pages = budget // page_bytes[kvf]
        eng = engine(kvf, n_pages)
        t0 = time.perf_counter()
        out = eng.run([Request(i, list(p)) for i, p in enumerate(prompts)])
        dt = time.perf_counter() - t0
        assert all(not r.failed and len(r.out_tokens) == max_new
                   for r in out)
        assert eng.pool_bytes_per_shard() <= budget
        gen = sum(len(r.out_tokens) for r in out)
        formats[kvf] = {
            "num_pages": int(n_pages),
            "page_bytes": int(page_bytes[kvf]),
            "bytes_per_request": int(page_bytes[kvf]),   # 1-page requests
            "peak_concurrency": eng.peak_active,
            "tok_per_s": round(gen / dt, 1),
        }
    ratio = formats["int4"]["peak_concurrency"] / \
        formats["fp"]["peak_concurrency"]
    assert ratio >= 4, \
        f"int4 pool must hold >= 4x the fp concurrency, got {ratio:.2f}x"

    # quality: ample pool, identical prompt history per first token.
    logs = {}
    for kvf in ("fp", "int8", "int4"):
        eng = engine(kvf, n_req, record_logits=True)
        out = eng.run([Request(i, list(p)) for i, p in enumerate(prompts)])
        logs[kvf] = {r.rid: r.logits[0] for r in out}
    quality = {}
    for kvf in ("int8", "int4"):
        err = max(float(np.max(np.abs(logs[kvf][i] - logs["fp"][i])))
                  for i in range(n_req))
        agree = sum(int(np.argmax(logs[kvf][i]) == np.argmax(logs["fp"][i]))
                    for i in range(n_req))
        quality[kvf] = {"first_token_max_logit_err": round(err, 4),
                        "first_token_argmax_agree_pct":
                            round(100 * agree / n_req, 1)}
    _BENCH["kv_quant"] = {"pool_budget_bytes": int(budget),
                          "formats": formats, "quality": quality}
    emit("serve/kv_quant_concurrency", formats["int4"]["peak_concurrency"],
         f"pool_budget_bytes={budget};"
         f"fp_peak={formats['fp']['peak_concurrency']};"
         f"int8_peak={formats['int8']['peak_concurrency']};"
         f"int4_peak={formats['int4']['peak_concurrency']};"
         f"bytes_per_request_fp={formats['fp']['bytes_per_request']};"
         f"bytes_per_request_int8={formats['int8']['bytes_per_request']};"
         f"bytes_per_request_int4={formats['int4']['bytes_per_request']}")
    emit("serve/kv_quant_error",
         quality["int8"]["first_token_max_logit_err"],
         f"int8_max_err={quality['int8']['first_token_max_logit_err']};"
         f"int4_max_err={quality['int4']['first_token_max_logit_err']};"
         f"int8_argmax_agree_pct="
         f"{quality['int8']['first_token_argmax_agree_pct']};"
         f"int4_argmax_agree_pct="
         f"{quality['int4']['first_token_argmax_agree_pct']}")


def _tiered(smoke: bool):
    """Two-tiered page pool: contexts beyond the device pool + stalls.

    Headline contract (ROADMAP): with a pinned host tier behind the
    device pool, (a) a request whose context is >= 4x the DEVICE pool
    completes — the single-tier baseline capacity-rejects it — and
    (b) on a slotted workload under enough pressure to force page
    evict/prefetch cycles, the fraction of decode ticks stalled waiting
    on a host->device transfer stays < 10% at the AUTO prefetch depth
    (restores issued ahead of the decode window overlap compute), while
    the emitted tokens stay bit-identical to an all-resident engine."""
    cfg = _cfg(None)
    params = init_params(cfg, jax.random.PRNGKey(0))
    page_size, num_pages, max_new = 8, 8, 8 if smoke else 16
    pool_rows = page_size * num_pages

    # (a) oversized context: >= 4x the device pool, host-tier resident.
    span = 4 * pool_rows
    big = _prompts(1, span - max_new, cfg.vocab_size)[0]
    ov_base = dict(max_batch=2, max_prompt=16, max_new_tokens=max_new,
                   page_size=page_size, num_pages=num_pages, max_seq=48)
    eng_b = ServingEngine(cfg, params, ServeConfig(
        strict_iotlb=False, **ov_base))
    [rej] = eng_b.run([Request(0, list(big))])
    assert rej.failed and not rej.out_tokens, \
        "baseline must capacity-reject the oversized context"
    eng_o = ServingEngine(cfg, params, ServeConfig(
        host_pool_pages=span // page_size, **ov_base))
    t0 = time.perf_counter()
    [done] = eng_o.run([Request(0, list(big))])
    dt_ov = time.perf_counter() - t0
    assert done.done and not done.failed and \
        len(done.out_tokens) == max_new, "oversized context must complete"

    # (b) slotted pressure: every admitted window only fits by evicting
    # colder pages to the host tier; auto-depth prefetch hides restores.
    n_req = 6 if smoke else 12
    key = jax.random.PRNGKey(41)
    prompts = []
    for i in range(n_req):
        key, k = jax.random.split(key)
        ln = 18 + (i % 4) * 6
        prompts.append([int(t) for t in
                        jax.random.randint(k, (ln,), 0, cfg.vocab_size)])
    sl_base = dict(max_batch=4, max_prompt=16, max_new_tokens=max_new,
                   page_size=page_size, max_seq=48)
    eng_r = ServingEngine(cfg, params, ServeConfig(
        num_pages=64, **sl_base))
    ref = {r.rid: r.out_tokens
           for r in eng_r.run([Request(i, list(p))
                               for i, p in enumerate(prompts)])}
    eng_t = ServingEngine(cfg, params, ServeConfig(
        num_pages=num_pages, host_pool_pages=64,
        prefetch_depth="auto", **sl_base))
    eng_t.warmup()
    t0 = time.perf_counter()
    out = eng_t.run([Request(i, list(p)) for i, p in enumerate(prompts)])
    dt_sl = time.perf_counter() - t0
    toks = {r.rid: r.out_tokens for r in out}
    assert toks == ref, "tiered tokens diverge from the all-resident pool"
    st = eng_t.tier_stats()
    assert st["n_evictions"] > 0, \
        "pressure workload must exercise page eviction"
    assert st["stall_tick_frac"] < 0.10, \
        f"decode ticks stalled on transfers must stay < 10% at auto " \
        f"prefetch depth, got {st['stall_tick_frac']:.1%}"
    gen = sum(len(t) for t in toks.values())
    _BENCH["tiered"] = {
        "device_pool_rows": pool_rows,
        "context_rows": span,
        "context_over_pool": round(span / pool_rows, 1),
        "oversized_completed": int(done.done),
        "baseline_rejected": int(rej.failed),
        "stall_tick_frac": round(st["stall_tick_frac"], 4),
        "prefetch_hit_rate": round(st["prefetch_hit_rate"], 3),
        "prefetch_depth_auto": eng_t._prefetch_depth(),
        "n_evictions": st["n_evictions"],
        "n_restores": st["n_restores"],
        "n_spills": st["n_spills"],
        "tok_per_s": round(gen / dt_sl, 1),
    }
    emit("serve/tiered_context", span / pool_rows,
         f"context_rows={span};device_pool_rows={pool_rows};"
         f"oversized_completed=1;baseline_rejected=1;"
         f"run_us={dt_ov * 1e6:.0f}")
    emit("serve/tiered_stall", st["stall_tick_frac"] * 100,
         f"stall_tick_frac_pct={st['stall_tick_frac'] * 100:.1f};"
         f"prefetch_hit_rate={st['prefetch_hit_rate']:.2f};"
         f"prefetch_depth={eng_t._prefetch_depth()};"
         f"evictions={st['n_evictions']};restores={st['n_restores']};"
         f"tok_per_s={gen / dt_sl:.1f};identical_tokens=1")


def _router_prompts(vocab: int, groups: int, per_group: int, page: int):
    """Shared-prompt traffic: ``groups`` families, each sharing a
    2-page prompt prefix — the workload where routing placement decides
    whether per-replica COW prefix sharing can fire at all."""
    key = jax.random.PRNGKey(53)
    out = []
    for g in range(groups):
        key, kp = jax.random.split(key)
        prefix = [int(t) for t in
                  jax.random.randint(kp, (2 * page,), 0, vocab)]
        for m in range(per_group):
            key, kt = jax.random.split(key)
            tail = [int(t) for t in
                    jax.random.randint(kt, (2 + m,), 0, vocab)]
            out.append(prefix + tail)
    return out


def _router(smoke: bool):
    """Replica router: prefix-affinity vs random placement, plus the
    aggregate-throughput and migration headlines.

    Placement is the whole game for cross-request KV reuse in a fleet:
    COW prefix sharing is per-replica, so random routing splits a prompt
    family across replicas and forfeits sharing that affinity keeps.
    Asserts affinity strictly beats random on prefix hit rate AND on
    engine-level shared admissions for the same traffic, with aggregate
    throughput not regressing in DETERMINISTIC engine ticks (wall-clock
    tokens/s is reported, not gated — CPU timing).  Also reports
    1-replica vs N-replica aggregate tokens/s on disjoint traffic and,
    on a deliberately saturated replica, the cross-replica migration
    count (must be > 0: parked work moves to idle capacity)."""
    from repro.serve import Router, RouterConfig

    cfg = _cfg(None)
    params = init_params(cfg, jax.random.PRNGKey(0))
    replicas = 2 if smoke else 4
    page, max_new = 8, 4 if smoke else 8
    per_group = 2 if smoke else 4
    prompts = _router_prompts(cfg.vocab_size, replicas, per_group, page)
    groups = [prompts[g * per_group:(g + 1) * per_group]
              for g in range(replicas)]

    def sc():
        return ServeConfig(max_batch=4, max_prompt=32,
                           max_new_tokens=max_new, page_size=page)

    def drive(routing):
        out, best = None, None
        for _ in range(2):              # best-of-2: CPU timing is noisy
            router = Router(cfg, params, sc(),
                            RouterConfig(replicas=replicas,
                                         routing=routing))
            router.warmup()
            t0 = time.perf_counter()
            # family leaders first, then the repeats once the leaders'
            # prompts are materialized — so placement decides whether
            # the owning engine can admit the repeats prefix-shared.
            hs = [router.submit(Request(rid=g * 100, prompt=list(grp[0])))
                  for g, grp in enumerate(groups)]
            router.tick()
            router.tick()
            for g, grp in enumerate(groups):
                hs += [router.submit(Request(rid=g * 100 + m,
                                             prompt=list(p)))
                       for m, p in enumerate(grp[1:], start=1)]
            router.drain()
            dt = time.perf_counter() - t0
            assert all(h.status == "done" for h in hs)
            gen = sum(len(h.req.out_tokens) for h in hs)
            # the policy metrics are deterministic across reps; only
            # the wall clock is noisy.
            metrics = {
                "prefix_hit_rate":
                    round(router.stats()["prefix_hit_rate"], 3),
                "shared_admissions": sum(ep.eng.n_shared_admissions
                                         for ep in router.replicas),
                "assigned": list(router.assigned),
                "ticks": router.tick_no,
                "tok_per_tick": round(gen / router.tick_no, 3),
            }
            assert out is None or out == metrics
            out = metrics
            best = dt if best is None else min(best, dt)
        out["tok_per_s"] = round(gen / best, 1)
        return out

    aff, rnd = drive("affinity"), drive("random")
    assert aff["prefix_hit_rate"] > rnd["prefix_hit_rate"], \
        "affinity must beat random routing on prefix hit rate"
    assert aff["shared_admissions"] >= max(rnd["shared_admissions"], 1), \
        "affinity placement must enable at least as much COW sharing"
    # throughput guard in DETERMINISTIC engine ticks (wall-clock tok/s
    # is reported but too noisy on a CPU runner to gate on): sharing
    # skips prefill work, so affinity placement can only need fewer
    # aggregate ticks for the same tokens, never more.
    assert aff["tok_per_tick"] >= rnd["tok_per_tick"], \
        "affinity routing must not regress aggregate tokens per tick"

    # aggregate scaling on disjoint traffic: 1 replica vs the fleet.
    flat = _prompts(4 * replicas, 12, cfg.vocab_size)
    scale = {}
    for n in (1, replicas):
        router = Router(cfg, params, sc(),
                        RouterConfig(replicas=n, routing="least_loaded"))
        router.warmup()
        t0 = time.perf_counter()
        done = router.run([Request(rid=i, prompt=list(p))
                           for i, p in enumerate(flat)])
        dt = time.perf_counter() - t0
        gen = sum(len(r.out_tokens) for r in done)
        scale[n] = round(gen / dt, 1)

    # migration: affinity piles one family onto replica 0 with a pool
    # too tight to re-admit its own swap-outs; the router must move the
    # parked snapshot to the idle replica and lose nothing.
    mig_prompts = _router_prompts(cfg.vocab_size, 1, 3, 4)
    router = Router(cfg, params, ServeConfig(
        max_batch=2, max_prompt=32, max_new_tokens=12, page_size=4,
        num_pages=7, reserve_decode_pages=False, preemption="swap"),
        RouterConfig(replicas=2, routing="affinity"))
    done = router.run([Request(rid=i, prompt=list(p))
                       for i, p in enumerate(mig_prompts)])
    assert len(done) == len(mig_prompts) and \
        all(not r.failed for r in done)
    assert router.n_migrations > 0, \
        "the saturated replica must migrate parked work to idle capacity"

    _BENCH["router"] = {
        "replicas": replicas,
        "requests": len(prompts),
        "affinity": aff,
        "random": rnd,
        "tok_per_s_1replica": scale[1],
        "tok_per_s_fleet": scale[replicas],
        "migrations_saturated": router.n_migrations,
    }
    emit("serve/router_affinity", aff["prefix_hit_rate"] * 100,
         f"prefix_hit_rate_affinity={aff['prefix_hit_rate']};"
         f"prefix_hit_rate_random={rnd['prefix_hit_rate']};"
         f"shared_admissions_affinity={aff['shared_admissions']};"
         f"shared_admissions_random={rnd['shared_admissions']};"
         f"tok_per_tick_affinity={aff['tok_per_tick']};"
         f"tok_per_tick_random={rnd['tok_per_tick']};"
         f"tok_per_s_affinity={aff['tok_per_s']};"
         f"tok_per_s_random={rnd['tok_per_s']};"
         f"assigned_affinity={'/'.join(map(str, aff['assigned']))};"
         f"assigned_random={'/'.join(map(str, rnd['assigned']))}")
    emit("serve/router_scale", scale[replicas],
         f"tok_per_s_1replica={scale[1]};"
         f"tok_per_s_{replicas}replica={scale[replicas]};"
         f"replicas={replicas};"
         f"migrations_saturated={router.n_migrations}")


def _spec(smoke: bool):
    """Speculative decoding: draft/verify rounds vs the plain engine,
    with the emitted streams asserted bit-identical in every leg.

    Two legs price the two ends of the drafter-quality spectrum:

      * self-draft — the target drafts for itself, so every proposal
        verifies (acceptance 1.0 by construction).  This is the
        deterministic ceiling, and carries the headline GATE: tokens
        per ENGINE TICK must be >= 1.5x the plain engine's.  Tick
        counts are exact, so the gate holds on any backend — wall
        tokens/s is reported alongside but never gated (on host CPU
        the k+1-row verify dispatch costs more than it saves; the
        wall-clock win needs real accelerator decode latency).
      * foreign draft — an untrained 1-layer drafter: near-zero
        acceptance prices the draft + catch-up dispatch overhead
        honestly while the emitted streams still match the baseline
        byte for byte (rejected rows roll back page-granular through
        ``Allocator.truncate_rows``).

    f32 params so the bit-identity assert is a BITWISE contract, same
    as tests/test_spec.py."""
    cfg = ArchConfig(name="thr_spec", family="dense", n_layers=2,
                     d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                     vocab_size=256, decode_margin=32, dtype=jnp.float32)
    dcfg = ArchConfig(name="thr_spec_draft", family="dense", n_layers=1,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=256, decode_margin=32, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    dparams = init_params(dcfg, jax.random.PRNGKey(1))
    max_new = 8 if smoke else 24
    spec_k = 4
    key = jax.random.PRNGKey(61)
    prompts = []
    for i in range(4 if smoke else 8):
        key, k = jax.random.split(key)
        ln = 5 + (i * 3) % 11
        prompts.append([int(t) for t in
                        jax.random.randint(k, (ln,), 0, cfg.vocab_size)])
    base = dict(max_batch=4, max_prompt=16, max_new_tokens=max_new,
                page_size=4, max_seq=64)

    def drive(sc, draft_model=None):
        eng = ServingEngine(cfg, params, sc, draft_model=draft_model)
        eng.warmup()
        t0 = time.perf_counter()
        out = eng.run([Request(i, list(p)) for i, p in enumerate(prompts)])
        dt = time.perf_counter() - t0
        toks = {r.rid: r.out_tokens for r in out}
        return toks, sum(len(t) for t in toks.values()), eng, dt

    ref, gen, eng_p, dt_p = drive(ServeConfig(**base))
    toks, gen_s, eng_s, dt_s = drive(
        ServeConfig(**base, spec_draft="self", spec_k=spec_k))
    assert toks == ref, "self-draft speculation changed the stream"
    st_s = eng_s.spec_stats()
    assert st_s["acceptance_rate"] == 1.0, \
        "self-draft must accept every proposal (it IS the target)"
    tpt_plain = gen / eng_p.tick_no
    tpt_spec = gen_s / eng_s.tick_no
    speedup = tpt_spec / tpt_plain
    assert speedup >= 1.5, \
        f"self-draft k={spec_k} must land >= 1.5x tokens per engine " \
        f"tick over plain decode, got {speedup:.2f}x " \
        f"({eng_p.tick_no} -> {eng_s.tick_no} ticks)"

    toks, _, eng_f, dt_f = drive(
        ServeConfig(**base, spec_draft="self", spec_k=spec_k),
        draft_model=(dcfg, dparams))
    assert toks == ref, "rejected foreign drafts must roll back cleanly"
    st_f = eng_f.spec_stats()
    # extra drafter forwards (propose + catch-up) per emitted token: the
    # price of speculating, paid whether or not the drafts land.
    overhead_f = (st_f["draft_dispatches"]
                  + st_f["catchup_dispatches"]) / gen
    _BENCH["spec"] = {
        "spec_k": spec_k,
        "gen_tokens": gen,
        "ticks_plain": eng_p.tick_no,
        "ticks_self_draft": eng_s.tick_no,
        "tok_per_tick_plain": round(tpt_plain, 3),
        "tok_per_tick_self_draft": round(tpt_spec, 3),
        "tick_speedup_self_draft": round(speedup, 2),
        "acceptance_self_draft": round(st_s["acceptance_rate"], 3),
        "acceptance_foreign_draft": round(st_f["acceptance_rate"], 3),
        "draft_dispatch_per_token_foreign": round(overhead_f, 3),
        "tok_per_s_plain": round(gen / dt_p, 1),
        "tok_per_s_self_draft": round(gen_s / dt_s, 1),
        "tok_per_s_foreign_draft": round(gen / dt_f, 1),
        "identical_tokens": 1,
    }
    emit("serve/spec_speedup", speedup,
         f"tick_speedup={speedup:.2f}x;spec_k={spec_k};"
         f"ticks_plain={eng_p.tick_no};ticks_spec={eng_s.tick_no};"
         f"acceptance=1.00;tok_per_s_plain={gen / dt_p:.1f};"
         f"tok_per_s_spec={gen_s / dt_s:.1f};identical_tokens=1")
    emit("serve/spec_acceptance", st_f["acceptance_rate"] * 100,
         f"acceptance_foreign={st_f['acceptance_rate']:.2f};"
         f"draft_dispatch_per_token={overhead_f:.2f};"
         f"spec_rounds={st_f['spec_rounds']};"
         f"tok_per_s_foreign={gen / dt_f:.1f};identical_tokens=1")


def run(smoke: bool = False):
    quants = [("bf16", None)] if smoke else \
        [("bf16", None),
         ("w4", QuantConfig(mode="wo", w_bits=4, use_kernel=False))]
    for tag, q in quants:
        cfg = _cfg(q)
        params = init_params(_cfg(None), jax.random.PRNGKey(0))
        if q is not None:
            params, _ = quantize_for_serving(cfg, params)
        if smoke:
            # tiny end-to-end pass of every section: one batch size, one
            # timing iter, few requests — asserts the benchmark still runs.
            eng = ServingEngine(cfg, params, ServeConfig(
                max_batch=1, max_prompt=MAX_PROMPT,
                max_new_tokens=MAX_NEW, paged=False))
            prompt = _prompts(1, 16, cfg.vocab_size)[0]
            us_tok = _per_token_prefill_us(eng, prompt, iters=1)
            us_chk = _chunked_prefill_us(eng, prompt, iters=1)
            emit(f"serve/smoke_ttft_{tag}", us_chk,
                 f"per_token_us={us_tok:.0f};smoke=1")
            _paged_capacity(cfg, params)
            _continuous_batching(cfg, params, n_requests=6)
            _mixed_priority(cfg, params, n_low=4, n_high=2)
            _sharded_pool(smoke=True)
            _quantized_pool(smoke=True)
            _tiered(smoke=True)
            _router(smoke=True)
            _spec(smoke=True)
            continue
        for bsz in (1, 2, 4):
            # contiguous layout here: the TTFT probes time the contiguous
            # step builders against the engine's own cache buffers.
            sc = ServeConfig(max_batch=bsz, max_prompt=MAX_PROMPT,
                             max_new_tokens=MAX_NEW, paged=False)
            prompts = _prompts(2 * bsz, MAX_PROMPT, cfg.vocab_size)

            eng = ServingEngine(cfg, params, sc)
            us_tok = _per_token_prefill_us(eng, prompts[0])
            us_chk = _chunked_prefill_us(eng, prompts[0])
            emit(f"serve/ttft_{tag}_b{bsz}", us_chk,
                 f"per_token_us={us_tok:.0f};chunked_us={us_chk:.0f};"
                 f"speedup={us_tok / us_chk:.1f}x")

            eng = ServingEngine(cfg, params, sc)
            reqs = [Request(i, p) for i, p in enumerate(prompts)]
            t0 = time.perf_counter()
            out = eng.run(reqs)
            dt = time.perf_counter() - t0
            n_tok = sum(len(r.out_tokens) for r in out)
            emit(f"serve/run_{tag}_b{bsz}", dt * 1e6,
                 f"requests={len(out)};gen_tokens={n_tok};"
                 f"tok_per_s={n_tok / dt:.1f}")

        _paged_capacity(cfg, params)
        _continuous_batching(cfg, params)
        _mixed_priority(cfg, params)
    if not smoke:
        _sharded_pool(smoke=False)
        _quantized_pool(smoke=False)
        _tiered(smoke=False)
        _router(smoke=False)
        _spec(smoke=False)
    _write_bench_json(smoke)


def _write_bench_json(smoke: bool) -> None:
    """Persist the headline metrics as BENCH_serve.json (repo root, or
    the BENCH_SERVE_JSON env var) — the artifact CI uploads."""
    # environment fingerprint for bench_diff.py: hostname-independent on
    # purpose (CI runners churn) — backend/version/device-kind is what
    # actually decides whether two artifacts' timings are comparable.
    _BENCH["meta"] = {"smoke": smoke, "backend": jax.default_backend(),
                      "device_count": jax.device_count(),
                      "jax_version": jax.__version__,
                      "device_kind": jax.devices()[0].device_kind}
    if jax.default_backend() != "tpu":
        _BENCH["meta"]["pallas_note"] = (
            "off-TPU the pallas decode numbers run the kernel under the "
            "Pallas interpreter (per-page grid programs emulated, block "
            "copies included); the compiled-kernel comparison — where the "
            "fusion's skipped pages and unmaterialized HBM window pay — "
            "requires a TPU backend")
    path = pathlib.Path(os.environ.get(
        "BENCH_SERVE_JSON",
        pathlib.Path(__file__).resolve().parent.parent / "BENCH_serve.json"))
    path.write_text(json.dumps(_BENCH, indent=2, sort_keys=True) + "\n")
    print(f"wrote {path}")


if __name__ == "__main__":
    run(smoke="--smoke" in sys.argv[1:])
