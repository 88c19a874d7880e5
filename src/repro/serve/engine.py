"""Serving EXECUTOR: session API, jitted dispatch, device data movement.

The serving stack is three layers with one owner per concern:

  * ``scheduler.py`` — POLICY.  The pending queue (priority-ordered
    admission), per-tick chunk budgets (resumable prefill), preemption
    victims, prefix matching, the swap queue, the deadline ledger.  Pure
    host logic over request metadata.
  * ``allocator.py`` — ACCOUNTING.  The physical page pool: free list,
    refcounted per-slot page tables, copy-on-write barriers, growth
    reservations, and the 32-entry LRU IOTLB over the page table.
  * ``engine.py`` (this file) — EXECUTION.  Owns params/cache/device
    buffers and the two jitted steps (chunked prefill + decode); builds
    per-tick staging host-side in numpy (one transfer per tick), applies
    the allocator's page copies, moves swapped state device<->host, and
    samples.  It consults the scheduler for WHAT to run and the allocator
    for WHERE it lives, and never decides either itself.

The client surface is a SESSION: ``submit(req)`` returns a
:class:`RequestHandle` immediately (the request lands on the scheduler's
pending queue — ASYNC admission, no slot is taken yet) and ``tick()``
is the externally-drivable step: drain admissions into free slots, then
advance prefill/decode.  A caller can submit mid-flight, poll a handle's
``status``/``tokens_so_far``, iterate ``stream()`` for tokens as decode
emits them, or block on ``result()``.  ``run()`` is a thin compatibility
shim (submit everything, tick until idle); ``drain()`` finishes all
outstanding work and CLOSES the engine — ``submit()`` afterwards raises.

Continuous batching: every engine tick is (at most) ONE chunked-prefill
wave — covering freshly admitted slots AND slots resuming a prompt
longer than one chunk, via the ``offset`` argument threaded through
``forward`` — followed by ONE decode dispatch for the slots whose prompt
is complete.  Prefill of the next wave therefore overlaps decode of the
current one, and a long prompt never stalls the tick loop.  Where every
cache leaf is a page pool, the wave goes out in dispatches of a few
slots each (about ``PREFILL_ROWS`` rows), sized to the slots that owe
prompt rows rather than to the whole batch.

Preemption (overcommit mode): when decode growth finds the pool empty,
the scheduler picks the youngest resident request, the engine snapshots
its pages and recurrent state to host memory, the allocator releases its
pages, and the request re-enters through the swap queue bit-for-bit —
``reserve_decode_pages=False`` stops being lossy under load.

Prefix sharing: refcounted page tables let a new prompt map a resident
request's physical pages for their common whole-page prompt prefix
(copy-on-write at the first divergent page) and resume prefill at the
first unshared row — admission cost scales with the UNSHARED suffix.

Sharded page pool: constructed inside a ``use_rules`` context whose
table maps the 'pages' logical axis (the default ``fsdp_sp`` stripes it
over 'model'), the engine rounds the pool up to a stripe multiple,
places every pool leaf physically page-striped over the seq mesh axes
(per-shard pool memory ~1/N), hands the allocator one balanced free
list per shard, and the jitted steps take the shard_map flash-decoding
path — logits bit-identical at any shard count.  Keep the rules
context installed while the engine serves: the steps trace on their
first dispatch, and the trace captures the mesh that is current THEN.

Two Shaheen touches survive every layer: weights can be served PACKED
sub-byte (quantize_for_serving) — decode is weight-bandwidth-bound,
exactly where the paper's formats pay — and every cache write is guarded
by the software IOTLB at page granularity, now hardware-faithfully
capped at the silicon block's 32 entries (misses on mapped pages refill
from the page table; misses on unmapped rows fault and contain, §III-C2).
All scheduling is pure addressing: logits stay bit-identical to the
single-pass, never-preempted, unshared path.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
# host spans named ``serve.*``: inert outside a profiler session; inside
# one they land on the host plane beside the device's programs, their int
# args as event stats (a request's spans share its ``rid``).
from jax.profiler import TraceAnnotation as span
from jax.sharding import NamedSharding, PartitionSpec

from repro.checkpoint import checkpoint as checkpointing
from repro.core.iotlb import FaultRecord, Iotlb, IotlbFault, Window
from repro.distributed.sharding import mesh_axes_for
from repro.kernels.paged_flash_decode import use_pallas_decode
from repro.models import init_cache, init_paged_cache
from repro.models.common import is_spec_tree_leaf, verify_greedy_tokens
from repro.models.config import ArchConfig
from repro.models.model import cache_specs, init_params
from repro.serve.allocator import PageAllocator
from repro.serve.config import Request, ServeConfig
from repro.serve.scheduler import Scheduler, SwappedRequest
from repro.serve.spec import SpecDrafter, vet_spec_arch
from repro.train.step import (make_chunked_prefill_resume_step,
                              make_chunked_prefill_step, make_decode_step,
                              make_paged_chunked_prefill_step,
                              make_paged_decode_step, make_paged_verify_step)

_DEFER = "defer"                    # admission verdict: retry after frees
_OVERSIZED = "oversized"            # admission verdict: host-tier context

# conservative host->device fallback bandwidth (bytes/s) when the
# measured model (benchmarks/fig12_offload.measure_offload_bandwidth)
# is unavailable — sized so the auto prefetch depth stays modest.
_FALLBACK_H2D_BPS = 1e9

# rows one chunked-prefill dispatch is sized to.  Below about 240 rows a
# bf16 matmul that streams its weights is bound by the weight read on a
# v5e (197 TFLOP/s / 819 GB/s), so rows up to there cost almost nothing
# and rows past them are paid for; 256 is that figure rounded to the
# MXU's 128.
PREFILL_ROWS = 256


class _ExpertTally:
    """A model with experts: its serving steps' pick counts by step kind
    ([picks routed to the experts held here, all picks, held experts any
    pick reached], over the layers, padding excluded), added up on the
    device, and the dispatches that carried them.  Read with one device
    transfer (``read``)."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.picks: Dict[str, jax.Array] = {}
        self.dispatches = collections.Counter()

    def add(self, kind: str, picks: jax.Array) -> None:
        self.picks[kind] = picks + self.picks[kind] \
            if kind in self.picks else picks
        self.dispatches[kind] += 1

    def read(self) -> dict:
        got = jax.device_get(self.picks)
        return {"expert_picks_held": {k: int(v[0]) for k, v in got.items()},
                "expert_picks_total": {k: int(v[1]) for k, v in got.items()},
                "expert_groups_touched": {k: int(v[2])
                                          for k, v in got.items()},
                "expert_dispatches": dict(self.dispatches)}


@dataclasses.dataclass
class _OversizedRequest:
    """A request whose page demand exceeds the DEVICE pool: its whole
    contiguous cache lives in the host tier (numpy) and streams through
    the device one dispatch at a time — chunked prefill, then one decode
    token per tick — so contexts far larger than the device pool
    complete instead of capacity-faulting.  fp-format only (the
    contiguous layout has no quantized pages); tokens are identical to
    an all-resident engine because contiguous-vs-paged is pure
    addressing."""
    req: Request
    cache: Any                  # contiguous batch-1 cache, numpy leaves
    cap: int                    # page-rounded row capacity
    n_host_pages: int           # host-tier pages reserved for accounting
    prefill_done: int = 0
    pos: int = 0                # next cache write row once prefilled
    last_token: int = 0


class RequestHandle:
    """Client-side view of one submitted request.

    Returned by :meth:`ServingEngine.submit` immediately — before any
    slot or page is taken.  Polling is free (pure host reads); the
    blocking accessors (``stream``/``result``) drive ``engine.tick()``
    themselves, so a single-threaded caller can await one request while
    the engine keeps serving everything else.
    """

    def __init__(self, engine: "ServingEngine", req: Request):
        self._eng = engine
        self.req = req

    @property
    def status(self) -> str:
        """'pending' | 'running' | 'swapped' | 'done' | 'failed'."""
        if self.req.done:
            return "failed" if self.req.failed else "done"
        st = self._eng.sched.state_of(self.req)
        if st == "unknown" and self._eng._is_oversized(self.req):
            return "running"    # streaming from the host tier, slotless
        return st

    @property
    def tokens_so_far(self) -> List[int]:
        """Snapshot of the tokens emitted so far (non-blocking)."""
        return list(self.req.out_tokens)

    def stream(self):
        """Yield tokens incrementally as decode ticks emit them, driving
        ``engine.tick()`` whenever none are buffered; ends at EOS /
        ``max_new_tokens`` / rejection (check ``status`` for 'failed')."""
        sent = 0
        while True:
            while sent < len(self.req.out_tokens):
                yield self.req.out_tokens[sent]
                sent += 1
            if self.req.done:
                return
            self._eng.tick()

    def result(self) -> Request:
        """Drive the engine until this request is terminal; returns the
        finished :class:`Request` (``failed`` marks rejection)."""
        while not self.req.done:
            self._eng.tick()
        return self.req

    def __repr__(self):
        return (f"RequestHandle(rid={self.req.rid}, status={self.status!r}, "
                f"tokens={len(self.req.out_tokens)})")


class _ListQueue:
    """Legacy admission source: a caller-owned FIFO list.  Pops mutate
    the caller's list; a deferred head goes back to position 0."""

    def __init__(self, lst: List[Request]):
        self.lst = lst

    def __bool__(self):
        return bool(self.lst)

    def pop(self) -> Request:
        return self.lst.pop(0)

    def defer(self, req: Request) -> None:
        self.lst.insert(0, req)


class _SchedQueue:
    """Admission source over the scheduler's priority-ordered pending
    queue (the session path: submit()/tick())."""

    def __init__(self, sched: Scheduler):
        self.sched = sched

    def __bool__(self):
        return self.sched.has_pending()

    def pop(self) -> Request:
        return self.sched.pop_pending()

    def defer(self, req: Request) -> None:
        self.sched.defer_pending(req)


class ServingEngine:
    def __init__(self, cfg: ArchConfig, params, serve_cfg: ServeConfig,
                 draft_model: Optional[Tuple[ArchConfig, Any]] = None):
        self.cfg = cfg
        self.params = params
        self.sc = serve_cfg
        self._experts = _ExpertTally() if cfg.n_experts else None
        bsz = serve_cfg.max_batch
        cap = serve_cfg.slot_rows
        if serve_cfg.paged:
            ps = serve_cfg.page_size
            self.pages_per_slot = -(-cap // ps)
            self._slot_span = self.pages_per_slot * ps
            self.num_pages = (serve_cfg.num_pages
                              if serve_cfg.num_pages is not None
                              else bsz * self.pages_per_slot)
            # Pool striping: when an installed rule table maps the
            # 'pages' logical axis onto present mesh axes, the pool is
            # distributed page-aligned over those axes (shard i holds
            # global pages [i*N/S, (i+1)*N/S)) and paged decode/resume
            # run the cross-shard flash-decoding combine.  The page
            # count is rounded UP to a stripe multiple so every shard
            # holds an equal slice; the allocator balances free pages
            # per shard and the jitted steps take the shard_map path.
            mesh, paxes = mesh_axes_for("pages")
            self.pool_shards = 1
            self._pool_sharding = None
            if mesh is not None and paxes:
                self.pool_shards = int(
                    np.prod([mesh.shape[a] for a in paxes]))
                self.num_pages = -(-self.num_pages // self.pool_shards) \
                    * self.pool_shards
                self._pool_sharding = NamedSharding(mesh, PartitionSpec(
                    None, paxes[0] if len(paxes) == 1 else paxes))
            self.cache = init_paged_cache(cfg, bsz, self.num_pages, ps,
                                          kv_format=serve_cfg.kv_format)
            # Fused Pallas decode: the knob is consulted at TRACE time by
            # the striped flash-decoding path, so every jitted dispatch
            # below runs under _kernel_ctx().  Each engine owns its own
            # jax.jit objects, so traces never leak across engines with
            # different knob settings.
            self._use_pallas = bool(serve_cfg.use_pallas_decode)
            self._decode = self._tallied(jax.jit(
                make_paged_decode_step(cfg), donate_argnums=1), "decode")
            self._prefill = self._tallied(jax.jit(
                make_paged_chunked_prefill_step(cfg), donate_argnums=1),
                "prefill")
            self.alloc = PageAllocator(self.num_pages, ps, bsz,
                                       self.pages_per_slot,
                                       num_shards=self.pool_shards,
                                       host_pages=serve_cfg.host_pool_pages)
            # which cache leaves are shared page POOLS (axis 1 = pages)
            # vs per-slot state (axis 1 = batch) — drives swap and COW.
            specs = cache_specs(cfg, bsz, 0, num_pages=self.num_pages,
                                page_size=ps,
                                kv_format=serve_cfg.kv_format)
            flat_specs, _ = jax.tree.flatten(specs,
                                             is_leaf=is_spec_tree_leaf)
            self._pooled = [s.axes[1] == "pages" for s in flat_specs]
            if self._pool_sharding is not None:
                # place each pool leaf physically striped: per-shard
                # pool memory is ~1/N of the replicated layout.
                flat_c, treedef = jax.tree.flatten(self.cache)
                self.cache = jax.tree.unflatten(treedef, [
                    jax.device_put(leaf, self._pool_sharding)
                    if pooled else leaf
                    for leaf, pooled in zip(flat_c, self._pooled)])
            # prefix sharing needs EVERY cache-carrying layer paged:
            # recurrent state cannot be inherited from a sharer.
            self._can_share = serve_cfg.prefix_sharing and \
                all(self._pooled) and len(self._pooled) > 0
        else:
            self.alloc = None
            self._can_share = False
            self._use_pallas = False    # contiguous path has no paged kernel
            self.cache = init_cache(cfg, bsz, cap)
            self._decode = self._tallied(jax.jit(
                make_decode_step(cfg), donate_argnums=1), "decode")
            self._prefill = self._tallied(jax.jit(
                make_chunked_prefill_step(cfg), donate_argnums=1), "prefill")
            self._slot_span = cap
            # whole-slot windows (one per slot), mapped once.
            self._plain_iotlb = Iotlb()
            for i in range(bsz):
                self._plain_iotlb.program(Window(
                    name=f"slot{i}", virt_base=i * cap,
                    size=cap, phys_base=i * cap,
                    readable=True, writable=True))
        # slots one prefill dispatch carries: where every cache leaf is a
        # page pool, a wave's owing slots go out in groups of about
        # PREFILL_ROWS rows, each with its own rows of the page table.
        # Leaves with a batch axis (recurrent state, the contiguous cache)
        # keep the whole batch: compacting them would gather and scatter
        # per-slot state.
        self._prefill_group = bsz
        if serve_cfg.paged and all(self._pooled):
            self._prefill_group = min(
                max(PREFILL_ROWS // serve_cfg.max_prompt, 1), bsz)
        self.sched = Scheduler(bsz, serve_cfg.max_prompt)
        self.positions = np.zeros((bsz,), np.int32)
        self.last_token = np.zeros((bsz,), np.int32)
        self.key = jax.random.PRNGKey(serve_cfg.seed)
        self.completed: List[Request] = []
        self.peak_active = 0        # high-water concurrency (benchmarks)
        self.active_ticks = 0       # sum of active slots over decode ticks
        self.n_preemptions = 0
        self.n_swap_ins = 0
        self.n_cow_copies = 0
        self.n_shared_admissions = 0
        self.n_swap_budget_denials = 0
        self._prefilled_since_step = False   # one prefill wave per tick
        self.tick_no = 0            # the serving clock (deadline ledger)
        self._closed = False        # set by drain(): no further submits
        # host bytes one swapped slot would occupy, for the swap budget:
        # pooled leaves contribute per mapped PAGE, per-slot leaves per
        # slot row (axis 1 is pages resp. batch in both layouts).
        flat_cache, _ = jax.tree.flatten(self.cache)
        if serve_cfg.paged:
            self._page_nbytes = sum(
                leaf.size * leaf.dtype.itemsize // leaf.shape[1]
                for leaf, pooled in zip(flat_cache, self._pooled) if pooled)
            self._slot_state_nbytes = sum(
                leaf.size * leaf.dtype.itemsize // leaf.shape[1]
                for leaf, pooled in zip(flat_cache, self._pooled)
                if not pooled)
        else:
            self._page_nbytes = self._slot_state_nbytes = 0
        # -- two-tier state (inert when host_pool_pages == 0) ----------------
        # The host tier is one pinned numpy buffer per POOLED cache leaf,
        # page-indexed on axis 0: evicted pages park here byte-exact
        # (quantized formats ride free — packed int4 pages are 4x denser
        # per host slot exactly as they are per device page).
        self.tiered = bool(serve_cfg.paged and serve_cfg.host_pool_pages)
        self._host_tier: List[np.ndarray] = []
        if self.tiered:
            self._host_tier = [
                np.zeros((serve_cfg.host_pool_pages, leaf.shape[0])
                         + leaf.shape[2:], leaf.dtype)
                for leaf, pooled in zip(flat_cache, self._pooled) if pooled]
        # (slot, j) -> in-flight restore: the async jax.device_put
        # arrays, issue tick, and whether a stall ever blocked on it.
        self._inflight_data: Dict[Tuple[int, int], dict] = {}
        # host slot -> in-flight EVICTION (device -> host), the mirror
        # of the restore dict: the sliced-out device page arrays and the
        # issue tick.  The allocator already marks the page host-resident;
        # the BYTES land in the pinned buffer when the transfer completes
        # (_land_evictions) or when a reader forces it (_flush_evictions).
        self._evict_pending: Dict[int, dict] = {}
        self._held_slots: set = set()   # blocked mid-restore this tick
        self._tick_ema: Optional[float] = None   # seconds per tick
        self._h2d_bps: Optional[float] = None    # measured lazily
        self._oversized: List[_OversizedRequest] = []
        self._ov_prefill = None     # lazy jits (oversized contexts only)
        self._ov_decode = None
        self._spill_seq = 0         # checkpoint step counter (spill_dir)
        self.n_evictions = 0
        self.evict_stalls = 0       # forced waits on an unfinished D2H copy
        self.n_restores = 0
        self.prefetch_hits = 0      # restores that landed fully overlapped
        self.prefetch_late = 0      # restores a stall tick blocked on
        self.stall_ticks = 0        # decode ticks with every candidate held
        self.decode_ticks = 0       # decode ticks with any candidate at all
        self.n_oversized = 0
        self.n_spills = 0
        # -- speculative decoding (inert when spec_draft is None) ------------
        self._drafter: Optional[SpecDrafter] = None
        self._verify = None
        self.n_spec_rounds = 0      # (slot, tick) verify rounds
        self.n_draft_tokens = 0     # drafted tokens offered to verify
        self.n_draft_accepted = 0   # drafted tokens accepted (emits - rounds)
        self.n_twin_pages = 0       # decode pages twin-shared, not grown
        if serve_cfg.spec_draft is not None:
            vet_spec_arch(cfg, "target")
            if not (self._pooled and all(self._pooled)):
                raise ValueError(
                    "speculative decoding needs every cache leaf paged: "
                    "recurrent state has no page-granular rollback")
            if draft_model is not None:
                dcfg, dparams = draft_model
            elif serve_cfg.spec_draft == "self":
                # self-speculation: the target drafts for itself —
                # acceptance 1.0 by construction (same argmax on the same
                # committed stream), the deterministic throughput leg.
                dcfg, dparams = cfg, params
            else:
                from repro.configs import get_config, reduce_config
                dcfg = reduce_config(get_config(serve_cfg.spec_draft))
                dparams = init_params(dcfg,
                                      jax.random.PRNGKey(serve_cfg.seed))
            self._verify = self._tallied(jax.jit(
                make_paged_verify_step(cfg), donate_argnums=1), "verify")
            self._drafter = SpecDrafter(dcfg, dparams, serve_cfg)

    def _tallied(self, step, kind: str):
        """``step``, a jitted serving step, returning (out, cache) for
        every model: a model with experts adds each dispatch's pick counts
        to the engine's tally, on the device (no read)."""
        tally = self._experts
        if tally is None:
            return step

        def call(*args):
            out, cache, picks = step(*args)
            tally.add(kind, picks)
            return out, cache
        return call

    def stats(self) -> dict:
        """A model with experts: ``expert_picks_held`` and
        ``expert_picks_total`` (picks routed to the experts held here, and
        all picks, padding excluded), ``expert_groups_touched`` (held
        experts that a pick reached, summed over layers and dispatches)
        and ``expert_dispatches``, each by step kind (``decode``,
        ``prefill``) since ``warmup()``: one device read, here and nowhere
        else.  Empty for a model without experts."""
        return {} if self._experts is None else self._experts.read()

    def _kernel_ctx(self):
        """Context for jitted dispatches: installs the fused-Pallas-decode
        knob when ``ServeConfig.use_pallas_decode`` asked for it (the
        striped flash-decoding path reads it at trace time), else a
        no-op.  Interpret-vs-compiled resolves from the backend."""
        if self._use_pallas:
            return use_pallas_decode()
        return contextlib.nullcontext()

    # -- compat views over the split layers ---------------------------------
    @property
    def slots(self) -> List[Optional[Request]]:
        return self.sched.requests()

    @property
    def iotlb(self):
        return self.alloc.iotlb if self.sc.paged else self._plain_iotlb

    @property
    def page_table(self) -> np.ndarray:
        return self.alloc.page_table

    @property
    def _free_pages(self) -> List[int]:
        return self.alloc.free_pages

    @property
    def _growth_due(self) -> np.ndarray:
        return self.alloc.growth_due

    def pages_in_use(self) -> int:
        return self.alloc.pages_in_use()

    def pool_bytes_per_shard(self) -> int:
        """Device bytes of page-pool state ONE pool shard holds (the
        whole pool when unsharded) — the memory the striping divides."""
        flat, _ = jax.tree.flatten(self.cache)
        total = sum(leaf.nbytes for leaf, pooled
                    in zip(flat, self._pooled) if pooled)
        return total // self.pool_shards

    # -- page demand --------------------------------------------------------
    def _max_pages(self, req: Request) -> int:
        """Pages covering every cache row the request could ever write:
        prompt rows [0, len) plus decode writes up to row
        len + max_new_tokens - 2 (the last sampled token is never cached)."""
        last_row = len(req.prompt) - 1
        if self.sc.max_new_tokens >= 2:
            last_row = len(req.prompt) + self.sc.max_new_tokens - 2
        return last_row // self.sc.page_size + 1

    def _claim_count(self, req: Request) -> int:
        """Pages claimed at admission: the prompt's rows, plus the first
        decode write row (row len(prompt)) — the latter only when a decode
        tick will actually happen (max_new_tokens >= 2; the prefill's own
        sampled token is never cached)."""
        last_row = len(req.prompt) - 1
        if self.sc.max_new_tokens >= 2:
            last_row = len(req.prompt)
        return last_row // self.sc.page_size + 1

    def _pages_dev(self) -> jax.Array:
        return jnp.asarray(self.alloc.page_table)

    # -- admission ----------------------------------------------------------
    def _free_slots(self) -> List[int]:
        return self.sched.free_slots()

    def _reject(self, req: Request) -> None:
        if not req.done:            # idempotent: retried rejects are no-ops
            req.failed = True
            req.done = True
            self.sched.note_terminal(req)
            self.completed.append(req)

    def _fault_reject(self, req: Request, kind: str, start: int,
                      length: int) -> None:
        """Record the fault, reject the request, and raise when strict —
        the request always gets a terminal signal BEFORE the raise."""
        self.iotlb.faults.append(FaultRecord(kind, start, length, True))
        self._reject(req)
        if self.sc.strict_iotlb:
            raise IotlbFault(kind, f"request {req.rid}: range "
                             f"[{start}, {start + length}) write=True")

    def _admissible(self, slot: int, req: Request):
        """Vet a request for ``slot``: (verdict, share) where verdict is
        True (admit), False (rejected), or _DEFER (transient page
        exhaustion — retry after completions free pages) or _OVERSIZED
        (host-tier streaming; slot not consumed), and ``share`` is the
        (resident slot, rows) prefix-sharing plan (None, 0) when not
        sharing.  No REQUEST cache region is written either way (the
        tiered engine may evict cold pages of other slots to the host
        tier to clear room — byte-exact, addressing only)."""
        no_share = (None, 0)
        if not req.prompt:
            # an empty prompt has nothing to prefill (and length 0 is the
            # chunk pass's inactive-slot sentinel): reject cleanly.
            self._reject(req)
            return False, no_share
        span = len(req.prompt) + self.sc.max_new_tokens
        if not self.sc.paged:
            ok = self.iotlb.translate(slot * self._slot_span, span,
                                      write=True, strict=False)
            if ok is None:
                self._reject(req)
                if self.sc.strict_iotlb:
                    f = self.iotlb.faults[-1]
                    raise IotlbFault(f.kind, f"request {req.rid}: range "
                                     f"[{f.start}, {f.start + f.length}) "
                                     f"write={f.write}")
                return False, no_share
            return True, no_share
        # paged: the request's full logical extent must fit the slot's
        # row capacity.  The prompt no longer has to fit ONE chunk —
        # resumable prefill spreads it over several ticks.
        base = slot * self._slot_span
        needed = self._claim_count(req)
        demand = (self._max_pages(req) if self.sc.reserve_decode_pages
                  else needed)
        if span > self.sc.slot_rows or demand > self.num_pages:
            # cannot fit a slot / the pool even with everything free.
            # The TIERED engine streams such a context from the host
            # tier instead of faulting (fp only; _vet_oversized); the
            # single-tier engine keeps its capacity fault bit-for-bit.
            verdict = self._vet_oversized(span)
            if verdict is not False:
                return verdict, no_share    # _OVERSIZED or _DEFER
            if span > self.sc.slot_rows:
                self._fault_reject(req, "miss", base, span)
            else:
                self._fault_reject(req, "capacity", base,
                                   demand * self.sc.page_size)
            return False, no_share
        if self.sched.swapped:
            # preempted work drains first: fresh admissions would starve
            # the swap queue of the very pages it is waiting for.
            return _DEFER, no_share
        share = (self.sched.shared_prefix(req.prompt, self.sc.page_size)
                 if self._can_share else no_share)
        if self.tiered and share[0] is not None:
            share = self._clamp_share(share)
        demand -= (share[1] // self.sc.page_size)   # shared pages are free
        if demand > self.alloc.reserved_free():
            if not (self.tiered and self._evict_pages(
                    demand - self.alloc.reserved_free(),
                    protect=self._held_slots)):
                return _DEFER, no_share   # pages come back on completion
        return True, share

    def _vet_oversized(self, span: int):
        """Can this span stream from the host tier?  _OVERSIZED (yes),
        _DEFER (would fit but the tier is transiently busy), or False
        (not servable — fall through to the capacity fault)."""
        if not (self.tiered and self.sc.kv_format == "fp"):
            return False
        n_host = -(-span // self.sc.page_size)
        if n_host > self.alloc.host_pages:
            return False
        if self.alloc.host_avail() < n_host:
            return _DEFER
        return _OVERSIZED

    def _clamp_share(self, share):
        """Prefix sharing refcount-maps the source slot's PHYSICAL device
        pages; rows whose page was evicted to host cannot be shared.
        Clamp the plan to the source's leading device-resident pages
        (below one page it degrades to no sharing, like shared_prefix)."""
        src, rows = share
        ps = self.sc.page_size
        run = 0
        while run * ps < rows and self.alloc.page_table[src, run] >= 0:
            run += 1
        rows = min(rows, run * ps)
        return (src, rows) if rows >= ps else (None, 0)

    def _claim_pages(self, slot: int, req: Request,
                     share) -> Tuple[int, List[Tuple[int, int]]]:
        """Claim the prompt's pages plus the first decode page.  With a
        prefix-sharing plan, whole shared pages are refcount-mapped from
        the resident slot and the divergent partial page is COW-copied;
        returns (prefill start row, device page copies to apply)."""
        ps = self.sc.page_size
        needed = self._claim_count(req)
        copies: List[Tuple[int, int]] = []
        start_row, start_j = 0, 0
        src, rows = share
        if src is not None and rows > 0:
            nfull = rows // ps
            for j in range(nfull):
                self.alloc.share(slot, j, int(self.alloc.page_table[src, j]))
            start_row, start_j = rows, nfull
            if rows % ps:
                # the divergent page: share it, then immediately hit the
                # COW barrier — the copy carries the shared prefix rows
                # this slot needs and the resumed prefill overwrites the
                # rest.  Writes to either copy can no longer reach the
                # other slot's logits.
                self.alloc.share(slot, nfull,
                                 int(self.alloc.page_table[src, nfull]))
                cp = self.alloc.privatize(slot, nfull)
                assert cp is not None
                copies.append(cp)
                start_j = nfull + 1
            self.n_shared_admissions += 1
        for j in range(start_j, needed):
            claimed = self.alloc.alloc(slot, j)
            assert claimed, "free-page count was vetted in _admissible"
        if self.sc.reserve_decode_pages:
            self.alloc.growth_due[slot] = self._max_pages(req) - needed
        for j in range(needed):
            if not self.alloc.check_write(slot, j * ps, ps, strict=False):
                raise IotlbFault(     # pragma: no cover - defensive
                    "miss", f"request {req.rid}: page {j} not covered")
        return start_row, copies

    def admit_many(self, pending: List[Request]) -> int:
        """Admit as many pending requests as there are free slots, then
        run ONE chunked-prefill wave covering the new slots' first
        chunks AND the next chunk of every slot still mid-prefill.  Pops
        admitted (and rejected) requests off ``pending``; returns the
        number admitted.  Swapped-out requests re-enter first.  A request
        that only fails on TRANSIENT page exhaustion stays at the head of
        ``pending`` and the wave stops — it retries once completions free
        pages.

        Legacy batch entry point: admits in LIST order, ignoring
        priorities.  The session path (``submit()`` + ``tick()``) admits
        from the scheduler's priority-ordered pending queue instead."""
        return self._admission_wave(_ListQueue(pending))

    def _admission_wave(self, queue) -> int:
        """One admission wave from ``queue`` (a _ListQueue or _SchedQueue):
        fill free slots in the queue's pop order, then one prefill
        dispatch covering new and resumed slots."""
        with span("serve.admit"):
            placed = self._place_wave(queue)
        if placed:
            self._prefill_tick()    # new slots' first chunk + resumed ones
        return len(placed)

    def _place_wave(self, queue) -> List[tuple]:
        """Place requests from ``queue`` into free slots, in its pop
        order; returns the (slot, request) pairs placed."""
        if self.sc.paged:
            self._swap_in_ready()
        placed: List[tuple] = []        # (slot, request) vetted this wave
        copies: List[Tuple[int, int]] = []
        try:
            for slot in self._free_slots():
                if not queue:
                    break               # out of requests
                with span("serve.place") as place:
                    got, share = None, (None, 0)
                    while queue and got is None:
                        req = queue.pop()
                        if req.done:    # already rejected/finished earlier
                            continue
                        verdict, share = self._admissible(slot, req)
                        if verdict is _DEFER:
                            queue.defer(req)
                            break
                        if verdict is _OVERSIZED:
                            # streams from the host tier: consumes no
                            # slot — keep popping for this one.
                            self._admit_oversized(req)
                            continue
                        if verdict:
                            got = req
                    if got is None:
                        break           # out of requests, or deferred
                    place.set_metadata(rid=got.rid)
                    start_row = 0
                    twin = None
                    if self.sc.paged:
                        if self.sc.decode_sharing:
                            # before place(): the ledger must not match
                            # the request against its own fresh slot.
                            twin = self.sched.find_twin(got.prompt)
                        start_row, cps = self._claim_pages(slot, got, share)
                        copies.extend(cps)
                    self.sched.place(slot, got, prefill_done=start_row)
                    if twin is not None:
                        self.sched.link_twin(slot, twin)
                    placed.append((slot, got))
        except IotlbFault:
            # strict fault mid-wave: no slot was mutated yet (the faulting
            # request is already marked failed + completed) — put the
            # already-vetted requests back (and release any pages they
            # claimed) so a caller that catches the fault loses neither
            # requests nor engine consistency.
            for slot, req in reversed(placed):
                if self.sc.paged:
                    self.alloc.release_slot(slot)
                self.sched.break_twins(slot)
                self.sched.release(slot)
                queue.defer(req)
            raise
        if placed:
            self.peak_active = max(self.peak_active,
                                   len(self.sched.active()))
            self._apply_copies(copies)
        return placed

    def warmup(self) -> None:
        """Compile the jitted prefill (both traces: fresh and resumed, at
        the prefill group's shape) and decode steps at their serving
        shapes with no-op dispatches — zero lengths, every slot inactive,
        so no cache row is written and nothing is admitted.  Benchmarks
        call this so TTFT measures serving latency, not XLA compilation."""
        bsz, sp = self.sc.max_batch, self.sc.max_prompt
        g = self._prefill_group
        z_tok = jnp.zeros((g, sp), jnp.int32)
        z_len = jnp.zeros((g,), jnp.int32)
        one = jnp.zeros((bsz, 1), jnp.int32)
        inactive = jnp.asarray(np.full((bsz,), -1, np.int32))
        if self.sc.paged:
            pages = jnp.asarray(self.alloc.page_table[:g])
            with self._kernel_ctx():
                _, self.cache = self._prefill(self.params, self.cache,
                                              z_tok, z_len, pages, None)
                _, self.cache = self._prefill(self.params, self.cache,
                                              z_tok, z_len, pages, z_len)
                lg, self.cache = self._decode(self.params, self.cache, one,
                                              inactive, self._pages_dev())
                if self._drafter is not None:
                    zv = jnp.zeros((bsz, self.sc.spec_k + 1), jnp.int32)
                    zb = jnp.asarray(np.zeros((bsz,), np.int32))
                    _, self.cache = self._verify(
                        self.params, self.cache, zv, zb,
                        self._pages_dev(), zb)
        else:
            _, self.cache = self._prefill(self.params, self.cache, z_tok,
                                          z_len)
            lg, self.cache = self._decode(self.params, self.cache, one,
                                          inactive)
        jax.block_until_ready(lg)
        if self._drafter is not None:
            self._drafter.warmup()
        if self._experts is not None:
            self._experts.reset()       # warm-up dispatches count nothing

    def admit(self, req: Request) -> bool:
        """Single-request admission (compat shim over the batched path).

        Returns True iff the request was admitted into a slot.  False can
        mean either no slot is free (retry later) or the request was
        rejected — check ``req.done``/``req.failed`` before retrying."""
        return self.admit_many([req]) == 1

    # -- resumable chunked prefill ------------------------------------------
    def _prefill_tick(self) -> None:
        """Chunked-prefill dispatches for every slot owing prompt rows:
        fresh admissions fill [0, chunk), resumed slots [done, done+chunk).
        The owing slots go out in groups of ``_prefill_group``, in slot
        order; slots whose prompt completes this tick sample their first
        token, read back once for the whole wave."""
        work = self.sched.prefill_plan()
        if self.tiered and work:
            # residency gate: a resumed chunk attends the WHOLE cached
            # history [0, off + len), so every page under it must be
            # device-resident; held slots wait for their prefetch.
            work = [(slot, off, toks) for slot, off, toks in work
                    if not self.alloc.blocked_pages(
                        slot,
                        (off + len(toks) - 1) // self.sc.page_size + 1)]
        if not work:
            return
        self._prefilled_since_step = True
        # trace invariance: fresh admissions (offset 0) and resumed chunks
        # dispatch as SEPARATE groups.  The all-fresh trace (offsets=None,
        # single-pass chunk kernel) and the resume trace (full-window
        # gather) sum in different orders, so a mixed group would let the
        # schedule — admissions staggered by tiered page pressure — shift
        # a fresh slot's logits by ~1e-7.  Splitting pins each chunk's
        # trace to its own offset, and every group has the same (group,
        # max_prompt) shape, keeping logits bitwise schedule-invariant
        # (the tiered-vs-resident contract).
        g = self._prefill_group
        groups = []
        for same in ([w for w in work if w[1] == 0],
                     [w for w in work if w[1] > 0]):
            groups += [same[i:i + g] for i in range(0, len(same), g)]
        pending = []
        for i, group in enumerate(groups):
            self._prefill_dispatch(group, pending, i == len(groups) - 1)

    def _prefill_dispatch(self, work, pending: list, land: bool) -> None:
        """Issue one prefill step for ``work`` (same-trace chunks of at
        most ``_prefill_group`` slots) and add what it samples to
        ``pending``; with ``land``, read every pending group's first
        tokens in one transfer and emit them.

        Where the group is smaller than the batch, row i of the dispatch
        is ``work[i]``'s slot, with that slot's row of the page table;
        rows past the work have length 0 and no pages, and write nothing.
        Otherwise row i is slot i, as in decode."""
        bsz, sp, ps = self.sc.max_batch, self.sc.max_prompt, self.sc.page_size
        g = self._prefill_group
        compact = g < bsz
        rows = list(range(len(work))) if compact else [w[0] for w in work]
        with span("serve.prefill", rows=sum(len(w[2]) for w in work),
                  padded_rows=g * sp,
                  resumed=int(any(w[1] for w in work))):
            self.sched.mark_dispatch([w[0] for w in work], self.tick_no)
            if self.sc.paged:
                with span("serve.iotlb"):
                    copies = []
                    for slot, off, toks in work:
                        # COW barrier + page-granular write coverage for
                        # the rows this chunk writes (TLB refills are
                        # counted, true misses fault before any cache
                        # mutation).
                        for j in range(off // ps,
                                       (off + len(toks) - 1) // ps + 1):
                            cp = self.alloc.privatize(slot, j)
                            if cp is not None:
                                copies.append(cp)
                            self.alloc.check_write(
                                slot, j * ps, ps, strict=self.sc.strict_iotlb)
                    self._apply_copies(copies)
            toks_np = np.zeros((g, sp), np.int32)
            lens_np = np.zeros((g,), np.int32)
            offs_np = np.zeros((g,), np.int32)
            for row, (slot, off, toks) in zip(rows, work):
                toks_np[row, :len(toks)] = toks
                lens_np[row] = len(toks)
                offs_np[row] = off
            toks, lens = jnp.asarray(toks_np), jnp.asarray(lens_np)
            if self.sc.paged:
                # all-fresh groups (the common case) pass offsets=None — a
                # separate trace of the same jitted step that keeps the
                # single-pass chunk kernel instead of the full-window
                # gather.
                offs = jnp.asarray(offs_np) if offs_np.any() else None
                if compact:
                    table = np.full((g, self.pages_per_slot), -1, np.int32)
                    table[:len(work)] = self.alloc.page_table[
                        [w[0] for w in work]]
                    pages = jnp.asarray(table)
                else:
                    pages = self._pages_dev()
                with self._kernel_ctx():
                    logits, self.cache = self._prefill(
                        self.params, self.cache, toks, lens, pages, offs)
            else:
                logits, self.cache = self._prefill(self.params, self.cache,
                                                   toks, lens)
            # sample only when some prompt completes this tick: intermediate
            # chunks discard their logits, and at temperature > 0 sampling
            # consumes PRNG key state, so ticks that emit nothing must not
            # burn splits.  (The engine-wide key still makes sampled
            # streams depend on co-admission order in mixed waves; fully
            # schedule-independent sampling needs per-request keys — the
            # greedy path, which every equivalence test uses, is exact.)
            finishes = any(
                off + len(toks) >= len(self.sched.slots[slot].req.prompt)
                for slot, off, toks in work)
            pending.append((work, rows,
                            self._sample(logits) if finishes else None,
                            logits if self.sc.record_logits else None))
            if land:
                self._prefill_land(pending)

    def _prefill_land(self, pending: list) -> None:
        """Read the wave's sampled first tokens (and logits, when
        recorded) in one transfer; emit each finished prompt's token."""
        with span("serve.sync"):
            got = jax.device_get([(firsts, lg)
                                  for _, _, firsts, lg in pending])
        for (work, rows, _, _), (firsts, lg_np) in zip(pending, got):
            for row, (slot, off, chunk_toks) in zip(rows, work):
                meta = self.sched.slots[slot]
                meta.prefill_done = off + len(chunk_toks)
                if not meta.prefilled:
                    continue        # more chunks to come; logits discarded
                req = meta.req
                first = int(firsts[row])
                self.positions[slot] = len(req.prompt)
                self.last_token[slot] = first
                req.out_tokens.append(first)    # the post-prompt prediction
                self.sched.note_first_token(req, self.tick_no)
                if lg_np is not None:
                    req.logits.append(lg_np[row].copy())
                if first == self.sc.eos_id or \
                        len(req.out_tokens) >= self.sc.max_new_tokens:
                    self._finish(slot)

    def _sample(self, logits):
        logits = logits.astype(jnp.float32)
        if self.sc.temperature <= 0:
            return jnp.argmax(logits, axis=-1)
        self.key, k = jax.random.split(self.key)
        return jax.random.categorical(k, logits / self.sc.temperature)

    def _finish(self, slot: int):
        req = self.sched.slots[slot].req
        with span("serve.finish", rid=req.rid):
            req.done = True
            self.sched.note_terminal(req)   # deadline miss if no first token
            self.completed.append(req)
            # twin links die with either party; an orphaned follower keeps
            # its shared pages (release_slot below drops this side's refs,
            # leaving the survivor sole owner) and the restored COW barrier
            # covers any write that would still land in one.
            self.sched.break_twins(slot)
            self.sched.release(slot)    # release slot
            if self._drafter is not None:
                self._drafter.release(slot)
            if self.sc.paged:
                # drop this slot's pending restore transfers BEFORE the
                # allocator cancels their bookkeeping — a stale entry here
                # would try to finish_restore a key the allocator forgot.
                for key in [k for k in self._inflight_data if k[0] == slot]:
                    self._inflight_data.pop(key)
                if self.tiered:
                    self._drop_evictions(slot)
                self.alloc.release_slot(slot)   # refs return to the pool

    # -- device <-> host page movement --------------------------------------
    def _map_cache(self, fn_pool, fn_slot):
        """Rebuild the cache pytree, applying ``fn_pool`` to shared page
        pools and ``fn_slot`` to per-slot state leaves.

        Pool leaves are re-pinned to the page-striped NamedSharding after
        every edit: host-side ``.at[].set`` updates (COW privatize, swap-in
        restore) produce fresh arrays whose placement the compiler is free
        to choose, and an unpinned result would silently replicate the
        pool — N× the per-shard memory the striping exists to save — until
        the next dispatch reshards it.  The explicit put keeps the leaves
        striped through every COW and swap cycle (a no-op transfer when
        the layout already matches)."""
        flat, treedef = jax.tree.flatten(self.cache)
        out = []
        for leaf, pooled in zip(flat, self._pooled):
            new = fn_pool(leaf) if pooled else fn_slot(leaf)
            if pooled and new is not leaf and \
                    self._pool_sharding is not None:
                new = jax.device_put(new, self._pool_sharding)
            out.append(new)
        self.cache = jax.tree.unflatten(treedef, out)

    def _apply_copies(self, copies: List[Tuple[int, int]]) -> None:
        """Apply allocator COW copies (src phys -> dst phys) on device."""
        if not copies:
            return
        src = jnp.asarray([c[0] for c in copies], jnp.int32)
        dst = jnp.asarray([c[1] for c in copies], jnp.int32)
        self._map_cache(lambda leaf: leaf.at[:, dst].set(leaf[:, src]),
                        lambda leaf: leaf)
        self.n_cow_copies += len(copies)

    def _swap_out(self, slot: int) -> None:
        """Preempt ``slot``: snapshot its pages + recurrent state to host,
        release its pages, and park it on the swap queue."""
        meta = self.sched.slots[slot]
        req = meta.req
        # snapshots never carry draft or twin state: the drafter
        # re-prefills from the committed stream after swap-in (lazy
        # catch-up) and a re-admitted twin re-links at admission — the
        # wire format is untouched.
        self.sched.break_twins(slot)
        if self._drafter is not None:
            self._drafter.release(slot)
        n_logical = self.alloc.logical_count(slot)
        # in-flight restores cancel cleanly (the host slot keeps the
        # bytes until finish_restore), so mid-transfer pages read as
        # host-tier below; their pending device arrays are dropped.
        for key in [k for k in self.alloc.inflight if k[0] == slot]:
            self.alloc.cancel_restore(*key)
            self._inflight_data.pop(key, None)
        flat, _ = jax.tree.flatten(self.cache)
        if not self.tiered:
            phys = np.asarray(
                [int(p) for p in self.alloc.page_table[slot, :n_logical]])
            pool_rows = [np.asarray(leaf[:, phys]) for leaf, pooled
                         in zip(flat, self._pooled) if pooled]
        else:
            # assemble the snapshot from BOTH tiers, logical order: a
            # device page gathers off the pool, an evicted page copies
            # straight out of its pinned host buffer — whose in-flight
            # evictions must land first (residency gate on host reads).
            self._flush_evictions(
                int(h) for h in self.alloc.host_table[slot] if h >= 0)
            pool_leaves = [leaf for leaf, pooled
                           in zip(flat, self._pooled) if pooled]
            pool_rows = []
            for li, leaf in enumerate(pool_leaves):
                cols = []
                for j in range(n_logical):
                    phys = int(self.alloc.page_table[slot, j])
                    if phys >= 0:
                        cols.append(np.asarray(leaf[:, phys]))
                    else:
                        h = int(self.alloc.host_table[slot, j])
                        assert h >= 0, "logical page in neither tier"
                        cols.append(self._host_tier[li][h])
                pool_rows.append(
                    np.stack(cols, axis=1) if cols else
                    np.zeros((leaf.shape[0], 0) + leaf.shape[2:],
                             leaf.dtype))
        slot_rows = [np.asarray(leaf[:, slot]) for leaf, pooled
                     in zip(flat, self._pooled) if not pooled]
        nbytes = sum(a.nbytes for a in pool_rows) + \
            sum(a.nbytes for a in slot_rows)
        self.sched.swapped.append(SwappedRequest(
            req=req, prefill_done=meta.prefill_done, order=meta.order,
            pos=int(self.positions[slot]),
            last_token=int(self.last_token[slot]),
            n_pages=n_logical, n_max=self._max_pages(req),
            growth_due=int(self.alloc.growth_due[slot]),
            pool_rows=pool_rows, slot_rows=slot_rows, nbytes=nbytes))
        self.alloc.release_slot(slot)
        self.sched.release(slot)
        req.preempts += 1
        self.n_preemptions += 1
        self._enforce_swap_budget()

    def _swap_in(self, slot: int, sw: SwappedRequest) -> None:
        """Re-admit a swapped request: fresh pages, exact bytes back."""
        for j in range(sw.n_pages):
            claimed = self.alloc.alloc(slot, j)
            assert claimed, "swap-in pages were vetted in _swap_in_ready"
        phys = jnp.asarray(
            [int(p) for p in self.alloc.page_table[slot, :sw.n_pages]],
            jnp.int32)
        pool_it = iter(sw.pool_rows)
        slot_it = iter(sw.slot_rows)
        self._map_cache(
            lambda leaf: leaf.at[:, phys].set(
                jnp.asarray(next(pool_it), leaf.dtype)),
            lambda leaf: leaf.at[:, slot].set(
                jnp.asarray(next(slot_it), leaf.dtype)))
        if self.sc.reserve_decode_pages:
            self.alloc.growth_due[slot] = sw.growth_due
        self.positions[slot] = sw.pos
        self.last_token[slot] = sw.last_token
        self.sched.place(slot, sw.req, prefill_done=sw.prefill_done,
                         order=sw.order)
        self.peak_active = max(self.peak_active, len(self.sched.active()))
        self.n_swap_ins += 1

    def _swap_in_ready(self) -> None:
        """Re-admit swapped requests (FIFO) while slots and pages allow:
        mapped pages to restore, plus one growth page of headroom so the
        next decode tick makes progress instead of re-thrashing."""
        while self.sched.swapped and self.sched.free_slots():
            # hoist the slot choice: the old code re-queried
            # free_slots() AFTER popping the queue, so anything between
            # the vet and the placement that took a slot would silently
            # re-pair (or IndexError on an empty list).  Choose first,
            # then assert the pairing still holds at placement.
            slot = self.sched.free_slots()[0]
            sw = self.sched.swapped[0]
            need = sw.n_pages + (sw.growth_due if
                                 self.sc.reserve_decode_pages
                                 else int(sw.n_pages < sw.n_max))
            short = need - self.alloc.reserved_free()
            if short > 0 and not (self.tiered and self._evict_pages(
                    short, protect=self._held_slots)):
                break
            self.sched.swapped.pop(0)
            if sw.spill_step is not None:
                self._unspill(sw)
            assert self.sched.slots[slot] is None, \
                "chosen free slot was taken before placement"
            self._swap_in(slot, sw)

    # -- cross-replica migration seam (router tier) -------------------------
    def export_parked(self) -> Optional[SwappedRequest]:
        """Pop this engine's COLDEST parked snapshot (the swap-queue
        tail — the request this replica would re-admit LAST, the same
        cold-first rule eviction and spill use) for cross-replica
        migration, or None when nothing is parked.  A spilled snapshot
        re-materializes from disk first: the wire format carries bytes,
        not checkpoint step ids."""
        sw = self.sched.pop_parked(coldest=True)
        if sw is None:
            return None
        if sw.spill_step is not None:
            self._unspill(sw)
        return sw

    def import_parked(self, sw: SwappedRequest) -> None:
        """Adopt a snapshot another replica exported: re-stamp it into
        the LOCAL admission order (cross-engine order values are
        meaningless and could collide) and park it on the swap queue —
        the normal ``_swap_in_ready`` path then restores its pages and
        resumes decode bit-for-bit, exactly like a home-grown swap-in.
        Raises when the snapshot can never fit this engine's pool."""
        if self._closed:
            raise RuntimeError(
                "ServingEngine is closed: import_parked() after drain()")
        if not self.sc.paged:
            raise ValueError("import_parked needs the paged engine "
                             "(snapshots hold page contents)")
        if sw.n_pages + int(sw.n_pages < sw.n_max) > self.num_pages:
            raise ValueError(
                f"snapshot needs {sw.n_pages} pages (+growth headroom); "
                f"this pool holds {self.num_pages}")
        sw.order = self.sched.next_order()
        self.sched.swapped.append(sw)
        self._enforce_swap_budget()

    # -- steady-state decode tick -------------------------------------------
    def _grow_pages(self, active: List[int]) -> None:
        """Map the page covering each active slot's next write row (decode
        crosses a page boundary every ``page_size`` ticks).  Exhaustion
        mid-decode — reachable only when ``reserve_decode_pages`` is off
        (overcommit) — triggers ``ServeConfig.preemption``: swap out the
        youngest other resident request and retry, or (no viable victim /
        preemption='terminate') a capacity fault that ends the request
        with its partial output (strict mode raises)."""
        ps = self.sc.page_size
        cow: List[Tuple[int, int]] = []
        for i in active:
            meta = self.sched.slots[i]
            if meta is None:        # swapped out by an earlier iteration
                continue
            wr = int(self.positions[i])     # this tick's cache write row
            j = wr // ps
            if self.alloc.page_table[i, j] < 0:
                L = self.sched.leader_of(i)
                if L is not None and self.sched.slots[L] is not None \
                        and self.alloc.page_table[L, j] >= 0 \
                        and int(self.positions[L]) >= wr:
                    # twin decode sharing: the leader has written (or
                    # writes this very dispatch, identical bytes — same
                    # token at the same row under greedy lockstep) every
                    # row of page j this follower will attend, so map the
                    # leader's physical page instead of growing a new
                    # one.  Both lanes' scatters then land the SAME bytes
                    # in the same rows; the COW barrier below stands down
                    # only while the equality ledger holds the link.
                    self.alloc.share(i, j,
                                     int(self.alloc.page_table[L, j]))
                    self.n_twin_pages += 1
                    self.alloc.growth_due[i] = max(
                        0, int(self.alloc.growth_due[i]) - 1)
                    self.alloc.check_write(i, wr, 1,
                                           strict=self.sc.strict_iotlb)
                    continue
                grown = self.alloc.alloc(i, j)
                if not grown and self.tiered and self._evict_pages(
                        1, protect=self._held_slots | set(active)):
                    # page-granular relief: a cold page moves to the host
                    # tier instead of a whole request swapping out.  EVERY
                    # slot dispatching this tick is protected — it already
                    # passed the residency gate, so stealing one of its
                    # window pages now would corrupt the very dispatch
                    # that gate cleared.
                    grown = self.alloc.alloc(i, j)
                while not grown and self.sc.preemption == "swap":
                    v = self.sched.victim(exclude=i)
                    if v is None or not self._swappable(v):
                        break
                    if self.sched.slots[v].req.priority > \
                            meta.req.priority:
                        # priority inversion guard: the best victim still
                        # outranks the grower, i.e. EVERY other resident
                        # does — park the grower itself rather than evict
                        # higher-priority work; when the grower cannot be
                        # parked (pool fit / swap budget), it takes the
                        # capacity path instead.  Higher-priority work is
                        # NEVER the victim here.  (Not taken at uniform
                        # priority, so the legacy youngest-first behavior
                        # is bit-preserved.)
                        if not (self._swap_fits_budget(i)
                                or self._spill_until_fits(i)):
                            self._deny_swap_budget(i)
                        elif self._swappable(i):
                            self._swap_out(i)
                        break
                    if not (self._swap_fits_budget(v)
                            or self._spill_until_fits(v)):
                        self._deny_swap_budget(v)
                        break
                    self._swap_out(v)
                    grown = self.alloc.alloc(i, j)
                if self.sched.slots[i] is None:
                    continue            # grower preempted itself
                if grown:
                    # a reserved page materialized: shrink the reservation.
                    self.alloc.growth_due[i] = max(
                        0, int(self.alloc.growth_due[i]) - 1)
                else:
                    self.iotlb.faults.append(FaultRecord(
                        "capacity", i * self._slot_span + wr, 1, True))
                    req = meta.req
                    req.failed = True
                    self._finish(i)
                    if self.sc.strict_iotlb:
                        raise IotlbFault(
                            "capacity", f"request {req.rid}: page pool "
                            f"exhausted growing row {wr}")
                    continue
            else:
                # COW barrier: decode never writes a page another slot
                # still references.  (Reachable only for prefix shares —
                # which lie strictly inside both parties' prompt regions,
                # so decode rows >= len(prompt) never hit them: defense
                # in depth — and for twin decode pages, where the barrier
                # STANDS DOWN while the link holds: both lanes write
                # identical bytes, and sharing them is the whole point.
                # A broken link restores the barrier before the next
                # write.)
                if not self.sched.is_twinned(i):
                    cp = self.alloc.privatize(i, j)
                    if cp is not None:
                        cow.append(cp)
            # page-granular write check for this tick's row: a row past
            # the slot's mapped pages faults AT THE PAGE BOUNDARY here
            # rather than silently landing inside a whole-slot window.
            self.alloc.check_write(i, wr, 1, strict=self.sc.strict_iotlb)
        self._apply_copies(cow)

    def _swappable(self, slot: int) -> bool:
        """Pool-fit probe (side-effect-free): a preempted request must be
        re-admittable later, so its mapped pages (plus a growth page if
        it is not fully grown) have to fit the pool."""
        meta = self.sched.slots[slot]
        n_logical = self.alloc.logical_count(slot)
        return n_logical + int(n_logical < self._max_pages(meta.req)) \
            <= self.num_pages

    def _swap_fits_budget(self, slot: int) -> bool:
        """Budget probe (side-effect-free): would swapping ``slot`` keep
        the swap queue within ``ServeConfig.swap_budget_bytes``?"""
        budget = self.sc.swap_budget_bytes
        if budget is None:
            return True
        est = self.alloc.logical_count(slot) * self._page_nbytes \
            + self._slot_state_nbytes
        return self.sched.swap_bytes() + est <= budget

    def _deny_swap_budget(self, slot: int) -> None:
        """Record a swap denied BECAUSE of the byte budget (the single
        accounting site): past the cap the swap queue stops absorbing
        state — the growing request takes the capacity path instead of
        the host holding unbounded memory."""
        self.iotlb.faults.append(FaultRecord(
            "swap_budget", slot * self._slot_span,
            self.alloc.logical_count(slot) * self.sc.page_size, True))
        self.n_swap_budget_denials += 1

    def _spill_until_fits(self, slot: int) -> bool:
        """Whether durable spill lets the budget absorb swapping ``slot``:
        with a ``spill_dir`` the answer is always yes — ``_swap_out``
        re-establishes the cap afterwards by spilling parked snapshots
        (coldest-first, the new arrival included) to disk, where the
        byte budget does not apply.  False without a spill_dir, so the
        budget-denial path is untouched when spilling is off."""
        del slot    # any snapshot can spill; the cap bounds host bytes only
        return self.sc.spill_dir is not None

    def _enforce_swap_budget(self) -> None:
        """Spill parked snapshots coldest-first — the queue TAIL
        re-admits last — until host-resident swap bytes are back under
        ``swap_budget_bytes``.  A spilled entry keeps only shape/dtype
        skeletons in memory, so the cap is always reachable."""
        budget = self.sc.swap_budget_bytes
        if budget is None or self.sc.spill_dir is None:
            return
        k = len(self.sched.swapped) - 1
        while self.sched.swap_bytes() > budget and k >= 0:
            if self.sched.swapped[k].spill_step is None:
                self._spill(self.sched.swapped[k])
            k -= 1

    def _spill(self, sw: SwappedRequest) -> None:
        """Swap queue -> disk: checkpoint the snapshot atomically, keep
        only shape/dtype skeletons in host memory (nbytes -> 0)."""
        tree = {"pool": {f"p{i}": a for i, a in enumerate(sw.pool_rows)},
                "slot": {f"s{i}": a for i, a in enumerate(sw.slot_rows)}}
        checkpointing.save(self.sc.spill_dir, tree, step=self._spill_seq)
        sw.spill_step = self._spill_seq
        self._spill_seq += 1
        sw.pool_rows = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                        for a in sw.pool_rows]
        sw.slot_rows = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                        for a in sw.slot_rows]
        sw.nbytes = 0
        self.n_spills += 1

    def _unspill(self, sw: SwappedRequest) -> None:
        """Disk -> swap queue: re-materialize a spilled snapshot (the
        skeletons carry shape/dtype, so nothing was allocated meanwhile)."""
        tree, _ = checkpointing.restore(
            self.sc.spill_dir,
            {"pool": {f"p{i}": a for i, a in enumerate(sw.pool_rows)},
             "slot": {f"s{i}": a for i, a in enumerate(sw.slot_rows)}},
            step=sw.spill_step)
        sw.pool_rows = [np.asarray(tree["pool"][f"p{i}"])
                        for i in range(len(sw.pool_rows))]
        sw.slot_rows = [np.asarray(tree["slot"][f"s{i}"])
                        for i in range(len(sw.slot_rows))]
        sw.nbytes = sum(a.nbytes for a in sw.pool_rows) + \
            sum(a.nbytes for a in sw.slot_rows)
        sw.spill_step = None

    # -- tiered pool: page-granular offload + async prefetch -----------------
    def _evict_pages(self, n: int, protect=frozenset()) -> bool:
        """Move ``n`` cold pages device -> host (coldest slot first,
        lowest page first — its longest-parked rows).  Returns True iff
        all ``n`` moved.  ``protect`` slots are never victims: the
        requester itself, plus every slot currently held mid-restore
        (stealing their pages back would livelock the rotation).

        The copy is ASYNC, mirroring the restore path: ``leaf[:, phys]``
        materializes the page as its OWN device buffer — so the freed
        physical page can be reallocated and rewritten immediately
        without racing the transfer — and the device->host copy overlaps
        later ticks' compute, landing in the pinned buffer at tick start
        (``_land_evictions``) or, residency-gated, the moment anything
        needs the host bytes (``_flush_evictions``; forced waits count
        ``evict_stalls``)."""
        if not self.tiered or n <= 0:
            return n <= 0
        flat, _ = jax.tree.flatten(self.cache)
        pool_leaves = [leaf for leaf, pooled
                       in zip(flat, self._pooled) if pooled]
        done = 0
        for slot in self.sched.cold_order(exclude=protect):
            for j in range(self.alloc.pages_per_slot):
                if done >= n:
                    return True
                got = self.alloc.evict(slot, j)
                if got is None:
                    continue
                phys, host = got
                assert host not in self._evict_pending, \
                    "host slot reissued with a copy still in flight"
                arrs = [leaf[:, phys] for leaf in pool_leaves]
                for a in arrs:
                    a.copy_to_host_async()
                self._evict_pending[host] = {"arrs": arrs,
                                             "tick": self.tick_no}
                self.n_evictions += 1
                done += 1
        return done >= n

    def _evict_ready(self, info) -> bool:
        if self.sc.transfer_ticks is not None:    # modeled, deterministic
            return self.tick_no - info["tick"] >= self.sc.transfer_ticks
        return all(a.is_ready() for a in info["arrs"])

    def _land_evictions(self) -> None:
        """Land the in-flight evictions whose transfer has completed
        (called once per tick, with restores, at ``_tier_tick``)."""
        for host in [h for h, info in self._evict_pending.items()
                     if self._evict_ready(info)]:
            info = self._evict_pending.pop(host)
            for li, a in enumerate(info["arrs"]):
                self._host_tier[li][host] = np.asarray(a)

    def _flush_evictions(self, hosts) -> None:
        """Residency gate on the HOST tier: force-land any pending
        eviction into the given host slots before their bytes are read
        (restore issue, swap-out assembly).  A landing the transfer had
        not finished on its own is a counted stall — the price of the
        overlap, the mirror of ``prefetch_late``."""
        for host in list(hosts):
            info = self._evict_pending.pop(int(host), None)
            if info is None:
                continue
            if not self._evict_ready(info):
                self.evict_stalls += 1
            for li, a in enumerate(info["arrs"]):
                self._host_tier[li][host] = np.asarray(a)

    def _drop_evictions(self, slot: int) -> None:
        """Discard pending evictions into ``slot``'s host slots (the
        request is finishing or being snapshot — the bytes are moot).
        Must run BEFORE ``alloc.release_slot`` returns those host slots
        to the free list: a later eviction reusing one would otherwise
        be corrupted by this stale landing."""
        for h in self.alloc.host_table[slot]:
            if h >= 0:
                self._evict_pending.pop(int(h), None)

    def _issue_restore(self, slot: int, j: int, protect) -> bool:
        """Start one async host -> device page restore: claim a target
        page (evicting a cold one if the pool is full), then launch
        ``jax.device_put`` of the pinned host bytes — the transfer
        overlaps subsequent ticks' compute and lands in
        ``_apply_restores``."""
        if not self.alloc.free_pages and \
                not self._evict_pages(1, protect=protect):
            return False
        got = self.alloc.begin_restore(slot, j)
        if got is None:
            return False
        dst, host = got
        # the source host slot may still have its eviction in flight:
        # land it first (counted as a stall if the copy wasn't done).
        self._flush_evictions([host])
        # .copy(): on the CPU backend device_put can be ZERO-copy — the
        # resulting array would alias the pinned host row, whose slot is
        # freed at finish_restore and rewritten by a later eviction
        # while the (async) apply may not have read it yet.
        self._inflight_data[(slot, j)] = {
            "dst": dst, "tick": self.tick_no, "waited": False,
            "arrs": [jax.device_put(buf[host].copy())
                     for buf in self._host_tier]}
        self.n_restores += 1
        return True

    def _restore_ready(self, info) -> bool:
        if self.sc.transfer_ticks is not None:    # modeled, deterministic
            return self.tick_no - info["tick"] >= self.sc.transfer_ticks
        return all(a.is_ready() for a in info["arrs"])

    def _apply_restores(self, keys) -> None:
        """Land finished restores: one batched ``.at[:, dst].set`` per
        pool leaf, then the allocator maps the pages in."""
        if not keys:
            return
        infos = [self._inflight_data.pop(k) for k in keys]
        dst = jnp.asarray([info["dst"] for info in infos], jnp.int32)
        per_leaf = [[info["arrs"][li] for info in infos]
                    for li in range(len(self._host_tier))]
        it = iter(per_leaf)
        self._map_cache(
            lambda leaf: leaf.at[:, dst].set(
                jnp.stack([jnp.asarray(a, leaf.dtype) for a in next(it)],
                          axis=1)),
            lambda leaf: leaf)
        for (slot, j), info in zip(keys, infos):
            self.alloc.finish_restore(slot, j)
            if info["waited"]:
                self.prefetch_late += 1
            else:
                self.prefetch_hits += 1

    def _tier_tick(self) -> None:
        """Once per tick, BEFORE dispatch planning: land finished
        restores and refresh the held set.  New restores are issued at
        the END of the tick (``_tier_prefetch``) — never here — so a
        slot whose window just completed always gets its dispatch in
        before any eviction can steal the restored pages back (the
        alternative ping-pongs: restore, steal, re-restore, forever)."""
        self._land_evictions()
        self._apply_restores([k for k, info in self._inflight_data.items()
                              if self._restore_ready(info)])
        self._held_slots = {slot for slot, _ in self._tier_needs()}

    def _tier_needs(self) -> List[Tuple[int, int]]:
        """(slot, page) pairs off-device in some slot's next dispatch
        window, coldest slot first, ascending page — the prefetch work
        list.  A slot mid-prefill needs its NEXT chunk's rows (plus the
        attended history); a prompt-complete slot needs [0, pos]."""
        ps = self.sc.page_size
        needs: List[Tuple[int, int]] = []
        for slot in self.sched.cold_order():
            meta = self.sched.slots[slot]
            if meta.prefilled:
                last_row = int(self.positions[slot])
            else:
                off = meta.prefill_done
                ln = min(self.sched.chunk, len(meta.req.prompt) - off)
                last_row = off + ln - 1
            needs.extend((slot, j) for j in
                         self.alloc.blocked_pages(slot, last_row // ps + 1))
        return needs

    def _tier_prefetch(self) -> None:
        """END of tick: issue restores for blocked windows — coldest
        slot first, ascending page — keeping up to the prefetch depth in
        flight.  Every slot that could dispatch this tick already did
        (and is now warm), so evicting a victim page here never undoes
        un-dispatched work.  The COLDEST blocked slot may, as a last
        resort, evict pages of other held slots (never vice versa), so
        exactly one slot always accumulates its window monotonically and
        the rotation cannot livelock."""
        needs = self._tier_needs()
        held = {slot for slot, _ in needs}
        self._held_slots = held
        depth = self._prefetch_depth()
        coldest = needs[0][0] if needs else None
        for slot, j in needs:
            if len(self._inflight_data) >= depth:
                break
            if (slot, j) in self.alloc.inflight:
                continue
            ok = self._issue_restore(slot, j, protect=held | {slot})
            if not ok and slot == coldest:
                ok = self._issue_restore(slot, j, protect={slot})
            if not ok:
                break

    def _blocked_decode(self, slots: List[int]) -> set:
        """Decode candidates whose attention window [0, pos] has a page
        off-device — they sit this tick out (their restores are already
        in the prefetch queue)."""
        ps = self.sc.page_size
        return {i for i in slots if self.alloc.blocked_pages(
            i, int(self.positions[i]) // ps + 1)}

    def _await_restore(self) -> None:
        """EVERY decode candidate is residency-blocked (the caller
        counted the stall): block on the oldest in-flight restore and
        land whatever is ready.  In modeled-latency mode the tick clock
        itself advances the transfer, so only the accounting happens.
        With nothing in flight at all, both tiers are saturated by held
        slots — relieve pressure the pre-tier way (whole-request swap of
        the coldest resident)."""
        if not self._inflight_data:
            self._tier_prefetch()   # issue what the pool allows right now
        if self._inflight_data:
            oldest = min(self._inflight_data,
                         key=lambda k: self._inflight_data[k]["tick"])
            info = self._inflight_data[oldest]
            info["waited"] = True
            if self.sc.transfer_ticks is None:
                jax.block_until_ready(info["arrs"])
            self._apply_restores(
                [k for k, i in self._inflight_data.items()
                 if self._restore_ready(i)])
            return
        if self.sc.preemption == "swap":
            for v in self.sched.cold_order():
                if self._swappable(v) and (self._swap_fits_budget(v)
                                           or self._spill_until_fits(v)):
                    self._swap_out(v)
                    return

    def _prefetch_depth(self) -> int:
        """Restores to keep in flight: the pinned knob, or ("auto") the
        pages one tick's worth of measured host->device bandwidth moves —
        deep enough to hide the transfer behind compute, shallow enough
        not to flood the pool with speculative pages."""
        if self.sc.prefetch_depth != "auto":
            return int(self.sc.prefetch_depth)
        tick_s = self._tick_ema if self._tick_ema else 1e-2
        pages = tick_s * self._h2d_bandwidth() / max(self._page_nbytes, 1)
        return max(1, min(8, int(pages)))

    def _h2d_bandwidth(self) -> float:
        """Measured host->device bytes/s (lazy, cached).  The measurement
        lives beside the figure it reproduces
        (benchmarks/fig12_offload.measure_offload_bandwidth); src/ must
        not hard-depend on benchmarks/, so a missing module falls back
        to a conservative constant."""
        if self._h2d_bps is None:
            try:
                from benchmarks.fig12_offload import \
                    measure_offload_bandwidth
                bw = measure_offload_bandwidth(
                    nbytes=max(self._page_nbytes, 1 << 16), iters=2)
                self._h2d_bps = float(bw["h2d_bytes_per_s"])
            except Exception:
                self._h2d_bps = _FALLBACK_H2D_BPS
        return self._h2d_bps

    def tier_stats(self) -> dict:
        """Tiered-pool telemetry (all zeros on a single-tier engine)."""
        hits, late = self.prefetch_hits, self.prefetch_late
        return {
            "n_evictions": self.n_evictions,
            "evict_stalls": self.evict_stalls,
            "n_restores": self.n_restores,
            "prefetch_hits": hits,
            "prefetch_late": late,
            "prefetch_hit_rate": hits / max(hits + late, 1),
            "decode_ticks": self.decode_ticks,
            "stall_ticks": self.stall_ticks,
            "stall_tick_frac": self.stall_ticks / max(self.decode_ticks, 1),
            "n_oversized": self.n_oversized,
            "n_spills": self.n_spills,
            "host_pages_used": (self.alloc.host_pages_used()
                                if self.sc.paged else 0),
            "spec_disabled": (self._drafter.n_disabled
                              if self._drafter is not None else 0),
        }

    # -- oversized contexts: host-resident cache, streamed dispatches --------
    def _is_oversized(self, req: Request) -> bool:
        return any(ov.req is req for ov in self._oversized)

    def _admit_oversized(self, req: Request) -> None:
        """Admit a context too large for the device pool: its contiguous
        batch-1 cache lives in HOST memory (priced against the host tier
        in pool pages) and every dispatch streams it through the device."""
        ps = self.sc.page_size
        span = len(req.prompt) + self.sc.max_new_tokens
        n_host = -(-span // ps)
        ok = self.alloc.reserve_host(n_host)
        assert ok, "host capacity was vetted in _admissible"
        cap = n_host * ps
        cache = jax.tree.map(np.asarray, init_cache(self.cfg, 1, cap))
        if self._ov_prefill is None:
            self._ov_prefill = self._tallied(jax.jit(
                make_chunked_prefill_resume_step(self.cfg)), "prefill")
            self._ov_decode = self._tallied(jax.jit(
                make_decode_step(self.cfg)), "decode")
        self._oversized.append(_OversizedRequest(
            req=req, cache=cache, cap=cap, n_host_pages=n_host))
        self.n_oversized += 1

    def _oversized_tick(self) -> None:
        for ov in list(self._oversized):
            self._ov_dispatch(ov)

    def _ov_dispatch(self, ov: _OversizedRequest) -> None:
        """One streamed dispatch for an oversized context: upload the
        host cache, run one prefill chunk (or one decode token), pull
        the cache back.  Same chunking, sampling, and termination rules
        as the slotted path, so tokens match an all-resident engine."""
        req = ov.req
        cache_dev = jax.tree.map(jnp.asarray, ov.cache)
        if ov.prefill_done < len(req.prompt):
            sp = self.sc.max_prompt
            off = ov.prefill_done
            toks = req.prompt[off:off + sp]
            toks_np = np.zeros((1, sp), np.int32)
            toks_np[0, :len(toks)] = toks
            logits, cache_dev = self._ov_prefill(
                self.params, cache_dev, jnp.asarray(toks_np),
                jnp.asarray([len(toks)], jnp.int32),
                jnp.asarray([off], jnp.int32))
            ov.cache = jax.tree.map(np.asarray, cache_dev)
            ov.prefill_done = off + len(toks)
            if ov.prefill_done < len(req.prompt):
                return                  # intermediate chunk: no sample
            tok = int(np.asarray(self._sample(logits))[0])
            ov.pos = len(req.prompt)
            self.sched.note_first_token(req, self.tick_no)
        else:
            logits, cache_dev = self._ov_decode(
                self.params, cache_dev,
                jnp.asarray([[ov.last_token]], jnp.int32),
                jnp.asarray([ov.pos], jnp.int32))
            ov.cache = jax.tree.map(np.asarray, cache_dev)
            ov.pos += 1
            tok = int(np.asarray(self._sample(logits))[0])
        ov.last_token = tok
        req.out_tokens.append(tok)
        if self.sc.record_logits:
            req.logits.append(np.asarray(logits)[0].copy())
        if tok == self.sc.eos_id or \
                len(req.out_tokens) >= self.sc.max_new_tokens:
            req.done = True
            self.sched.note_terminal(req)
            self.completed.append(req)
            self.alloc.release_host(ov.n_host_pages)
            self._oversized.remove(ov)

    def _spec_round(self, active: List[int]) -> None:
        """One speculative round over ``active``: the drafter proposes up
        to ``spec_k`` greedy tokens per slot, the target verifies all
        k+1 candidate rows in ONE dispatch, the longest accepted prefix
        commits, and rejected rows roll back page-granularly.

        Greedy bit-identity: row j of the verify block attends exactly
        the window plain decode would at position P+j with the same
        flash op order (models/attention._verify_attention_local), the
        committed tokens in rows [P, P+j) are by construction the ones
        decode would have written (row P is the feed token; an accepted
        draft row IS the target's argmax), and the commit loop below
        replays decode's append -> terminate -> continue rule token by
        token.  Whatever the drafter proposes only changes how many
        dispatches the stream costs, never its bytes."""
        ps, sk = self.sc.page_size, self.sc.spec_k
        bsz = self.sc.max_batch
        work: List[Tuple[int, List[int], int]] = []
        for i in active:
            req = self.sched.slots[i].req
            P = int(self.positions[i])
            # clamp: candidate rows past the max_new_tokens cap can
            # never commit, and the clamp keeps every verify write row
            # (<= P + k <= len(prompt) + max_new_tokens - 2) inside the
            # slot's worst-case page reservation (_max_pages).
            k = max(0, min(sk, self.sc.max_new_tokens
                           - len(req.out_tokens) - 1))
            # target pages for verify rows (P, P+k] beyond the one
            # _grow_pages mapped — drawn from the slot's own reservation
            # (reserve mode: always succeeds) or the free pool
            # (overcommit: exhaustion degrades k for this round; the
            # engine never preempts anyone to speculate).
            for j in range(P // ps + 1, (P + k) // ps + 1):
                if self.alloc.page_table[i, j] < 0 and \
                        not self.alloc.alloc(i, j):
                    k = j * ps - 1 - P
                    break
            if self.sc.reserve_decode_pages:
                self.alloc.growth_due[i] = max(
                    0, self._max_pages(req) - self.alloc.logical_count(i))
            if k > 0:
                work.append((i, req.prompt + req.out_tokens, k))
        proposals = self._drafter.propose(work)
        # COW + page-granular write coverage for the verify rows
        # (privatize is defense in depth, as on the decode path: shared
        # pages live in prompt regions, verify writes at rows >= P >=
        # len(prompt)).
        cow: List[Tuple[int, int]] = []
        for i in active:
            d = proposals.get(i, [])
            P = int(self.positions[i])
            for j in range(P // ps, (P + len(d)) // ps + 1):
                cp = self.alloc.privatize(i, j)
                if cp is not None:
                    cow.append(cp)
                self.alloc.check_write(i, j * ps, ps,
                                       strict=self.sc.strict_iotlb)
        self._apply_copies(cow)
        # ONE verify dispatch at the FIXED (bsz, spec_k + 1) trace shape:
        # row 0 carries the committed feed token (exactly plain decode's
        # write), rows 1..k the draft; a slot with no draft this round
        # rides as a length-1 row — bitwise plain decode — and inactive
        # lanes ride at length 0 (no write, fully-masked attention).
        toks_np = np.zeros((bsz, sk + 1), np.int32)
        lens_np = np.zeros((bsz,), np.int32)
        offs_np = np.zeros((bsz,), np.int32)
        for i in active:
            d = proposals.get(i, [])
            toks_np[i, 0] = self.last_token[i]
            toks_np[i, 1:1 + len(d)] = d
            lens_np[i] = len(d) + 1
            offs_np[i] = self.positions[i]
        with self._kernel_ctx():
            logits, self.cache = self._verify(
                self.params, self.cache, jnp.asarray(toks_np),
                jnp.asarray(lens_np), self._pages_dev(),
                jnp.asarray(offs_np))
        with span("serve.sync"):
            greedy = np.asarray(verify_greedy_tokens(logits))
            lg_np = np.asarray(logits) if self.sc.record_logits else None
        for i in active:
            req = self.sched.slots[i].req
            d = proposals.get(i, [])
            P = int(self.positions[i])
            n_emit = 0
            finished = False
            for j in range(len(d) + 1):
                tok = int(greedy[i, j])
                req.out_tokens.append(tok)
                if lg_np is not None:
                    req.logits.append(lg_np[i, j].copy())
                n_emit += 1
                if tok == self.sc.eos_id or \
                        len(req.out_tokens) >= self.sc.max_new_tokens:
                    finished = True   # decode's exact termination rule
                    break
                if j < len(d) and tok != d[j]:
                    break             # first rejection: later rows invalid
            self.last_token[i] = req.out_tokens[-1]
            self.positions[i] = P + n_emit
            # page-granular rollback: whole pages past the last committed
            # row (P + n_emit - 1) release back to the pool — respecting
            # refcounts, so a prefix-shared page merely drops this ref.
            # Rejected rows left on the kept boundary page are never
            # attended (decode at position p masks rows > p) and are
            # overwritten before the position reaches them.
            self.alloc.truncate_rows(i, P + n_emit)
            if self.sc.reserve_decode_pages:
                self.alloc.growth_due[i] = max(
                    0, self._max_pages(req) - self.alloc.logical_count(i))
            self.n_spec_rounds += 1
            self.n_draft_tokens += len(d)
            self.n_draft_accepted += n_emit - 1
            req.spec_drafted += len(d)
            req.spec_accepted += n_emit - 1
            if finished:
                self._finish(i)
            else:
                self._drafter.commit(i, P, len(d), n_emit)

    def spec_stats(self) -> dict:
        """Speculation telemetry (all zeros when spec_draft is None)."""
        d = self._drafter
        return {
            "spec_rounds": self.n_spec_rounds,
            "draft_tokens": self.n_draft_tokens,
            "draft_accepted": self.n_draft_accepted,
            "acceptance_rate": self.n_draft_accepted
            / max(self.n_draft_tokens, 1),
            "draft_dispatches": d.n_draft_dispatches if d else 0,
            "catchup_dispatches": d.n_catchup_dispatches if d else 0,
            "spec_disabled": d.n_disabled if d else 0,
        }

    def step(self):
        """One engine tick: advance any unfinished prefill by one chunk
        (unless this tick's admission wave already did), then one decode
        step for every prompt-complete slot — at most ONE prefill wave and
        ONE decode dispatch per tick."""
        t0 = time.perf_counter()
        if self.tiered:
            with span("serve.tier"):
                self._tier_tick()
        if self.sc.paged and self.sched.has_prefill_work() \
                and not self._prefilled_since_step:
            self._prefill_tick()
        self._prefilled_since_step = False
        if self.sc.paged:
            runnable = self.sched.decode_slots()
            if self.tiered and runnable:
                # residency gate: a held slot sits the tick out while
                # its prefetch lands (overlap, not a stall); only when
                # EVERY candidate is held has the tick truly stalled on
                # the transfer tier.
                self.decode_ticks += 1
                blocked = self._blocked_decode(runnable)
                if len(blocked) == len(runnable):
                    self.stall_ticks += 1
                    self._await_restore()
                    blocked = self._blocked_decode(
                        self.sched.decode_slots())
                runnable = [i for i in runnable if i not in blocked]
            with span("serve.grow"):
                self._grow_pages(runnable)
            runnable = set(runnable)
            active = [i for i in self.sched.decode_slots()
                      if i in runnable]   # growth may have swapped slots
        else:
            active = self.sched.decode_slots()
        self.active_ticks += len(active)
        if not active:
            self._end_tick(t0)
            return
        self.sched.mark_dispatch(active, self.tick_no)
        if self._drafter is not None:
            with span("serve.spec"):
                self._spec_round(active)
            self._end_tick(t0)
            return
        with span("serve.decode"):
            self._decode_dispatch(active)
        self._end_tick(t0)

    def _decode_dispatch(self, active: List[int]) -> None:
        """Stage, dispatch and read one decode step for ``active``, then
        emit each slot's token."""
        # host-side staging: ONE mask/position build + one transfer per
        # tick, not one .at[i].set dispatch per active slot.
        mask_np = np.zeros((self.sc.max_batch,), bool)
        mask_np[active] = True
        toks = jnp.asarray(self.last_token[:, None])
        pos_v = jnp.asarray(np.where(mask_np, self.positions, -1)
                            .astype(np.int32))
        if self.sc.paged:
            with self._kernel_ctx():
                logits, self.cache = self._decode(
                    self.params, self.cache, toks, pos_v, self._pages_dev())
        else:
            logits, self.cache = self._decode(self.params, self.cache, toks,
                                              pos_v)
        with span("serve.sync"):
            nxt = np.asarray(self._sample(logits))
            lg_np = np.asarray(logits) if self.sc.record_logits else None
        self.last_token = np.where(mask_np, nxt,
                                   self.last_token).astype(np.int32)
        self.positions = np.where(mask_np, self.positions + 1,
                                  self.positions).astype(np.int32)
        with span("serve.emit"):
            for i in active:
                req = self.sched.slots[i].req
                tok = int(nxt[i])
                req.out_tokens.append(tok)
                if lg_np is not None:
                    req.logits.append(lg_np[i].copy())
                if self.sc.decode_sharing and \
                        not self.sched.check_twin_token(i):
                    # divergence (unreachable for greedy twins; ledger
                    # defense): break the link so the COW barrier
                    # privatizes any still-shared page before the next
                    # write.
                    self.sched.break_twins(i)
                if tok == self.sc.eos_id or \
                        len(req.out_tokens) >= self.sc.max_new_tokens:
                    self._finish(i)

    def _end_tick(self, t0: float) -> None:
        """Per-tick epilogue: oversized streams advance (one dispatch
        each, outside the slot budget), the prefetcher issues restores
        for next tick's blocked windows (AFTER dispatches, so evictions
        never steal pages a slot restored but had not yet used), and the
        tick-time EMA feeding the auto prefetch depth updates."""
        if self._oversized:
            self._oversized_tick()
        if self.tiered:
            with span("serve.tier"):
                self._tier_prefetch()
            dt = time.perf_counter() - t0
            self._tick_ema = (dt if self._tick_ema is None
                              else 0.9 * self._tick_ema + 0.1 * dt)

    # -- session API ---------------------------------------------------------
    def submit(self, req: Request) -> RequestHandle:
        """Queue ``req`` for ASYNC admission and return its handle
        immediately — no slot, page, or dispatch happens here.  The next
        ``tick()`` (or any handle-driven one) drains the pending queue in
        priority order (FIFO within a class).  ``submit_tick`` is stamped
        for the TTFT deadline ledger.  Raises RuntimeError once the
        engine has been ``drain()``ed."""
        if self._closed:
            raise RuntimeError(
                "ServingEngine is closed: submit() after drain() — "
                "construct a new engine (or use run() before draining)")
        with span("serve.submit", rid=req.rid):
            if req.submit_tick is None:
                req.submit_tick = self.tick_no
            self.sched.submit(req)
        return RequestHandle(self, req)

    def tick(self) -> None:
        """One externally-drivable engine step: advance the serving
        clock, drain pending admissions into free slots (at most ONE
        chunked-prefill wave, covering fresh and resumed prompts),
        then one decode dispatch for the prompt-complete slots.  Safe to
        call when idle (no-op dispatches are skipped)."""
        self.tick_no += 1
        with span("serve.tick"):
            self._admission_wave(_SchedQueue(self.sched))
            self.step()

    def drain(self) -> List[Request]:
        """Serve every outstanding submission to completion, then CLOSE
        the engine: subsequent ``submit()``/``run()`` raise.  Returns the
        requests finished during this call, in completion order."""
        start = len(self.completed)
        while self.sched.has_work() or self._oversized:
            self.tick()
        self._closed = True
        return self.completed[start:]

    def run(self, requests: List[Request]) -> List[Request]:
        """Serve ``requests`` to completion (compatibility shim: submit
        them all, then tick until idle — the engine stays OPEN, unlike
        ``drain()``).  Returns the requests finished during this call, in
        completion order (rejected requests appear with ``failed=True``
        and no output tokens)."""
        start = len(self.completed)
        for req in requests:
            self.submit(req)
        while self.sched.has_work() or self._oversized:
            self.tick()
        return self.completed[start:]
