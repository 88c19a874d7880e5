"""Serving configuration + request record.

``ServeConfig`` and ``Request`` validate themselves at construction
(``__post_init__``) so a bad pool geometry or a malformed priority /
deadline fails loudly at the API surface with the offending field named,
instead of deep inside the allocator or scheduler ticks later.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

import numpy as np


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 4
    max_prompt: int = 64            # prefill CHUNK budget per dispatch
    max_new_tokens: int = 32
    temperature: float = 0.0        # 0 = greedy
    eos_id: int = -1                # -1 = never
    seed: int = 0
    strict_iotlb: bool = True       # False: record fault, reject admission
    paged: bool = True              # page the KV cache (attention families)
    page_size: int = 16             # cache rows per page
    num_pages: Optional[int] = None  # pool pages; None = one full window
    #                                  per slot (contiguous-equivalent)
    pool_rows: Optional[int] = None  # alternative pool spec in cache ROWS;
    #                                  page_size must divide it exactly
    max_seq: Optional[int] = None   # per-slot row capacity (prompt+decode);
    #                                  None = max_prompt + max_new_tokens.
    #                                  Prompts longer than max_prompt (but
    #                                  within max_seq - max_new_tokens) are
    #                                  served via RESUMABLE chunked prefill.
    reserve_decode_pages: bool = True
    # True: admission ACCOUNTS for every in-flight request's worst-case
    #   decode growth (pages still materialize lazily at page boundaries,
    #   and early EOS releases the whole reservation), so the pool can
    #   never exhaust mid-decode and every admitted request completes.
    # False: overcommit — admission claims only prompt + first-decode
    #   pages and growth races the pool; mid-decode exhaustion triggers
    #   ``preemption``.
    preemption: str = "swap"
    # What overcommit does when growth finds the pool empty mid-decode:
    #   "swap":      evict the youngest resident request's pages (and
    #                recurrent state) to host memory and re-admit it later
    #                bit-for-bit — no request is lost;
    #   "terminate": the growing request dies with a capacity fault and
    #                its partial output (the pre-PR behavior).
    # Either way the fault path still fires when no victim can help.
    prefix_sharing: bool = True
    # Refcounted page tables: a new prompt sharing a whole-page prompt
    # prefix with a resident request maps the resident's physical pages
    # (copy-on-write at the first divergent page) and resumes prefill at
    # the first unshared row.  Engages only for fully-paged models —
    # recurrent state cannot be inherited — and is pure addressing:
    # logits are unchanged.
    decode_sharing: bool = False
    # Decode-token TWIN sharing: greedy requests with IDENTICAL full
    # prompts emit identical streams (same params, argmax sampling), so
    # their decode rows hold identical K/V — a follower slot maps its
    # twin leader's physical decode pages instead of growing its own
    # (both lanes write the same bytes, so no COW fires while the link
    # holds; the scheduler's equality ledger breaks the link — and the
    # normal COW barrier takes back over — at finish, swap-out, or any
    # divergence).  Paged + greedy only; off by default (pure addressing,
    # logits unchanged — the saving is pool pages, not compute).
    use_pallas_decode: bool = False
    # Route PAGE-STRIPED paged decode/resume attention through the fused
    # Pallas flash-decoding kernel (kernels/paged_flash_decode): page-
    # table translation + pool-page gather + per-logical-page flash
    # partials in ONE kernel instead of paged_gather materializing the
    # window in HBM, with non-resident/future pages skipped.  On a TPU
    # backend the kernel is compiled with Mosaic; on any other backend
    # (the CPU test suite) it runs the same grid through the Pallas
    # interpreter, which checks its logic but says nothing of its
    # speed.  The cross-shard combine is
    # unchanged: f32-pool logits are bit-identical to the lax path.
    # Inert when the pool is replicated (no 'pages' mesh striping in the
    # active rule table) — that path keeps its local gather.
    kv_format: str = "fp"
    # Page STORAGE format of the paged KV pool (core/pageformat):
    #   "fp":   pages stored at model dtype — the bit-exact reference path
    #           (logits identical to the pre-format engine at every shard
    #           count, through resume/COW/swap);
    #   "int8": pages stored as int8 with one f32 absmax scale per cache
    #           row, the scale pool a pool-shaped leaf beside the page
    #           table (so COW/swap/striping move scales with their pages);
    #   "int4": as int8, rows additionally packed 2 lanes/byte.
    # Quantization happens once at page-write time and dequantization
    # inside the flash partial (lax and Pallas kernel both) — no fp window
    # is materialized in HBM.  Quantized formats trade a benchmarked logit
    # error for 4-8x pool capacity at fixed memory.  Paged engine only.
    record_logits: bool = False     # keep per-token logits on each Request
    swap_budget_bytes: Optional[int] = None
    # Cap on host memory held by the swap queue (preempted requests park
    # their page contents + recurrent state host-side).  None = unbounded
    # (the pre-cap behavior).  When swapping a victim would push the
    # queue past the budget, that victim is not swappable: the growing
    # request takes the capacity-fault path instead (recorded as a
    # ``swap_budget`` fault; strict mode raises), so the host never holds
    # unbounded swapped state — unless ``spill_dir`` is set, in which
    # case the coldest swapped request spills to durable storage first.
    spill_dir: Optional[str] = None
    # Directory for spilling swapped requests through the checkpoint
    # layer (checkpoint/checkpoint.py) when ``swap_budget_bytes`` is hit:
    # host RAM becomes a CACHE over a durable tier instead of a hard cap.
    # The coldest queued SwappedRequest (the tail — re-admission is FIFO
    # from the head) writes its page/slot snapshots to an atomic
    # checkpoint and drops them from host memory; swap-in restores them
    # from disk bit-for-bit.  None = the pre-spill denial behavior.
    host_pool_pages: int = 0
    # Pages of the pinned HOST tier of a TWO-TIERED page pool (the
    # paper's small fast memory backed by large slow HyperRAM, at page
    # granularity).  0 = single-tier (the pre-tiering engine, all paths
    # bit-identical).  > 0: pool pressure EVICTS cold pages (least-
    # recently-dispatched slots first) to the host tier instead of
    # swapping a whole victim request, and each prefill-resume/decode
    # dispatch is GATED on its slot's attention window being device-
    # resident, with asynchronous prefetches issued ahead of the decode
    # window so transfers overlap compute.  Also admits OVERSIZED
    # requests (page demand beyond the device pool, up to the host
    # tier's capacity; fp format only) whose context lives host-side and
    # streams through the device per dispatch — contexts far larger than
    # the device pool complete instead of capacity-faulting.  Paged
    # engine only.  Logits stay bit-identical to the all-resident
    # engine: gating guarantees a dispatched window is fully resident,
    # and paging is pure addressing.
    prefetch_depth: Any = "auto"
    # Restores issued per tick ahead of the decode window when the pool
    # is tiered.  "auto": derived from a measured host<->device bandwidth
    # model (benchmarks/fig12_offload.measure_offload_bandwidth feeding
    # a transfers-per-tick cost model; conservative constants when the
    # benchmark module is unavailable).  An int pins the depth —
    # deterministic, for tests.
    transfer_ticks: Optional[int] = None
    # None: restores are REAL async jax.device_put transfers, applied
    # when the device signals ready (``is_ready``).  An int T models the
    # transfer latency instead: a restore completes exactly T ticks
    # after issue — deterministic stall/prefetch accounting for tests
    # and for pricing prefetch depth against a known latency.
    spec_draft: Optional[str] = None
    # SPECULATIVE DECODING drafter.  None = off (the plain decode loop).
    # "self" = the target model drafts for itself (same config + same
    # params — acceptance is 1.0 by construction, the deterministic
    # throughput leg: k+1 committed tokens per engine tick).  Any other
    # string names a model config from repro.configs (reduced via
    # reduce_config so the drafter stays small); the engine runs it per
    # session with its OWN params and its OWN paged cache/allocator —
    # draft pages never compete with (so can never evict) target pages —
    # proposes spec_k greedy tokens per tick, and the target verifies all
    # k+1 positions in ONE dispatch.  Rejected rows roll back at page
    # granularity (Allocator.truncate_rows).  With greedy sampling the
    # emitted stream is BIT-IDENTICAL to plain decode, whatever the
    # drafter proposes — acceptance only changes how many target
    # dispatches that stream costs.  Paged engine only; requires
    # temperature == 0 (greedy verification is an argmax equality);
    # attention + dense-MLP families only (MoE capacity routing couples
    # tokens within a dispatch, so k+1-row verify logits would not be
    # bitwise the 1-row decode logits; recurrent state has no pages to
    # roll back; MLA decode runs in absorbed space with its own op
    # order).
    spec_k: int = 4
    # Draft tokens proposed per engine tick when spec_draft is set;
    # clamped per slot to the tokens the request can still emit.
    spec_draft_pages: Optional[int] = None
    # Device pages of the DRAFT pool.  None = full (max_batch slots'
    # worth — the drafter can always follow).  Smaller values exercise
    # the degradation path: a slot whose draft-pool claim fails decodes
    # speculation-free (k_i = 0 — the verify dispatch degenerates to a
    # bitwise plain decode step), counted in tier_stats()['spec_disabled'].

    def __post_init__(self):
        def bad(field, why):
            raise ValueError(f"ServeConfig.{field} {why}")
        if self.swap_budget_bytes is not None and self.swap_budget_bytes <= 0:
            bad("swap_budget_bytes", "must be positive (None = unbounded), "
                f"got {self.swap_budget_bytes}")
        if self.max_batch <= 0:
            bad("max_batch", f"must be positive, got {self.max_batch}")
        if self.max_prompt <= 0:
            bad("max_prompt", f"must be positive, got {self.max_prompt}")
        if self.max_new_tokens <= 0:
            bad("max_new_tokens", "must be >= 1 (every request emits at "
                f"least the post-prompt token), got {self.max_new_tokens}")
        if self.temperature < 0:
            bad("temperature", f"must be >= 0, got {self.temperature}")
        if self.preemption not in ("swap", "terminate"):
            bad("preemption", f"must be 'swap' or 'terminate', "
                f"got {self.preemption!r}")
        from repro.core.pageformat import KV_FORMATS
        if self.kv_format not in KV_FORMATS:
            bad("kv_format", f"must be one of {KV_FORMATS}, "
                f"got {self.kv_format!r}")
        if isinstance(self.host_pool_pages, bool) or \
                not isinstance(self.host_pool_pages, int) or \
                self.host_pool_pages < 0:
            bad("host_pool_pages", "must be a non-negative int "
                f"(0 = single-tier pool), got {self.host_pool_pages!r}")
        if self.prefetch_depth != "auto" and (
                isinstance(self.prefetch_depth, bool)
                or not isinstance(self.prefetch_depth, int)
                or self.prefetch_depth <= 0):
            bad("prefetch_depth", "must be 'auto' or a positive int, "
                f"got {self.prefetch_depth!r}")
        if self.transfer_ticks is not None and (
                isinstance(self.transfer_ticks, bool)
                or not isinstance(self.transfer_ticks, int)
                or self.transfer_ticks <= 0):
            bad("transfer_ticks", "must be a positive int of engine ticks "
                f"(None = real async transfers), got {self.transfer_ticks!r}")
        if isinstance(self.spec_k, bool) or not isinstance(self.spec_k, int) \
                or self.spec_k < 1:
            bad("spec_k", f"must be an int >= 1, got {self.spec_k!r}")
        if self.spec_draft is not None:
            if not isinstance(self.spec_draft, str) or not self.spec_draft:
                bad("spec_draft", "must be 'self' or a model config name "
                    f"(None = speculation off), got {self.spec_draft!r}")
            if self.temperature > 0:
                bad("spec_draft", "requires greedy sampling (temperature "
                    "== 0): speculative verification commits by argmax "
                    f"equality, got temperature={self.temperature}")
        if self.spec_draft_pages is not None and (
                isinstance(self.spec_draft_pages, bool)
                or not isinstance(self.spec_draft_pages, int)
                or self.spec_draft_pages <= 0):
            bad("spec_draft_pages", "must be a positive int (None = a "
                f"full draft pool), got {self.spec_draft_pages!r}")
        if self.decode_sharing:
            if self.temperature > 0:
                bad("decode_sharing", "twin streams are only provably "
                    "identical under greedy sampling (temperature == 0), "
                    f"got temperature={self.temperature}")
            if self.spec_draft is not None:
                bad("decode_sharing", "incompatible with spec_draft: "
                    "speculative rollback truncates decode pages a twin "
                    "may still be reading")
        if not self.paged:
            if self.decode_sharing:
                bad("decode_sharing", "needs the paged engine "
                    "(paged=True): twins share physical decode PAGES")
            if self.spec_draft is not None:
                bad("spec_draft", "needs the paged engine (paged=True); "
                    "speculative rollback is page-granular "
                    "(Allocator.truncate_rows)")
            if self.host_pool_pages:
                bad("host_pool_pages", "needs the paged engine "
                    "(paged=True); only pool pages can tier to host")
            if self.kv_format != "fp":
                bad("kv_format", f"({self.kv_format!r}) needs the paged "
                    "engine (paged=True); only pool pages carry per-row "
                    "scales — the contiguous layout stores model dtype")
            if self.use_pallas_decode:
                bad("use_pallas_decode", "needs the paged engine "
                    "(paged=True); the contiguous layout has no paged "
                    "flash-decoding kernel")
            if self.max_seq is not None:
                bad("max_seq", "is only honored by the paged engine "
                    "(paged=True); the contiguous layout fixes slot "
                    "capacity at max_prompt + max_new_tokens")
            return
        if self.page_size <= 0:
            bad("page_size", f"must be positive, got {self.page_size}")
        if self.num_pages is not None and self.num_pages <= 0:
            bad("num_pages", f"must be positive, got {self.num_pages}")
        if self.pool_rows is not None:
            if self.num_pages is not None:
                bad("pool_rows", "and num_pages are two spellings of the "
                    "same pool — set only one")
            if self.pool_rows <= 0:
                bad("pool_rows", f"must be positive, got {self.pool_rows}")
            if self.pool_rows % self.page_size:
                bad("page_size", f"({self.page_size}) does not divide the "
                    f"pool (pool_rows={self.pool_rows})")
            self.num_pages = self.pool_rows // self.page_size
        if self.max_seq is not None and \
                self.max_seq < self.max_new_tokens + 1:
            bad("max_seq", f"({self.max_seq}) cannot hold even a 1-token "
                f"prompt plus max_new_tokens={self.max_new_tokens} rows")

    @property
    def slot_rows(self) -> int:
        """Per-slot logical row capacity."""
        if self.paged and self.max_seq is not None:
            return self.max_seq
        return self.max_prompt + self.max_new_tokens


@dataclasses.dataclass
class RouterConfig:
    """Policy knobs of the replica router (:mod:`repro.serve.router`).

    The router owns N :class:`~repro.serve.engine.ServingEngine`
    replicas (each with its own ServeConfig, allocator, and sharded
    pool) behind the session surface; every router<->replica interaction
    crosses the :mod:`repro.serve.wire` byte boundary."""
    replicas: int = 1
    routing: str = "affinity"
    # Placement policy for a fresh submission:
    #   "affinity":     prefix-affinity first — hash the prompt's
    #                   whole-page prefixes and route to the replica
    #                   already serving a prompt with the longest
    #                   matching prefix (COW prefix sharing is
    #                   per-replica, so co-locating shared-prompt
    #                   traffic keeps it working); least-loaded when no
    #                   prefix is known.
    #   "least_loaded": fewest live requests, lowest replica id on ties
    #                   (the default admission policy under affinity).
    #   "random":       seeded uniform choice — the baseline the router
    #                   benchmark compares affinity against.
    # With 1 replica every policy routes identically (replica 0), so a
    # 1-replica router stays bit-identical to a bare engine.
    migrate: bool = True
    # Cross-replica migration of PARKED requests: when a replica cannot
    # re-admit its coldest swapped snapshot (no free slot, or not enough
    # reserved-free pages) while another replica has both, the snapshot
    # crosses the wire (encode_snapshot/decode_snapshot) and resumes on
    # the other replica bit-for-bit.  False = parked work waits for its
    # home replica, the single-engine behavior.
    seed: int = 0                   # RNG seed for routing="random"

    def __post_init__(self):
        def bad(field, why):
            raise ValueError(f"RouterConfig.{field} {why}")
        if isinstance(self.replicas, bool) or \
                not isinstance(self.replicas, int) or self.replicas < 1:
            bad("replicas", f"must be an int >= 1, got {self.replicas!r}")
        if self.routing not in ("affinity", "least_loaded", "random"):
            bad("routing", "must be 'affinity', 'least_loaded', or "
                f"'random', got {self.routing!r}")
        if not isinstance(self.migrate, bool):
            bad("migrate", f"must be a bool, got {self.migrate!r}")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    priority: int = 0
    # Admission order and preemption victim selection are priority-aware:
    # higher admits first (FIFO within a class), lower is preempted first.
    # The default 0 everywhere degrades to pure FIFO / youngest-first —
    # bit-identical to the pre-priority engine.
    ttft_deadline: Optional[int] = None
    # TTFT deadline in ENGINE TICKS from submission: the first token must
    # be emitted within this many ``tick()`` calls.  Ticks, not wall
    # clock, keep the accounting deterministic.  None = best-effort.
    # The scheduler records the hit/miss; nothing is cancelled.
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    failed: bool = False            # rejected by IOTLB containment
    preempts: int = 0               # times swapped out mid-decode
    spec_drafted: int = 0           # draft tokens verified for this request
    spec_accepted: int = 0          # of those, committed to the stream
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)
    # per-emitted-token logits rows, populated when
    # ServeConfig.record_logits (bit-exactness tests / debugging)
    submit_seq: Optional[int] = None    # scheduler-stamped FIFO tie-break
    submit_tick: Optional[int] = None   # engine tick at submit()
    first_token_tick: Optional[int] = None  # engine tick of first token
    deadline_miss: Optional[bool] = None
    # None until resolved (or no deadline); then True/False.

    def __post_init__(self):
        def bad(field, why):
            raise ValueError(f"Request.{field} {why}")
        if isinstance(self.priority, bool) or \
                not isinstance(self.priority, int):
            bad("priority", f"must be an int, got {self.priority!r}")
        if self.ttft_deadline is not None and (
                isinstance(self.ttft_deadline, bool)
                or not isinstance(self.ttft_deadline, int)
                or self.ttft_deadline <= 0):
            bad("ttft_deadline", "must be a positive int of engine ticks "
                f"(None = no deadline), got {self.ttft_deadline!r}")

    @property
    def ttft_ticks(self) -> Optional[int]:
        """Ticks from submission to first token; None until emitted (or
        when the request never went through ``submit()``)."""
        if self.first_token_tick is None or self.submit_tick is None:
            return None
        return self.first_token_tick - self.submit_tick
