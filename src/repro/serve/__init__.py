"""Session-based continuous-batching serving runtime.

The package is four modules with a one-way dependency chain and one
concern each — the contract every change must preserve:

  * :mod:`repro.serve.config` — the API surface's data types.
    ``ServeConfig`` (pool geometry, preemption/sharing/swap-budget
    knobs) and ``Request`` (now carrying ``priority`` and a tick-based
    ``ttft_deadline``) validate themselves at construction, naming the
    offending field.
  * :mod:`repro.serve.scheduler` — POLICY.  Owns the PENDING QUEUE
    (``submit()`` lands requests here; admission order is highest
    priority first, FIFO within a class, head-of-line blocking on
    transient page exhaustion so big high-priority work is never
    starved by bypass), request metadata per slot, the swap queue and
    its host-byte footprint, the deadline hit/miss ledger, and every
    decision: which prompt rows each slot prefills this tick (resumable
    chunked prefill), which slots decode, who gets preempted (lowest
    priority first, youngest within a class), which resident prompt a
    new request may share a prefix with.  Never touches pages or device
    state.
  * :mod:`repro.serve.allocator` — ACCOUNTING, now PER SHARD.  Owns the
    physical page pool: ONE FREE LIST PER POOL SHARD (the pool is
    striped page-aligned over the seq mesh axes; shard ``s`` physically
    holds pages [s*N/S, (s+1)*N/S)), refcounted per-slot page tables
    (prefix sharing), copy-on-write barriers, worst-case growth
    reservations, and the hardware-faithful 32-entry LRU IOTLB whose
    windows are programmed against SHARD-LOCAL physical pages (phys
    base = the page's offset within its owning shard's stripe).  The
    contract: any physical page can back any logical page, so
    allocation BALANCES across shards (most-free shard first, ties to
    the lowest shard id) and exhaustion stays a POOL-level event — one
    shard running dry never faults while another still has pages;
    growth reservations are held against the pool, not a shard; a
    released page returns to its OWNING shard's free list; refcounts
    and COW semantics are shard-oblivious (a copy may cross shards —
    the engine applies it on device).  ``num_shards=1`` degrades to the
    single FIFO free list bit-for-bit.  Never decides policy and never
    touches device memory.
  * :mod:`repro.serve.engine` — EXECUTION + the client session.
    ``submit(req) -> RequestHandle`` queues a request asynchronously
    (no slot or dispatch yet) and returns a handle exposing ``status``,
    ``tokens_so_far``, an incremental ``stream()``, and a blocking
    ``result()``.  ``tick()`` is the externally-drivable step and
    guarantees: the serving clock advances by one, pending admissions
    drain into free slots first (swapped work re-enters before fresh
    submissions), and at most ONE chunked-prefill and ONE decode
    dispatch are issued — so prefill of the next wave overlaps decode
    of the current one.  ``run()`` is a thin submit-everything-then-
    tick shim (the engine stays open); ``drain()`` finishes all
    outstanding work and CLOSES the engine — ``submit()`` after
    ``drain()`` raises RuntimeError.

Every scheduling decision is pure addressing: logits are bit-identical
to the single-pass, never-preempted, unshared execution of the same
requests (tests/test_continuous_batching.py, tests/test_session_api.py
enforce this), and at uniform priority the session path reproduces the
legacy batch path token for token.  Under a seq-sharding rule table the
pool is additionally DISTRIBUTED: each pool leaf is placed page-striped
over the mesh (per-shard pool memory ~1/N) and KEPT there — the engine
re-pins pool leaves to their stripe after every host-side page edit
(COW privatize, swap-in restore), so no data-movement path silently
replicates the pool — paged decode/resume combine per-logical-page
flash partials across shards with pmax/psum, and the logits are
bit-identical at every shard count (tests/test_distributed_paging.py).

``ServeConfig.use_pallas_decode`` swaps the page-partials seam inside
that combine for the FUSED Pallas flash-decoding kernel
(:mod:`repro.kernels.paged_flash_decode`): page-table translation,
pool-page gather, and per-logical-page partials in one kernel — no
gathered window in HBM, non-resident/future pages skipped.  Off-TPU it
runs under the Pallas interpreter, and for f32 pools the served logits
are bit-identical to the lax path at every shard count
(tests/test_paged_flash_decode.py).

``ServeConfig.kv_format`` selects the pool's PAGE STORAGE FORMAT
(:mod:`repro.core.pageformat`) — the contract layered under everything
above:

  * ``"fp"`` is the BIT-EXACT REFERENCE: pages store model dtype,
    specs, traces, and logits are identical to the pre-format engine at
    every shard count, through multi-chunk resume, prefix-shared/COW
    tables, and swap cycles.
  * ``"int8"``/``"int4"`` are ERROR-BUDGETED: pages store packed
    integer rows plus one f32 absmax scale per cache row, quantized
    once at page-write time and dequantized inside the flash partial
    (lax and Pallas kernel alike — never an fp window in HBM).  The
    fp-vs-quantized logit error is budgeted in tests/test_quant_pool.py
    (the chip benchmark, ``benchmarks/chip/run.py``, serves fp pages
    only); what stays EXACT is addressing-invariance —
    a row's stored bytes depend only on its own fp values, so
    quantized logits are bitwise identical across chunking schedules,
    sharing on/off, swap cycles, shard counts, and lax-vs-kernel
    (tests/test_quant_pool.py).

Scales ride COW/swap/striping for free because they are ordinary
pool-shaped cache leaves (``(num_pages, page_size)`` f32 on the same
'pages' axis): the engine's pooled-leaf classification makes every
page-indexed data movement — COW privatize, swap-out/swap-in, stripe
re-pinning, per-page byte accounting (``_page_nbytes`` prices packed
rows + scales together) — move a page's scales with its rows.

``ServeConfig.host_pool_pages`` adds a SECOND TIER under the device
pool — the two-tier contract:

  * RESIDENCY is per logical page, one of three states the allocator
    tracks exactly: DEVICE (``page_table[slot, j] >= 0``), HOST
    (``host_table[slot, j] >= 0`` — bytes parked in a pinned host
    buffer per pool leaf), or IN-FLIGHT (``(slot, j)`` in
    ``alloc.inflight`` — a host->device restore issued but not landed;
    the destination page is claimed, the host slot still owns the
    bytes, so cancellation is always clean).  Exactly one state per
    page; eviction of an in-flight or shared (refcount > 1) page is
    refused at the allocator.
  * WHO MAY EVICT: only three engine sites, all page-granular and all
    coldest-slot-first / lowest-page-first — admission (making room
    for a new prompt), decode growth (``_grow_pages``), and the
    END-OF-TICK prefetcher balancing the pool.  Every eviction
    protects the tick's HELD set: slots whose next dispatch window is
    being prefetched plus every slot that passed this tick's residency
    gate — a gate-cleared dispatch can never lose a window page to a
    colder slot's restore.  Eviction snapshots the page's bytes
    (all pooled leaves — quantized rows and scales alike) as
    independent device slices and issues the device->host copy ASYNC,
    mirroring the restore path: the physical page is reusable
    immediately and the copy overlaps compute, while any reader of the
    host bytes (a restore of that host slot, a swap-out snapshot)
    forces the landing first — blocking only when the copy is
    genuinely unfinished (``evict_stalls`` counts those).
  * WHAT GATES A DISPATCH: residency of the slot's ATTENTION WINDOW.
    A resumed prefill chunk attends [0, off + chunk_len); a decode
    tick attends [0, pos] — ``alloc.blocked_pages`` over exactly those
    pages must be empty or the slot sits out the tick (stalled ticks
    are counted; all-blocked decode waits on the oldest transfer and,
    if both tiers are saturated, falls back to a whole-request swap).
    Restores land at tick START (``transfer_ticks`` models latency;
    ``None`` uses real async ``jax.device_put`` readiness); new
    prefetches are issued at tick END, deepest-need-first, up to
    ``prefetch_depth`` (``"auto"`` sizes the depth from measured
    host->device bandwidth x the decode-tick EMA).
  * The INVARIANT over all of it: fp logits stay bit-identical to the
    all-resident engine through arbitrary evict/prefetch/swap cycles,
    at every shard count, lax and Pallas (tests/test_tiered_pool.py);
    contexts larger than the device pool complete off the host tier
    (the streamed oversized path — token-exact vs the teacher-forced
    oracle), and ``swap_budget_bytes`` overflow spills parked
    snapshots through the checkpoint layer (``spill_dir``) instead of
    denying swaps.

``ServeConfig.spec_draft`` turns a slot's decode loop into SPECULATIVE
DECODING (:mod:`repro.serve.spec`) — the contract, layered on top of
paged greedy decode:

  * WHO OWNS WHAT: the engine owns the speculation POLICY — per-slot
    draft length (``spec_k``, clamped so a round never overruns
    ``max_new_tokens``), the single (B, k+1) VERIFY dispatch, and the
    accept/rollback arithmetic.  ``SpecDrafter`` owns draft-side
    EXECUTION: the draft model's own fp paged cache and
    ``PageAllocator`` over a SEPARATE pool (``spec_draft_pages``), so
    speculation can never evict, share, or COW a target page.  The
    drafter never mirrors prefill/swap machinery — before proposing it
    lazily re-prefills its cache from the target's committed stream,
    which uniformly covers fresh admissions, prefix-shared admissions,
    swap-ins, and the row a fully-accepted round leaves behind.
  * WHAT ROLLS BACK: pages, not rows.  A round commits the longest
    verified prefix, then ``Allocator.truncate_rows(slot, new_len)``
    releases every whole page past the last committed row — respecting
    refcounts (a prefix-shared page merely drops this slot's mapping)
    and every residency state (device, host, in-flight).  Rejected
    rows left on the kept boundary page are dead by masking: decode at
    position p never attends rows > p, and the rows are overwritten
    before the position reaches them.
  * WHY GREEDY OUTPUT IS BIT-IDENTICAL: the verify dispatch scores
    each candidate row with the decode step's OWN attention
    computation (per-row ``lax.map`` at Sq=1 — see
    ``_verify_attention_local``; on the striped pool the shard_map
    body is already shared), so the logits at every accepted position
    are BITWISE the logits plain decode would have produced there, and
    the commit loop applies decode's exact emit/terminate rule.
    Emitted tokens AND recorded logits are therefore identical to the
    plain engine whatever the drafter proposes — through chunked
    prefill, COW sharing, swap and tiered-pool cycles, fp and
    quantized pages, shard counts, lax and Pallas
    (tests/test_spec.py).  A drafter only moves THROUGHPUT: k accepted
    drafts + 1 verified token per engine tick instead of 1.
  * DEGRADATION: when the draft pool cannot back a slot, that slot's
    drafter goes dead and the slot decodes speculation-free (the k=0
    verify row is bitwise a plain decode step) — counted once in
    ``tier_stats()['spec_disabled']``, re-armed on release.  Supported
    architectures are vetted (``vet_spec_arch``): attention blocks
    only — MoE capacity ranking and recurrent state couple tokens
    across a dispatch and would break the bitwise contract.

``ServeConfig.decode_sharing`` extends prefix sharing to DECODE pages:
greedy requests with identical full prompts emit identical streams, so
the scheduler twins them — the follower maps the leader's decode page
at each growth boundary (one physical write serves both), the COW
barrier stands down while the twin link holds, and the link breaks —
restoring normal COW — the moment either side finishes, swaps, or (by
the per-token equality ledger) diverges.  Mutually exclusive with
``spec_draft``: speculative rollback truncates pages a twin may still
read.

Above the single engine sits the REPLICA TIER — two modules, same
one-way layering (wire depends on config only; router depends on both
plus the engine):

  * :mod:`repro.serve.wire` — the BYTE BOUNDARY.  A versioned,
    backend-agnostic frame (magic + version + message kind + sorted-key
    JSON meta + raw C-order array blobs) for the four messages that
    ever cross between router and replica: REQUEST (submission),
    STATUS (per-request token/logits/terminal deltas), SNAPSHOT (the
    swap-out serialization — pool rows, slot rows, and quantized-scale
    leaves ride as ordinary arrays), STATS (load/capacity telemetry).
    Decoding is strict: wrong magic/version/kind, malformed meta,
    short or trailing bytes all raise ``WireError`` — never a
    half-decoded message.
  * :mod:`repro.serve.router` — the REPLICA TIER's policy + session.
    ``Router`` owns N engine replicas (each with its OWN ServeConfig,
    allocator, and sharded pool) behind the unchanged session surface:
    ``submit(req) -> RouterHandle``, ``tick()`` fans out one engine
    tick per replica then syncs status deltas, ``drain()`` finishes
    and closes all.  WHO ROUTES: the router, never an engine —
    prefix-affinity by default (whole-page prompt-prefix hashes map to
    the replica already serving the longest match, so per-replica COW
    prefix sharing keeps working across the fleet), least-loaded when
    no prefix is known, seeded random as the baseline.  WHAT CROSSES
    THE WIRE: everything — each router<->replica interaction is wire
    bytes even in-process (``ReplicaEndpoint`` is the stand-in a real
    RPC worker replaces), so client and engine never share a mutable
    Request.  MIGRATION INVARIANTS: a parked request moves replicas
    only as a wire SNAPSHOT, only when its home cannot re-admit it (no
    free slot or too few reserved-free pages) while the receiver has
    both and no queue of its own; the receiver re-stamps the
    engine-local admission order and re-enters through the ordinary
    swap-in path, and because status deltas sync BEFORE migration the
    token stream resumes bit-for-bit.  With 1 replica the router is
    BIT-identical (tokens + logits) to a bare engine at uniform
    priority (tests/test_router.py).
"""
from repro.serve.config import Request, RouterConfig, ServeConfig  # noqa: F401
from repro.serve.engine import RequestHandle, ServingEngine  # noqa: F401
from repro.serve.router import ReplicaEndpoint, Router, RouterHandle  # noqa: F401
