"""Continuous-batching serving scheduler over one shared KV page pool.

The paper's tuner wants the *aggregate* workload, not one request: this
module is the layer that owns a shared hybrid-memory pool across many
in-flight requests and feeds online Cori from the merged traffic.

  * ``ContinuousBatcher`` -- the model-backed scheduler: requests join the
    running batch between decode steps (admission is per-step, prefills
    of a step's joiners run as ONE packed forward pass, and each
    request's KV occupies whole bucket-rounded page runs of the shared
    pool, so joins are page-aligned by construction), decode runs over
    the whole request set, and requests retire on EOS or length,
    returning their pages.  In **fully-paged mode** (the default) the
    shared pool is the ONLY state store for EVERY cache geometry:
    plain/local attention gathers (k, v) token pages, MLA gathers
    compressed (ckv, krope) pages, recurrent cells read/write one packed
    state page per request, prefix architectures map shared read-only
    prefix pages prefilled once -- all through the pool's ``slot_of``
    tables, and the per-page masses feeding the tuner come from ALL
    state-bearing layers of that same decode step.
  * ``TrafficScheduler`` -- the model-free twin for traffic simulation:
    each request is a synthetic per-step page-mass pattern
    (``repro.memtier.workload``), so thousands of scheduler steps replay
    without touching KV bytes.  Same admission, bucket-rounded
    allocation, merge and retirement path.
  * ``TrafficMonitor`` -- the traffic-level monitor: merges per-request
    page masses into the global logical-page ID space and drives ONE
    ``TieringManager`` (+ optional ``OnlineTuner``) for the whole mix.

Invariants (pinned by tests/test_sched.py):

  * **Page-ID recycling contract.**  A retiring request's global IDs are
    released *everywhere* -- pool slots, manager hotness, the tuner's
    reuse collector -- before the allocator may recycle them, so a
    recycled ID starts cold and never inherits the old owner's reuse
    chain (``TrafficMonitor.release`` is the single choke point).
  * **Active-mask semantics.**  Tiering ranks only pages of in-flight
    requests (``pools.allocated_mask``); bucket-tail pages a request
    holds but has not yet written are allocated (and thus rankable) but
    carry no mass, so they tier out naturally.
  * **Token parity.**  A request's emitted stream is identical to
    per-request ``engine.generate`` with the same prompt/key -- across
    dense vs fully-paged decode, staggered admission, batched prefill,
    row reuse and temperature sampling.
  * **Residency before decode.**  In fully-paged mode every page the
    step's attention can touch is made HBM-resident first
    (``ensure_resident``, charged as on-demand fetch misses); the kernel
    never gathers a host-only page.  Admission is gated so the in-flight
    footprint fits the HBM slot pool.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cori
from repro.core.traffic import RequestSpec
from repro.ft.inject import NULL_PLAN
from repro.ft.monitor import StepTimer
from repro.kernels import ops
from repro.memtier import workload as W
from repro.memtier.tiering import (PAGE_DROP, SharedPagedPools,
                                   TieringManager, bucket_pages,
                                   write_pages_batched)
from repro.models import model as mdl
from repro.obs import telemetry as _obs
from repro.serve import engine as E
from repro.serve.pipeline import DecisionWorker

__all__ = ["Request", "TrafficMonitor", "ContinuousBatcher",
           "TrafficScheduler", "WORKLOAD_KINDS", "decode_macro"]

# The served programs take ``params`` as an argument -- closed over, every
# weight would be embedded in the program as a constant -- and ``cfg`` as
# a static one, so every batcher in the process shares one compiled
# program per shape.  The kv pytree is dead after a decode call (the
# caller publishes the returned one): donating it lets XLA update the
# pool buffers in place instead of copying the whole layered store.
_prefill_batched = jax.jit(mdl.prefill_batched, static_argnums=(1,))
_prefill_chunk = jax.jit(mdl.prefill_chunk, static_argnums=(1,),
                         static_argnames=("start",))
_decode_paged = jax.jit(mdl.decode_step_paged, static_argnums=(1,),
                        static_argnames=("page_size", "impl"),
                        donate_argnums=(2,))
#: the macro-step decode program (one launch per movement period)
decode_macro = jax.jit(mdl.decode_macro_step, static_argnums=(1,),
                       static_argnames=("n_steps", "page_size", "impl"),
                       donate_argnums=(2,))


# ---------------------------------------------------------------------------
# traffic-level monitor: merged masses -> one manager/tuner
# ---------------------------------------------------------------------------


class TrafficMonitor:
    """Merges per-request page masses into the global page-ID space and
    feeds one ``TieringManager`` + optional ``OnlineTuner`` for the whole
    traffic mix -- the aggregation point between the scheduler and Cori."""

    def __init__(self, pools: SharedPagedPools, manager: TieringManager,
                 tuner: Optional[cori.OnlineTuner] = None):
        if manager.n != pools.n_logical:
            raise ValueError("manager and pools disagree on the logical "
                             f"page space ({manager.n} vs {pools.n_logical})")
        self.pools = pools
        self.manager = manager
        self.tuner = tuner

    def merge(self, contributions: Sequence[Tuple[np.ndarray, np.ndarray]]
              ) -> np.ndarray:
        """Scatter per-request (gids, local_mass) rows into one global
        f32[n_logical] mass vector (max-merge: a page is as hot as its
        hottest accessor, matching the engine's batch reduction)."""
        mass = np.zeros(self.pools.n_logical, np.float32)
        for gids, local in contributions:
            np.maximum.at(mass, np.asarray(gids, np.int64),
                          np.asarray(local, np.float32)[: len(gids)])
        return mass

    def on_step(self, global_mass: np.ndarray,
                n_active: Optional[float] = None, *,
                n_tokens: Optional[int] = None,
                force_tier: bool = False, fetched: int = 0,
                degraded: int = 0) -> int:
        """Feed one scheduler step's merged masses: accounting, periodic
        tiering over the shared pool, and the closed tuning loop.  Returns
        the tiering period now in force.

        With ``n_active`` the tuner is fed the *per-request* step cost.
        Aggregate cost scales with however many requests happen to be in
        flight, so a burst of arrivals (or a drain of retirements) looks
        exactly like workload drift and makes the tuner churn through
        re-profiles on a perfectly stable mix; per-request cost is the
        load-invariant serving metric the drift detector should watch.
        ``n_tokens`` declares how many token-steps this feed spans (the
        macro length): the tuner's clock and reuse gaps advance by it and
        the manager's service-cost accounting scales by it, keeping the
        derived period in the token-step units it is actuated in and the
        per-token cost comparable across period lengths.  ``fetched``
        demand-fetch page misses are charged INSIDE the cost window (the
        macro path prefetches its horizon up front -- those misses are
        the price of the current period and must reach the tuner).  They
        are priced at ``fetch_cost``, not ``miss_penalty``: the pools
        batch every ``ensure_resident`` call's host->HBM copies into one
        gathered transfer, so a prefetched page is cheaper than the
        synchronous mid-decode stall ``miss_penalty`` models.  Every
        fetch path routes through here so the pricing cannot fork.
        ``force_tier`` tiers regardless of the step cadence.

        The tuner's adversarial-traffic defenses (cost-spike guardrail,
        variance-scaled trial windows, warm re-tunes -- see
        ``OnlineTuner``) apply unchanged here: both the per-token and
        the macro path route every cost observation through
        ``tuner.on_step``, so a flash crowd poisoning a TRIAL mid-sweep
        aborts to the last-good period on either path.  A non-finite
        merged mass (a NaN'd attention row) is clamped to zero before it
        can corrupt the reuse collector's accessed-set thresholding."""
        mgr = self.manager
        if not np.all(np.isfinite(global_mass)):
            global_mass = np.nan_to_num(global_mass, nan=0.0,
                                        posinf=0.0, neginf=0.0)
        before = mgr.modeled_time
        if fetched:
            mgr.misses += fetched
            mgr.modeled_time += fetched * mgr.cfg.fetch_cost
        if degraded:
            # retry-exhausted fetches lost the batched-transfer discount:
            # top their price up from fetch_cost to the synchronous
            # miss_penalty, INSIDE the tuner's window, so Cori re-plans
            # around the failing pages instead of seeing them as cheap
            mgr.modeled_time += degraded * max(
                0.0, mgr.cfg.miss_penalty - mgr.cfg.fetch_cost)
        rec = _obs.RECORDER
        with rec.span("tier.account"):
            mgr.on_step(global_mass, self.pools.resident_mask,
                        weight=float(n_tokens or 1))
        with rec.span("tier.maybe_tier"):
            mgr.maybe_tier(self.pools, active=self.pools.allocated_mask,
                           force=force_tier)
        if self.tuner is not None:
            cost = mgr.modeled_time - before
            if n_active is not None:
                cost /= max(1, n_active)
            with rec.span("tuner.on_step"):
                mgr.set_period(self.tuner.on_step(global_mass, cost=cost,
                                                  dt=n_tokens or 1))
        return mgr.period

    def on_macro_step(self, global_mass: np.ndarray,
                      n_active: Optional[float] = None,
                      n_tokens: int = 1, fetched: int = 0,
                      degraded: int = 0) -> int:
        """Feed one *macro step* (one movement period) of merged masses.

        The macro-step serving loop wakes the host exactly once per
        period, so this is one accounting step, a FORCED tier (every
        wakeup is a tiering boundary -- the period knob now controls the
        macro length itself, not a sub-cadence), and one tuner update
        spanning ``n_tokens`` token-steps: the tuner's reuse gaps and
        trial windows keep counting TOKENS (quantised to macro
        boundaries), so the period it derives means the same thing it
        does on the per-token path.  ``n_active`` is the mean number of
        in-flight requests over the macro (per-request cost
        normalisation, as on_step); ``fetched`` is the macro's up-front
        demand-fetch count, charged inside the tuner's cost window."""
        return self.on_step(global_mass, n_active, n_tokens=n_tokens,
                            force_tier=True, fetched=fetched,
                            degraded=degraded)

    def plan_step(self, global_mass: np.ndarray,
                  n_active: Optional[float] = None, *,
                  n_tokens: int = 1, fetched: int = 0,
                  degraded: int = 0,
                  resident: Optional[np.ndarray] = None,
                  n_free: int = 0,
                  active: Optional[np.ndarray] = None,
                  planes: int = 2):
        """The *worker half* of a pipelined macro boundary: identical
        accounting to ``on_macro_step`` (NaN clamp, fetch charge, manager
        feed, tuner update) except tiering stops at ``plan_tier`` -- no
        pool mutation -- so the whole call can run on the background
        ``DecisionWorker`` while the next scan is in flight.

        ``resident``/``n_free``/``active`` are snapshots the dispatch
        thread took at the boundary (the pools move on between plan and
        apply; ``TieringManager.apply_plan`` revalidates against the live
        state).  Thread-safety comes from the worker's strict-alternation
        protocol, not locks: the dispatch thread only touches the
        manager/tuner between ``wait`` and the next ``submit``, when the
        worker is idle.  Returns ``(period, plan)`` where ``plan`` is the
        ``(bring, evict)`` pair for ``apply_decision``."""
        mgr = self.manager
        if not np.all(np.isfinite(global_mass)):
            global_mass = np.nan_to_num(global_mass, nan=0.0,
                                        posinf=0.0, neginf=0.0)
        before = mgr.modeled_time
        if fetched:
            mgr.misses += fetched
            mgr.modeled_time += fetched * mgr.cfg.fetch_cost
        if degraded:
            mgr.modeled_time += degraded * max(
                0.0, mgr.cfg.miss_penalty - mgr.cfg.fetch_cost)
        mgr.on_step(global_mass, resident, weight=float(n_tokens or 1))
        plan = mgr.plan_tier(resident, n_free, active=active,
                             planes=planes, force=True)
        if self.tuner is not None:
            cost = mgr.modeled_time - before
            if n_active is not None:
                cost /= max(1, n_active)
            mgr.set_period(self.tuner.on_step(global_mass, cost=cost,
                                              dt=n_tokens or 1))
        return mgr.period, plan

    def apply_decision(self, plan) -> None:
        """The *dispatch half*: actuate a worker-planned tiering move on
        the live pools (``apply_plan`` revalidates each page first)."""
        if plan is not None:
            self.manager.apply_plan(self.pools, *plan)

    def release(self, gids: np.ndarray) -> None:
        """Retire a request's pages everywhere: pool slots freed, manager
        hotness cleared, reuse-collector entries invalidated (a recycled
        global ID must not inherit the old owner's reuse chain)."""
        self.manager.release(gids)
        if self.tuner is not None:
            self.tuner.forget_pages(gids)
        self.pools.free(gids)


# ---------------------------------------------------------------------------
# model-backed continuous batcher
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    """One serving request and its in-flight state."""

    rid: int
    prompt: np.ndarray                 # int32[plen]
    max_new_tokens: int
    eos_id: Optional[int] = None
    temperature: float = 0.0
    key: Optional[jax.Array] = None    # defaults to PRNGKey(0), as generate()
    #: deadline in scheduler steps from submission; None = no deadline.
    #: A request whose deadline passes while still QUEUED is shed
    #: (status "expired"); once admitted it always runs to completion
    #: (aborting mid-decode would break the token-parity contract)
    ttl_steps: Optional[int] = None
    # -- runtime state (owned by the batcher) --
    #: typed terminal status: "completed" | "shed" | "expired"
    status: str = ""
    deadline_step: int = -1            # absolute step the ttl resolves to
    row: int = -1
    gids: Optional[np.ndarray] = None  # pages the request OWNS (kv + state)
    n_pages: int = 0                   # exact page footprint
    n_alloc: int = 0                   # bucket-rounded pages actually held
    # paged mode: the pages the request's table maps (shared prefix pages
    # + own kv pages + state page) and their columns in the mass rows --
    # a superset of ``gids``: shared pages are mapped, never owned
    table_gids: Optional[np.ndarray] = None
    mass_cols: Optional[np.ndarray] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    _key: Optional[jax.Array] = None
    _i: int = 0                        # decode iterations done
    # pipelined admission: the lazily-sampled first token (a [1] device
    # array still chained behind the prefill) whose host bookkeeping --
    # the int() download, the tokens append, the emit -- is deferred to
    # the next macro boundary so activation never blocks the launch
    _first_tok: object = None
    _t_submit: float = 0.0             # wall clock at submit (queue wait)
    # preemption freeze-frame: the row state saved when the request is
    # frozen (pages stay allocated host-side; _key/_i live on the
    # request already, so reactivation is a pure row re-install)
    _frozen_pos: int = 0
    _frozen_tok: int = 0

    @property
    def total_len(self) -> int:
        return len(self.prompt) + self.max_new_tokens


@dataclasses.dataclass
class _PendingAdmit:
    """A reserved-but-not-yet-active admission of the pipelined loop:
    the row and pages are held (the HBM admission gate counts them) and
    the prefill is dispatched -- packed before the same boundary's
    launch, or chunk-by-chunk inside overlap windows for long prompts --
    after which the row activates lazily (the first-token sample chains
    behind the prefill; only its bookkeeping waits for a boundary)."""

    req: Request
    plen: int
    chunked: bool = False
    past: object = None          # accumulated chunk cache (chunked only)
    next_start: int = 0          # absolute position of the next chunk
    chunk_idx: int = 0
    logits: object = None        # lazy [1, 1, V] first-token logits
    ready: bool = False
    t_reserved: float = 0.0      # wall clock at reservation


class ContinuousBatcher:
    """Continuous batching: a fixed-capacity request-set decoded together.

    ``max_active`` rows are decoded together; requests are admitted into
    free rows between decode steps and retired on EOS or length (pages
    released).  A step's joiners are prefilled as ONE packed right-padded
    forward pass (``model.prefill_batched``) whenever the architecture
    has no recurrent state.  Per-request sampling keys follow exactly
    ``engine.generate``'s schedule, so a request's token stream is
    identical to running ``generate`` alone with the same prompt/key --
    the property the traffic benchmark pins down.

    Decode data paths:

    * **Fully paged** (``paged=True``, the default whenever a monitor is
      attached -- every registered cache geometry is expressible on the
      shared slot pool): the shared pool is the ONLY state store.  Each
      request's token pages occupy a bucket-rounded run of global pages
      (``memtier.bucket_pages``); every state-bearing layer decodes
      through the pool's ``slot_of`` tables (``model.decode_step_paged``)
      with its own leaf geometry -- (k, v) token rows for plain/local
      attention, compressed (ckv, krope) rows for MLA, one packed state
      page per request for recurrent cells (mapped at a fixed table
      column past every token position, so attention never reads it),
      and ``prefix_len`` architectures map shared read-only prefix pages
      that are prefilled ONCE at batcher construction instead of
      re-prefilled per admission.  There is no dense per-row ``max_len``
      cache at all; peak cache memory is the sum of the in-flight
      bucket-rounded footprints plus the one shared prefix run.  The
      per-page masses feeding the tuner come from ALL state-bearing
      layers of the decode step itself (head-normalised attention mass,
      a unit state-page touch per recurrent layer, layer-averaged) --
      the true aggregate traffic, not a one-layer sample.  Before each
      step, every page the decode can touch is demand-fetched into HBM
      (charged as misses); admission is gated so the in-flight exact
      footprint fits the HBM slot pool.

      By default the paged path runs **macro-step decode** (``macro=True``):
      one device launch per movement period (``model.decode_macro_step``
      -- on-device sampling, EOS/length masking, mass accumulation), so
      the host only intervenes at tiering boundaries: tables upload once
      per macro, ``(tokens, summed mass, finished flags)`` download once,
      and the monitor merge collapses to one call per period.
      ``macro=False`` keeps the per-token paged loop (the measured
      baseline); ``macro_steps`` pins a fixed macro length instead of
      tracking the manager's live Cori period.

    * **Dense** (``paged=False``; the measured baseline): ``max_active``
      rows share one packed cache of ``max_len`` positions, the monitor
      layer's masses are recomputed per step (``engine.make_monitor``)
      and, with ``mirror_pages=True``, that layer's pages are
      write-through mirrored into the shared pool for ``paged_context``.

    With ``pipeline=True`` (macro mode only) the loop runs as a software
    pipeline: each scheduler step completes the *previous* macro, then
    launches the next one and does the boundary's host work -- tiering
    decision apply, admission prefill, next-horizon prefetch, table
    staging -- in the **overlap window** behind the in-flight scan
    (docs/serving.md, "Pipelined macro loop").  Tiering/tuner decisions
    move to a background ``DecisionWorker`` and land one boundary late
    (the stale-by-one contract); ``admit_chunk_tokens`` bounds how much
    long-prompt prefill any single window dispatches (the SLO admission
    knob; ``None`` keeps whole-prompt packed admission).  Overlap only
    changes *when* work happens, never *what* is computed: the emitted
    streams are token-identical to the synchronous loop.  In pipelined
    mode ``paged_context`` probes and manager/tuner reads are only safe
    between ``step()`` calls after ``run()`` returned (the worker may be
    mid-decision otherwise); call ``close()`` to tear the worker down.

    ``cond`` ([T, d] or [1, T, d]) is the serving session's shared
    cross-attention conditioning (musicgen-style archs); ``extra_embeds``
    ([prefix_len, d] or [1, prefix_len, d]) is the shared prefix, required
    whenever ``cfg.prefix_len > 0``.
    """

    def __init__(self, params, cfg, *, max_active: int = 4,
                 max_len: int = 128, page_size: int = 16,
                 monitor: Optional[TrafficMonitor] = None,
                 mirror_pages: bool = False,
                 paged: Optional[bool] = None,
                 macro: Optional[bool] = None,
                 macro_steps: Optional[int] = None,
                 pipeline: bool = False,
                 admit_chunk_tokens: Optional[int] = None,
                 cond=None, extra_embeds=None,
                 fault_plan=None,
                 max_queue: Optional[int] = None,
                 watchdog_s: Optional[float] = None,
                 max_worker_restarts: int = 3):
        self.params, self.cfg = params, cfg
        self.page_size = page_size
        self.max_len = -(-max_len // page_size) * page_size
        self.max_active = max_active
        self.prefix = cfg.prefix_len or 0
        self.monitor = monitor
        self._has_state = mdl.has_state_pages(cfg)
        self._has_attn = mdl.has_attention(cfg)
        self._state_extra = 1 if self._has_state else 0
        # one extra table column holds the state page, PAST every token
        # position (col * page_size >= any length), so attention kernels
        # can never gather it
        self.n_row_pages = self.max_len // page_size + self._state_extra
        can_page = monitor is not None and mdl.paged_supported(cfg)
        self.paged = can_page if paged is None else bool(paged)
        if self.paged and not can_page:
            raise ValueError("fully-paged decode needs a TrafficMonitor "
                             f"({cfg.name})")
        if self.prefix % page_size:
            raise ValueError(f"prefix_len {self.prefix} must be page-"
                             f"aligned (page_size {page_size}) so request "
                             "pages start on a page boundary")
        if self.prefix and self._has_state:
            raise ValueError("shared prefix pages cannot seed recurrent "
                             "state (no such architecture is registered)")
        self._prefix_pages = self.prefix // page_size
        if self.prefix and extra_embeds is None:
            raise ValueError(f"{cfg.name}: serving needs the shared prefix "
                             "embeddings (extra_embeds [prefix_len, "
                             "d_model])")
        self._ex = None
        if extra_embeds is not None:
            ex = jnp.asarray(extra_embeds)
            self._ex = ex[None] if ex.ndim == 2 else ex
        self._cond = None
        self._cond_rows = None
        if cond is not None:
            c = jnp.asarray(cond)
            self._cond = c[None] if c.ndim == 2 else c
            self._cond_rows = jnp.broadcast_to(
                self._cond, (max_active,) + self._cond.shape[1:])
        # macro-step decode: the default hot loop whenever fully paged --
        # the host wakes once per movement period (``macro_steps`` pins a
        # fixed macro length; None tracks the manager's live Cori period).
        # ``macro=False`` keeps the per-token paged loop (the benchmark
        # baseline the macro path is measured against).
        self.macro = self.paged if macro is None else bool(macro)
        if self.macro and not self.paged:
            raise ValueError("macro-step decode runs on the fully-paged "
                             "path only")
        self.macro_steps = macro_steps
        # pipelined macro loop (opt-in): the synchronous loop stays the
        # measured baseline and keeps its pinned per-step contracts
        self.pipeline = bool(pipeline)
        if self.pipeline and not self.macro:
            raise ValueError("pipeline=True needs macro-step decode (the "
                             "overlap window is the macro's flight time)")
        self.admit_chunk_tokens = admit_chunk_tokens
        if admit_chunk_tokens is not None:
            if admit_chunk_tokens < 1:
                raise ValueError("admit_chunk_tokens must be >= 1")
            # page-aligned chunks: every pool page is written by exactly
            # one chunk's scatter
            self._chunk_width = -(-admit_chunk_tokens // page_size) \
                * page_size
        else:
            self._chunk_width = None
        # the write-through mirror needs the LEGACY single-layer arrays;
        # a layered-only pool is physical but has no k_host/k_hbm pair
        self.mirror_pages = (not self.paged) and mirror_pages \
            and monitor is not None and monitor.pools.k_host is not None
        self._batched_prefill = mdl.batched_prefill_supported(cfg)
        if self._batched_prefill:
            # admission prefills run jitted; prompt lengths are
            # pow2-bucketed in _prefill so the compile cache is bounded
            # (causal padding cannot change valid rows)
            self._prefill_fn = functools.partial(_prefill_batched, params,
                                                 cfg)

        # macro-launch straggler detection (the serving twin of the
        # training loop's step timer); its name routes flags and the
        # step-time histogram into the flight recorder
        self.macro_timer = StepTimer(name="serve.macro")

        self.tok = jnp.zeros((max_active, 1), jnp.int32)
        self.pos = jnp.zeros((max_active,), jnp.int32)
        #: the last packed prefill's expert counts (device int32[2])
        self._prefill_moe = None
        self.rows_free = list(range(max_active - 1, -1, -1))
        self.active: Dict[int, Request] = {}
        self.queue: "collections.deque[Request]" = collections.deque()
        self.step_idx = 0
        self.completed: List[Request] = []

        # -- overload-safety machinery (docs/robustness.md) --
        #: deterministic fault-injection plan; inert by default
        self.fault_plan = fault_plan if fault_plan is not None else NULL_PLAN
        if monitor is not None:
            monitor.pools.fault_plan = self.fault_plan
        #: bounded submit queue: a submit past this depth is shed
        #: immediately (status "shed") instead of queueing unboundedly
        self.max_queue = max_queue
        #: DecisionWorker watchdog: how long a boundary may wait for the
        #: background decision before declaring the worker hung, falling
        #: back to a synchronous decision and restarting it.  None keeps
        #: the untimed wait (the fault-free default)
        self.watchdog_s = watchdog_s
        self.max_worker_restarts = max_worker_restarts
        self._worker_restarts = 0
        self._worker_degraded = False   # restarts exhausted: stay sync
        #: live-epoch guard: bumped on every worker restart so a zombie
        #: worker thread that wakes after being abandoned sees a stale
        #: epoch in its payload and never touches the manager/tuner
        self._live_epoch = 0
        self._last_payload: Optional[Dict] = None
        #: preemption-frozen requests, FIFO (oldest reactivates first)
        self._frozen: List[Request] = []
        self.preemptions = 0
        self.shed = 0                   # queue-full sheds
        self.expired = 0                # deadline expiries while queued

        # epoch-keyed device table cache: (pools.slot_epoch, _rows_epoch)
        # unchanged => the staged upload is reused (a buffer swap), so a
        # boundary where tiering moved nothing skips the rebuild+upload
        self._rows_epoch = 0
        self._tables_key = None
        self._tables_dev = None
        # pipelined-loop state (inert when pipeline=False)
        self._inflight: Optional[Dict] = None
        self._pending_admits: List[_PendingAdmit] = []
        self._prefetched_next = 0
        self._decision_gen: Optional[int] = None
        self._decision_worker = (DecisionWorker(self._plan_decision)
                                 if self.pipeline else None)

        if self.paged:
            pools = monitor.pools
            if pools.kv_layers is None:
                pools.attach_layered(mdl.slot_leaf_specs(cfg, page_size),
                                     dtype=jnp.float32)
            self.cache = None
            self._hbm_need = 0     # exact pages the in-flight set can touch
            self._gid_tables = np.full((max_active, self.n_row_pages), -1,
                                       np.int32)
            # recurrent archs: every row's state page sits at the fixed
            # last table column (see n_row_pages above)
            self._state_cols = (jnp.full((max_active,), self.n_row_pages - 1,
                                         jnp.int32)
                                if self._has_state else None)
            self._paged_fn = functools.partial(_decode_paged, params, cfg,
                                               page_size=page_size)
            # shared read-only prefix: allocated + prefilled ONCE; every
            # request's table maps these pages, admission never
            # re-prefills the prefix
            self._prefix_gids: Optional[np.ndarray] = None
            if self._prefix_pages:
                g = pools.alloc(self._prefix_pages, -1)
                if g is None:
                    raise ValueError(
                        f"the logical space ({pools.n_logical}) cannot hold "
                        f"the {self._prefix_pages} shared prefix pages")
                self._prefix_gids = g
                self._hbm_need += self._prefix_pages
                self._prefill_prefix_pages()
        else:
            # prefill produces float32 caches on this substrate; the packed
            # cache must match or row writes would silently downcast
            self.cache = mdl.init_cache(cfg, max_active, self.max_len,
                                        dtype=jnp.float32)
            self._step_fn = jax.jit(
                lambda c, t, p, cond=None: mdl.decode_step(
                    params, cfg, c, t, p, cond=cond))
        self._mon_fn = (E.make_monitor(params, cfg, page_size,
                                       self.n_row_pages)
                        if monitor is not None and not self.paged else None)
        # the monitor SLOT only exists for architectures with a
        # full-attention layer; the fully-paged path monitors every layer
        # itself and only needs the slot for ``paged_context`` probes
        try:
            self._si, self._sj = E.monitor_slot(cfg)
        except ValueError:
            self._si = self._sj = None
        if self.mirror_pages and self._si is None:
            raise ValueError(f"{cfg.name}: mirror_pages needs a "
                             "full-attention monitor layer")

    # -- admission -----------------------------------------------------------
    def _pages_kv_exact(self, req: Request) -> int:
        """Exact token pages the request's own positions span.  In paged
        mode the shared prefix pages are NOT the request's (they are
        mapped, not owned, and the prefix is page-aligned so its own
        tokens start on a page boundary); pure-recurrent architectures
        keep no token pages at all."""
        if not self.paged:
            return -(-(self.prefix + req.total_len) // self.page_size)
        if not self._has_attn:
            return 0
        return -(-req.total_len // self.page_size)

    def _pages_exact(self, req: Request) -> int:
        """Exact own-page footprint: token pages plus the state page."""
        return self._pages_kv_exact(req) + (self._state_extra if self.paged
                                            else 0)

    def _pages_alloc(self, req: Request) -> int:
        """Bucket-rounded allocation size (power-of-two token pages,
        capped at one row, plus the un-bucketed state page): what the
        request actually holds in the shared pool."""
        if self.monitor is None:
            return 0
        if not self.paged:
            return bucket_pages(self._pages_exact(req), cap=self.n_row_pages)
        kv_exact = self._pages_kv_exact(req)
        cap = self.max_len // self.page_size - self._prefix_pages
        kv_alloc = bucket_pages(kv_exact, cap=cap) if kv_exact else 0
        return kv_alloc + self._state_extra

    def submit(self, req: Request) -> None:
        req._t_submit = time.monotonic()
        req.deadline_step = (self.step_idx + req.ttl_steps
                             if req.ttl_steps is not None else -1)
        if self.prefix + req.total_len > self.max_len:
            raise ValueError(f"request {req.rid} needs "
                             f"{self.prefix + req.total_len} positions, "
                             f"cache rows hold {self.max_len}")
        if self.monitor is not None:
            n_pages = self._pages_alloc(req)
            avail = self.monitor.pools.n_logical - self._prefix_pages
            if n_pages > avail:
                # would head-of-line-block the queue forever: alloc can
                # never succeed, not even with the pool fully drained
                raise ValueError(
                    f"request {req.rid} needs {n_pages} pages, the logical "
                    f"space holds {avail} beyond the shared prefix")
            if self.paged and (self._prefix_pages + self._pages_exact(req)
                               > self.monitor.pools.hbm_pages):
                raise ValueError(
                    f"request {req.rid} touches "
                    f"{self._prefix_pages + self._pages_exact(req)} "
                    f"pages, the HBM slot pool holds "
                    f"{self.monitor.pools.hbm_pages}: it can never decode "
                    "fully paged")
        if (self.max_queue is not None and len(self.queue) >= self.max_queue
                and self.fault_plan.fires("admit.flood") is None):
            # bounded queue: shed at submit time with a typed status
            # instead of queueing unboundedly.  An armed ``admit.flood``
            # fault bypasses the bound -- the chaos harness forces the
            # queue past its depth to prove downstream stages still shed
            # rather than stall.
            self._retire_unadmitted(req, "shed", "queue-full")
            return
        self.queue.append(req)

    def _retire_unadmitted(self, req: Request, status: str,
                           reason: str) -> None:
        """Terminate a request that never reached a row: load-shed at
        submit (``status="shed"``) or deadline-expired while queued
        (``status="expired"``).  It lands in ``completed`` with an empty
        token stream -- every submitted request terminates with a typed
        status, the no-hang contract tests/test_faults.py pins."""
        req.done = True
        req.status = status
        self.completed.append(req)
        if status == "shed":
            self.shed += 1
        else:
            self.expired += 1
        if (r := _obs.RECORDER).enabled:
            r.emit("serve.shed", step=self.step_idx, rid=req.rid,
                   reason=reason, queue_depth=len(self.queue))
            r.emit("serve.retire", step=self.step_idx, rid=req.rid,
                   tokens=0, status=status,
                   deadline_ms=(time.monotonic() - req._t_submit) * 1e3
                   if req._t_submit else 0.0)
            r.count("serve.shed_total")
            r.count("serve.retired")

    def _expire_queue(self) -> None:
        """Drop queued requests whose deadline has passed (admission-time
        TTL): they can no longer finish useful work, so spending rows and
        pages on them only delays in-deadline traffic.  Admitted requests
        are never aborted (token-parity contract)."""
        if not any(req.deadline_step >= 0 for req in self.queue):
            return
        keep: List[Request] = []
        for req in self.queue:
            if 0 <= req.deadline_step < self.step_idx:
                self._retire_unadmitted(req, "expired", "deadline")
            else:
                keep.append(req)
        self.queue = collections.deque(keep)

    def _admit(self) -> List[Tuple[int, int]]:
        batch: List[Request] = []
        t_admit = time.monotonic()         # the joiners' queue wait ends
        with _obs.RECORDER.span("serve.admit"):
            self._expire_queue()
            while self.queue and self.rows_free:
                req = self.queue[0]
                n_exact = self._pages_exact(req)
                n_alloc = self._pages_alloc(req)
                gids = None
                if self.monitor is not None:
                    # the gate runs against the EFFECTIVE capacity (equal
                    # to hbm_pages unless a squeeze fault shrank it), so
                    # new admissions respect the degraded budget
                    if self.paged and (self._hbm_need + n_exact
                                       > self.monitor.pools.effective_hbm):
                        break          # head-of-line: keep arrival order
                    gids = self.monitor.pools.alloc(n_alloc, req.rid)
                    if gids is None:   # head-of-line: keep arrival order
                        break
                self.queue.popleft()
                row = self.rows_free.pop()
                req.row, req.gids, req.n_pages = row, gids, n_exact
                req.n_alloc = n_alloc
                if self.paged:
                    self._hbm_need += n_exact
                    self._map_row(req)
                batch.append(req)
        if not batch:
            return []
        with _obs.RECORDER.span("serve.prefill", joiners=len(batch)):
            emitted = self._prefill(batch)
        if (r := _obs.RECORDER).enabled:
            moe = {}
            if self._prefill_moe is not None:
                # the packed prefill's expert counts; its logits were
                # downloaded above, so the pass is done
                pairs, touched = np.asarray(self._prefill_moe)
                moe = dict(moe_pairs_held=int(pairs),
                           moe_experts_touched=int(touched))
            r.emit("serve.admit", step=self.step_idx, joiners=len(batch),
                   pages=int(sum(b.n_alloc for b in batch)),
                   queue_depth=len(self.queue),
                   rids=[b.rid for b in batch],
                   wait_ms=[(t_admit - b._t_submit) * 1e3 for b in batch],
                   **moe)
            r.count("serve.admitted", len(batch))
            r.gauge("serve.queue_depth", len(self.queue))
        return emitted

    def _map_row(self, req: Request) -> None:
        """Build the request's logical page-table row: shared prefix
        pages first, its own token-page run next (bucket tail included),
        the state page at the fixed last column.  Also records the
        (gids, mass columns) the monitor merge reads -- exact pages only,
        so bucket-tail slack never accrues mass."""
        pp = self._prefix_pages
        kv_alloc = req.n_alloc - self._state_extra
        kv_own = req.gids[:kv_alloc]
        row = np.full(self.n_row_pages, -1, np.int32)
        if pp:
            row[:pp] = self._prefix_gids
        row[pp: pp + kv_alloc] = kv_own
        parts, cols = [], []
        if pp:
            parts.append(np.asarray(self._prefix_gids, np.int64))
            cols.append(np.arange(pp))
        kv_exact = self._pages_kv_exact(req)
        if kv_exact:
            parts.append(np.asarray(kv_own[:kv_exact], np.int64))
            cols.append(pp + np.arange(kv_exact))
        if self._state_extra:
            row[-1] = req.gids[-1]
            parts.append(np.asarray(req.gids[-1:], np.int64))
            cols.append(np.asarray([self.n_row_pages - 1]))
        self._gid_tables[req.row] = row
        req.table_gids = np.concatenate(parts)
        req.mass_cols = np.concatenate(cols).astype(np.int64)
        self._rows_epoch += 1

    def _slot_table(self, rows: Sequence[int]) -> np.ndarray:
        """Physical HBM slot tables for the given rows, derived from the
        logical ``_gid_tables`` (rebuilt per upload: tiering may have
        re-slotted any resident page)."""
        pools = self.monitor.pools
        tables = np.full((self.max_active, self.n_row_pages), -1, np.int32)
        for row in rows:
            g = self._gid_tables[row]
            m = g >= 0
            tables[row, m] = pools.table(g[m])
        return tables

    def _tables_for(self, rows: Sequence[int]):
        """Device-side ``(slot_table, gid_table)`` pair for a decode
        launch, cached across boundaries: rebuilt and re-uploaded only
        when tiering re-slotted a page (``pools.slot_epoch``) or the
        row->page mapping changed (admission/retire/activation bump
        ``_rows_epoch``).  A boundary where tiering moved zero pages
        becomes a buffer swap; the ``pool.table_upload.performed`` /
        ``.skipped`` counters measure the split.  ``rows`` is implied by
        the epochs (every active-set change bumps ``_rows_epoch``), so
        the key needs no row list."""
        pools = self.monitor.pools
        key = (int(getattr(pools, "slot_epoch", 0)), self._rows_epoch)
        r = _obs.RECORDER
        if self._tables_key == key and self._tables_dev is not None:
            if r.enabled:
                r.count("pool.table_upload.skipped")
            return self._tables_dev
        with r.span("serve.tables"):
            self._tables_dev = (jnp.asarray(self._slot_table(rows)),
                                jnp.asarray(self._gid_tables))
        self._tables_key = key
        if r.enabled:
            r.count("pool.table_upload.performed")
        return self._tables_dev

    def _need(self, pos_np: np.ndarray, horizon: int,
              per_row: Optional[Dict[int, int]] = None) -> np.ndarray:
        """Every page the next ``horizon`` decode steps can touch: the
        shared prefix run, each row's token pages through its horizon
        (incl. the write pages) and its state page."""
        need: List[np.ndarray] = []
        if self._prefix_gids is not None:
            need.append(np.asarray(self._prefix_gids, np.int64))
        pp = self._prefix_pages
        for row, req in self.active.items():
            h = per_row.get(row, horizon) if per_row else horizon
            if self._has_attn:
                n_cols = -(-(int(pos_np[row]) + h) // self.page_size)
                kv_own = req.gids[: req.n_alloc - self._state_extra]
                need.append(np.asarray(kv_own[: max(0, n_cols - pp)],
                                       np.int64))
            if self._state_extra:
                need.append(np.asarray(req.gids[-1:], np.int64))
        if not need:
            return np.asarray([], np.int64)
        return np.concatenate(need)

    def _launch_packed_prefill(self, prompts: List[np.ndarray]):
        """Pad a batch of prompts and dispatch ONE packed prefill; returns
        its lazy ``(logits, cache)``.  BOTH packed dims -- width and
        joiner count -- are pow2-bucketed, so the jitted prefill (and the
        downstream page scatter) compiles per shape class, not per
        admission.  Right-padding is inert under causal attention and
        dummy joiner rows are simply never read, so valid rows are
        bit-identical."""
        with _obs.RECORDER.span("serve.prefill.launch"):
            plens = [len(p) for p in prompts]
            smax = bucket_pages(max(plens))
            jp = bucket_pages(len(prompts))
            toks = np.zeros((jp, smax), np.int32)
            plens_p = np.ones((jp,), np.int32)
            for i, p in enumerate(prompts):
                toks[i, : plens[i]] = p
                # lengths INCLUDE the shared prefix: the last valid
                # position of row i sits at prefix + plen - 1
                plens_p[i] = self.prefix + plens[i]
            kw = {}
            if self._cond is not None:
                kw["cond"] = jnp.broadcast_to(
                    self._cond, (jp,) + self._cond.shape[1:])
            if self._ex is not None:
                kw["extra_embeds"] = jnp.broadcast_to(
                    self._ex, (jp,) + self._ex.shape[1:])
            return self._prefill_fn(jnp.asarray(toks), jnp.asarray(plens_p),
                                    **kw)

    def _prefill(self, batch: List[Request]) -> List[Tuple[int, int]]:
        """Prefill a step's joiners as one packed forward pass, seed their
        rows/pages, and sample each first token."""
        plens = [len(r.prompt) for r in batch]
        if self._batched_prefill:
            logits_b, cache_b = self._launch_packed_prefill(
                [r.prompt for r in batch])
        else:               # recurrent state: one request at a time
            logits_b, cache_b = None, None
        self._prefill_moe = None if cache_b is None else cache_b.get("moe")

        if self.paged and self._batched_prefill:
            # one on-device gather/scatter writes EVERY joiner's KV for
            # EVERY layer straight into the pool slots
            self._write_prefill_pages_batched(cache_b, batch, plens)

        with _obs.RECORDER.span("serve.prefill.first_tokens"):
            return self._first_tokens(batch, plens, logits_b, cache_b)

    def _first_tokens(self, batch: List[Request], plens: List[int],
                      logits_b, cache_b) -> List[Tuple[int, int]]:
        """The per-joiner half of an admission: install each row, sample
        its first token (a host sync each), retire one-token requests."""
        emitted: List[Tuple[int, int]] = []
        for bi, req in enumerate(batch):
            row, plen = req.row, plens[bi]
            if self._batched_prefill:
                logits = logits_b[bi: bi + 1]
                if self.paged:
                    pass                 # pages already written (batched)
                else:
                    one = mdl.row_cache_from_batched(
                        cache_b, self.cfg, bi, self.prefix + plen,
                        self.max_len)
                    self.cache = jax.tree.map(
                        lambda full, o: full.at[:, row].set(o),
                        self.cache, one)
            else:
                prompt = jnp.asarray(req.prompt, jnp.int32)[None]
                logits, cache1 = mdl.prefill(self.params, self.cfg, prompt,
                                             cond=self._cond,
                                             extra_embeds=self._ex)
                if self.paged:
                    self._write_prefill_pages_row(cache1, req, plen)
                else:
                    cache1 = mdl.pad_cache(cache1, self.cfg, self.max_len)
                    self.cache = jax.tree.map(
                        lambda full, o: full.at[:, row].set(o[:, 0]),
                        self.cache, cache1)
            req._key = (req.key if req.key is not None
                        else jax.random.PRNGKey(0))
            tok = E._sample(logits[:, 0], req._key, req.temperature)
            req.tokens.append(int(tok[0]))
            emitted.append((req.rid, int(tok[0])))
            self.tok = self.tok.at[row].set(tok)
            self.pos = self.pos.at[row].set(self.prefix + plen)
            self.active[row] = req
            if self.mirror_pages:
                self._mirror(req, range(-(-(self.prefix + plen)
                                          // self.page_size)))
            if req.max_new_tokens <= 1 or (req.eos_id is not None
                                           and req.tokens[-1] == req.eos_id):
                self._retire(req)
        return emitted

    def _prefill_leaves(self, cache, meta, start: int):
        """{leaf_name: per-slot cache rows} for ``write_pages_batched``,
        sliced from absolute position ``start`` (the shared prefix region
        is written once at construction, not per admission)."""
        leaves: Dict[str, List] = {}
        for li, (si, j, _, _, kind) in enumerate(meta):
            if not kind.is_attention:
                continue
            e = cache["segments"][si][j]
            for name in (("ckv", "krope") if kind.mla else ("k", "v")):
                leaves.setdefault(name, [None] * len(meta))[li] = \
                    e[name][:, :, start:]
        return leaves

    def _write_prefill_pages_batched(self, cache_b, batch: List[Request],
                                     plens: List[int]) -> None:
        """Scatter a whole admission's prefilled cache (every joiner,
        every geometry leaf, host + HBM tiers) into the shared pool in
        ONE jitted gather/scatter (``memtier.write_pages_batched``).
        Slots are assigned bookkeeping-only first (initial placement, not
        charged as misses) since the scatter overwrites both tiers --
        the prefill bytes never take the host detour."""
        with _obs.RECORDER.span("pool.write_prefill"):
            pools = self.monitor.pools
            ps = self.page_size
            # own token pages only: the prefix is page-aligned, so each
            # prompt's pages start at cache position ``prefix``
            ns = [-(-p // ps) for p in plens]
            # both scatter dims pow2-bucketed (matching the prefill batch):
            # padded joiner rows / tail pages carry PAGE_DROP and vanish
            jp = cache_b["segments"][0][0]["pos"].shape[1]
            n_max = bucket_pages(max(ns))
            gids_m = np.full((jp, n_max), PAGE_DROP, np.int32)
            slots_m = np.full((jp, n_max), PAGE_DROP, np.int32)
            for i, (req, n) in enumerate(zip(batch, ns)):
                gids_m[i, :n] = req.gids[:n]
            flat = np.concatenate([req.gids[:n]
                                   for req, n in zip(batch, ns)])
            slots_flat = pools.assign_slots(flat)
            o = 0
            for i, n in enumerate(ns):
                slots_m[i, :n] = slots_flat[o: o + n]
                o += n
            leaves = self._prefill_leaves(
                cache_b, mdl.state_slot_meta(self.cfg), self.prefix)
            pools.set_kv(write_pages_batched(
                pools.kv_view(), leaves, jnp.asarray(gids_m),
                jnp.asarray(slots_m)))

    def _write_prefill_pages_row(self, cache1, req: Request,
                                 plen: int) -> None:
        """Write ONE request's per-request prefill into the shared pool:
        the non-batched admission path of recurrent architectures.  Token
        pages scatter position-keyed (page = pos // ps, offset = pos %
        ps), which lands window-ring cache layouts correctly -- a clipped
        ring holds exactly the unmasked last-window positions, each
        tagged with its absolute position.  Recurrent slots pack their
        final cell state into the request's state page."""
        pools = self.monitor.pools
        ps = self.page_size
        kv_exact = self._pages_kv_exact(req)
        own = req.gids[: req.n_alloc - self._state_extra]
        touched = np.concatenate([own[:kv_exact],
                                  req.gids[-1:] if self._state_extra
                                  else np.asarray([], np.int64)])
        slots = pools.assign_slots(touched)
        kv_slots = slots[:kv_exact]
        kv = pools.kv_view()
        drop = int(PAGE_DROP)
        for li, (si, j, r, _, kind) in enumerate(
                mdl.state_slot_meta(self.cfg)):
            e = cache1["segments"][si][j]
            if kind.is_attention:
                pos = np.asarray(e["pos"][0, 0])      # same across repeats
                valid = pos >= 0
                page = np.clip(np.where(valid, pos, 0) // ps, 0,
                               max(kv_exact - 1, 0))
                rows_s = np.where(valid, kv_slots[page], drop)
                rows_g = np.where(valid, own[:kv_exact][page], drop)
                offs = np.where(valid, pos % ps, 0)
                for name in (("ckv", "krope") if kind.mla else ("k", "v")):
                    arr = e[name][:, 0]               # [R, T, ...]
                    kv[f"{name}_hbm"][li] = kv[f"{name}_hbm"][li].at[
                        :, rows_s, offs].set(arr, mode="drop")
                    kv[f"{name}_host"][li] = kv[f"{name}_host"][li].at[
                        :, rows_g, offs].set(arr, mode="drop")
            else:
                flat = jnp.stack([mdl.pack_state(
                    jax.tree.map(lambda a: a[rr], e))[0] for rr in range(r)])
                kv["state_hbm"][li] = kv["state_hbm"][li].at[
                    :, int(slots[-1])].set(flat)
                kv["state_host"][li] = kv["state_host"][li].at[
                    :, int(req.gids[-1])].set(flat)
        pools.set_kv(kv)

    def _prefill_prefix_pages(self) -> None:
        """Prefill the shared read-only prefix ONCE and write its KV into
        the shared pages every request's table maps.  Under the causal
        mask the prefix positions attend only the prefix embeddings, so
        one dummy-token prefill is exact for every future prompt --
        admission maps these pages instead of re-prefilling the prefix."""
        pools = self.monitor.pools
        dummy = jnp.zeros((1, 1), jnp.int32)
        _, cache1 = mdl.prefill(self.params, self.cfg, dummy,
                                extra_embeds=self._ex, cond=self._cond)
        slots = pools.assign_slots(self._prefix_gids)
        meta = mdl.state_slot_meta(self.cfg)
        leaves: Dict[str, List] = {}
        for li, (si, j, _, _, kind) in enumerate(meta):
            e = cache1["segments"][si][j]
            for name in (("ckv", "krope") if kind.mla else ("k", "v")):
                leaves.setdefault(name, [None] * len(meta))[li] = \
                    e[name][:, :, : self.prefix]
        pools.set_kv(write_pages_batched(
            pools.kv_view(), leaves,
            jnp.asarray(self._prefix_gids, jnp.int32)[None],
            jnp.asarray(slots, jnp.int32)[None]))

    # -- overload safety: fault clock, preemption, reactivation --------------
    def _fault_tick(self) -> None:
        """Advance the fault plan's logical clock once per scheduler step
        and actuate the capacity-squeeze fault: while a ``pool.squeeze``
        point fires, the pool's *effective* HBM capacity shrinks to the
        point's value, and every admission gate, tiering budget and the
        preemption loop run against that budget.  When the window closes
        the full capacity returns."""
        plan = self.fault_plan
        if not plan.enabled:
            return
        plan.tick()
        if self.monitor is not None and self.paged:
            pools = self.monitor.pools
            p = plan.fires("pool.squeeze")
            pools.effective_hbm = (max(1, int(p.value)) if p is not None
                                   else pools.hbm_pages)

    def _rebalance(self) -> None:
        """Pressure response at a scheduler boundary (docs/robustness.md,
        "Preemption semantics").  First reactivate frozen requests whose
        footprint fits the effective capacity again -- FIFO, oldest
        first, with a forced-progress escape: if nothing else is active
        or pending, one frozen request thaws regardless, so a squeeze
        below any single footprint still drains instead of deadlocking.
        Then, while the in-flight footprint exceeds the effective
        capacity, preempt the COLDEST victim -- the active request whose
        pages carry the least manager hotness (Cori page mass), ties to
        the newest rid -- until the remainder fits or one request is
        left (the last row never preempts: forward progress)."""
        if not self.paged or self.monitor is None:
            return
        pools = self.monitor.pools
        with _obs.RECORDER.span("serve.rebalance"):
            while self._frozen and self.rows_free:
                req = self._frozen[0]
                fits = self._hbm_need + req.n_pages <= pools.effective_hbm
                if not fits and (self.active or self._pending_admits):
                    break
                self._thaw(self._frozen.pop(0))
            while (self._hbm_need > pools.effective_hbm
                   and len(self.active) > 1):
                hot = self.monitor.manager.hotness
                victims = [req for req in self.active.values()
                           if req._first_tok is None]
                if len(victims) <= 1:
                    break
                victim = min(victims, key=lambda q: (
                    float(hot[q.gids].sum()), -q.rid))
                self._preempt(victim)

    def _preempt(self, req: Request) -> None:
        """Freeze one active request: demote its own pages to host
        (releasing their HBM slots -- the write-through invariant means
        the host copies are already current, so this moves no data),
        free its row, and park it on the frozen list with the row state
        (position, last token) it needs to resume bit-identically.  Its
        pages stay ALLOCATED -- the KV survives host-side -- so
        reactivation is a row re-install plus demand fetches, never a
        re-prefill."""
        pools = self.monitor.pools
        row = req.row
        req._frozen_pos = int(np.asarray(self.pos)[row])
        req._frozen_tok = int(np.asarray(self.tok)[row, 0])
        hot = float(self.monitor.manager.hotness[req.gids].sum())
        released = pools.demote(req.gids)
        del self.active[row]
        self.rows_free.append(row)
        self._hbm_need -= req.n_pages
        self._gid_tables[row, :] = -1
        self._rows_epoch += 1
        req.row = -1
        self._frozen.append(req)
        self.preemptions += 1
        if (r := _obs.RECORDER).enabled:
            r.emit("serve.preempt", step=self.step_idx, rid=req.rid,
                   pages=int(released), mass=hot,
                   hbm_need=int(self._hbm_need),
                   hbm_cap=int(pools.effective_hbm))
            r.count("serve.preempted")

    def _thaw(self, req: Request) -> None:
        """Reactivate a frozen request into a free row.  ``_key``/``_i``
        never left the request, the pages never left the pool, and the
        saved (position, last token) re-install restores the row exactly
        -- the resumed stream is bit-identical to an uninterrupted run.
        The pages fetch back to HBM lazily through the next launch's
        ``ensure_resident`` (the Cori-visible cost of the preemption)."""
        row = self.rows_free.pop()
        req.row = row
        self._map_row(req)
        self._hbm_need += req.n_pages
        self.pos = self.pos.at[row].set(req._frozen_pos)
        self.tok = self.tok.at[row].set(req._frozen_tok)
        self.active[row] = req
        if (r := _obs.RECORDER).enabled:
            r.count("serve.thawed")

    # -- the per-step scheduler loop -----------------------------------------
    def step(self) -> List[Tuple[int, int]]:
        """One scheduler step: admit (one packed prefill), monitor+tier,
        decode the request set, sample, retire.  Returns the (rid, token)
        pairs emitted this step, including the prefill-sampled first token
        of newly admitted requests.  In pipelined mode a step instead
        completes the PREVIOUS in-flight macro, launches the next one and
        fills the overlap window behind it, so tokens surface one step
        after their macro launched."""
        with _obs.RECORDER.span("serve.step"):
            self._fault_tick()
            if self.pipeline:
                return self._step_pipelined()
            self._rebalance()
            emitted = self._admit()
            self.step_idx += 1
            if self.active:
                if self.paged:
                    emitted += (self._step_paged_macro() if self.macro
                                else self._step_paged())
                else:
                    emitted += self._step_dense()
            return emitted

    def _step_dense(self) -> List[Tuple[int, int]]:
        emitted: List[Tuple[int, int]] = []
        if self.monitor is not None:
            masses = np.asarray(self._mon_fn(self.cache, self.tok, self.pos))
            merged = self.monitor.merge(
                [(r.gids[: r.n_pages], masses[r.row, : r.n_pages])
                 for r in self.active.values()])
            self.monitor.on_step(merged, n_active=len(self.active))

        pos_before = np.asarray(self.pos)
        logits, self.cache = self._step_fn(self.cache, self.tok, self.pos,
                                           self._cond_rows)
        self.pos = self.pos + 1
        new_tok = self.tok
        for row, req in list(self.active.items()):
            req._key = jax.random.fold_in(req._key, req._i)
            req._i += 1
            tok = E._sample(logits[row: row + 1, 0], req._key,
                            req.temperature)
            req.tokens.append(int(tok[0]))
            new_tok = new_tok.at[row].set(tok)
            emitted.append((req.rid, int(tok[0])))
            if self.mirror_pages:
                self._mirror(req, [int(pos_before[row]) // self.page_size])
            if (len(req.tokens) >= req.max_new_tokens
                    or (req.eos_id is not None
                        and req.tokens[-1] == req.eos_id)):
                self._retire(req)
        self.tok = new_tok
        return emitted

    def _step_paged(self) -> List[Tuple[int, int]]:
        """Fully-paged decode step: demand-fetch the in-flight working
        set, run every attention layer off the shared slot pool, feed the
        monitor the ALL-layer masses, sample, retire."""
        pools = self.monitor.pools
        pos_np = np.asarray(self.pos)

        # every page this step's decode can touch (shared prefix, token
        # pages incl. the write page, the state page) must be
        # HBM-resident; re-fetches after eviction are on-demand host
        # reads, charged inside the monitor feed below (fetch_cost: the
        # pools batch the copies into one gathered transfer)
        fetched = pools.ensure_resident(self._need(pos_np, 1))
        degraded = pools.degraded_fetches
        pools.degraded_fetches = 0

        # page tables re-upload only when a page re-slotted or the row
        # mapping changed since the last step (epoch-keyed cache)
        tables_dev, gids_dev = self._tables_for(list(self.active))
        cur = np.full((self.max_active,), -1, np.int32)
        for row in self.active:
            cur[row] = pos_np[row]

        logits, kv, masses = self._paged_fn(
            pools.kv_view(), tables_dev, gids_dev, self.tok,
            jnp.asarray(cur), cond=self._cond_rows,
            state_cols=self._state_cols)
        pools.set_kv(kv)
        masses = np.asarray(masses)
        merged = self.monitor.merge(
            [(r.table_gids, masses[r.row][r.mass_cols])
             for r in self.active.values()])
        self.monitor.on_step(merged, n_active=len(self.active),
                             fetched=fetched, degraded=degraded)

        self.pos = self.pos + 1
        emitted: List[Tuple[int, int]] = []
        new_tok = self.tok
        for row, req in list(self.active.items()):
            req._key = jax.random.fold_in(req._key, req._i)
            req._i += 1
            tok = E._sample(logits[row: row + 1, 0], req._key,
                            req.temperature)
            req.tokens.append(int(tok[0]))
            new_tok = new_tok.at[row].set(tok)
            emitted.append((req.rid, int(tok[0])))
            if (len(req.tokens) >= req.max_new_tokens
                    or (req.eos_id is not None
                        and req.tokens[-1] == req.eos_id)):
                self._retire(req)
        self.tok = new_tok
        return emitted

    def _macro_fn(self, n_steps: int):
        """The macro program for one scan length (one compile per length:
        lengths are the tuner's period ladder, pow2-capped by the
        remaining work)."""
        return functools.partial(decode_macro, self.params, self.cfg,
                                 page_size=self.page_size, n_steps=n_steps)

    def _step_paged_macro(self) -> List[Tuple[int, int]]:
        """Macro-step decode: ONE device launch runs up to a movement
        period's worth of tokens for the whole request set
        (``model.decode_macro_step``), with on-device sampling, mass
        accumulation and EOS/length masking.  The host uploads page
        tables once per macro step and downloads (tokens, summed mass,
        finished flags) once -- between tiering boundaries the loop is
        device-resident, and ``TrafficMonitor.merge`` collapses to one
        call per movement period.  (The pipelined loop splits the same
        launch/complete halves across scheduler steps so the boundary
        host work runs behind the in-flight scan.)"""
        emitted, _ = self._macro_complete(self._macro_launch(), sync=True)
        return emitted

    def _macro_launch(self) -> Dict:
        """Dispatch one macro scan over the current request set and
        return the in-flight record WITHOUT blocking on the result: the
        outputs (tokens, state, the donated-in/returned kv pytree) are
        lazy.  ``pools.set_kv`` publishes the lazy kv immediately, so
        any pool work dispatched before the blocking download -- the
        pipelined prefetch, a tiering apply, an admission chunk's page
        scatter -- consumes these arrays and therefore chains *after*
        the scan on device.  That data dependency is the entire overlap
        mechanism: host work reorders freely, device work cannot."""
        pools = self.monitor.pools
        pos_np = np.asarray(self.pos)
        rows = list(self.active.items())

        period = self.macro_steps or self.monitor.manager.period
        # a lazily-admitted row's first token is still in flight: it
        # counts against the budget (the device's emitted/eos init check
        # relies on it) but is not in req.tokens yet
        ect = {row: len(req.tokens) + (req._first_tok is not None)
               for row, req in rows}
        max_rem = max(req.max_new_tokens - ect[row] for row, req in rows)
        # The scan length is pow2-bucketed on BOTH sides -- the pow2
        # floor of the live period (a non-pow2 period quantises to
        # slightly shorter macros rather than minting a compile per
        # ladder value: the tuner walks arbitrary DR multiples, and each
        # distinct n_steps is a full-model XLA compile) and the pow2
        # ceiling of the remaining work (rows that finish early freeze,
        # and whole overshoot steps short-circuit on device).  The jit
        # cache is therefore log-bounded.
        n_steps = max(1, min(1 << max(0, int(period).bit_length() - 1),
                             bucket_pages(max_rem)))

        # every page the macro's decode can touch (through each row's
        # horizon, incl. the write pages, the shared prefix and state
        # pages) must be HBM-resident up front: the device never calls
        # home mid-macro.  Re-fetches after eviction are on-demand host
        # reads, charged as misses inside the monitor feed below so the
        # tuner's cost window sees them (they are the price of the
        # current period).
        horizons = {row: min(n_steps, req.max_new_tokens - ect[row])
                    for row, req in rows}
        fetched = pools.ensure_resident(
            self._need(pos_np, n_steps, per_row=horizons))
        # pages the pipelined overlap window already prefetched for this
        # macro count toward ITS fetch bill (they are the price of the
        # period, wherever the copy was dispatched)
        fetched += self._prefetched_next
        self._prefetched_next = 0
        # drain the pool's degraded-fetch counter (retry-exhausted,
        # host-pinned fetches -- wherever they were dispatched, incl. the
        # overlap prefetch) into this macro's cost bill: the monitor tops
        # their price up from fetch_cost to miss_penalty
        degraded = pools.degraded_fetches
        pools.degraded_fetches = 0

        # page tables upload once per macro step (tiering only runs at
        # macro boundaries, so no page can re-slot mid-macro) -- and only
        # when something actually changed since the last upload
        # (epoch-keyed cache; otherwise the staged buffer is swapped in)
        tables_dev, gids_dev = self._tables_for([row for row, _ in rows])
        with _obs.RECORDER.span("serve.macro.launch", n_steps=n_steps):
            cur = np.full((self.max_active,), -1, np.int32)
            keys = np.zeros((self.max_active, 2), np.uint32)
            iters = np.zeros((self.max_active,), np.int32)
            emitted_ct = np.zeros((self.max_active,), np.int32)
            max_new = np.zeros((self.max_active,), np.int32)
            eos = np.full((self.max_active,), -1, np.int32)
            temps = np.zeros((self.max_active,), np.float32)
            for row, req in rows:
                cur[row] = pos_np[row]
                keys[row] = np.asarray(req._key, np.uint32)
                iters[row] = req._i
                emitted_ct[row] = ect[row]
                max_new[row] = req.max_new_tokens
                eos[row] = -1 if req.eos_id is None else req.eos_id
                temps[row] = req.temperature

            n_flags = len(self.macro_timer.stragglers)
            self.macro_timer.start()
            toks, kv, st = self._macro_fn(n_steps)(
                pools.kv_view(), tables_dev, gids_dev, self.tok,
                jnp.asarray(cur), jnp.asarray(keys), jnp.asarray(iters),
                jnp.asarray(emitted_ct), jnp.asarray(max_new),
                jnp.asarray(eos), jnp.asarray(temps),
                cond=self._cond_rows, state_cols=self._state_cols)
            pools.set_kv(kv)
        return {"toks": toks, "st": st, "rows": rows, "n_steps": n_steps,
                "fetched": fetched, "degraded": degraded,
                "n_flags": n_flags, "horizons": horizons, "pos_np": pos_np}

    def _macro_complete(self, fl: Dict, sync: bool
                        ) -> Tuple[List[Tuple[int, int]], Optional[Dict]]:
        """Block on an in-flight macro's downloads and run the boundary:
        merge masses, restore device-side row state, append/emit tokens,
        retire finished requests.  ``sync=True`` (the synchronous loop)
        feeds the monitor inline -- tier + tune before the next launch.
        ``sync=False`` (the pipelined loop) instead returns the
        monitor-feed payload for the caller to hand to the
        ``DecisionWorker`` *after* the boundary's remaining manager
        touches (retire/release, activation) are done -- the worker's
        strict-alternation safety window."""
        st, rows, n_steps = fl["st"], fl["rows"], fl["n_steps"]
        with _obs.RECORDER.span("serve.macro.wait"):
            toks_np = np.asarray(fl["toks"])
            mass_sum = np.asarray(st["mass_sum"])
            # alive steps per row, then the expert counts: one download
            counts = np.asarray(st["counts"])
            alive_steps = counts[:self.max_active]
            stopped = np.asarray(st["stopped"])
            iters_out = np.asarray(st["iters"])
        # the downloads above force the device sync: the stop covers the
        # whole launch + transfer, which is what a straggler would slow
        macro_wall = self.macro_timer.stop(self.step_idx)
        straggler = len(self.macro_timer.stragglers) > fl["n_flags"]

        # ONE merge + monitor feed per movement period (mean mass over
        # the steps each row actually ran, so the per-step scale the
        # access threshold expects is preserved).  dt = the macro's span
        # in token-steps; the mean in-flight count normalises cost per
        # request as on the per-token path.
        with _obs.RECORDER.span("serve.monitor"):
            merged = self.monitor.merge(
                [(r.table_gids,
                  mass_sum[r.row][r.mass_cols]
                  / max(1, int(alive_steps[r.row])))
                 for _, r in rows])
            dt = max(1, int(alive_steps.max()))
            n_active = float(alive_steps.sum()) / dt
            if (plan := self.fault_plan).enabled \
                    and plan.fires("mass.nonfinite") is not None:
                # corrupt the merged telemetry deterministically: the monitor
                # feed's NaN clamp must neutralise it before the reuse
                # collector / tuner see it (the defense this fault exercises)
                merged[::3] = np.nan
                merged[1::5] = np.inf
            payload: Optional[Dict] = None
            if sync:
                self.monitor.on_macro_step(merged, n_active=n_active,
                                           n_tokens=dt, fetched=fl["fetched"],
                                           degraded=fl["degraded"])
            else:
                # boundary snapshots for the worker's plan (apply_plan
                # revalidates against whatever moves before actuation).  The
                # free-slot budget is clamped to the squeezed capacity so a
                # worker-planned bring never overfills the effective pool.
                pools = self.monitor.pools
                n_free = int((pools.page_of_slot < 0).sum())
                n_free = min(n_free, max(0, pools.effective_hbm
                                         - pools.hbm_occupied))
                payload = dict(global_mass=merged, n_active=n_active,
                               n_tokens=dt, fetched=fl["fetched"],
                               degraded=fl["degraded"],
                               resident=pools.slot_of >= 0,
                               n_free=n_free,
                               active=pools.allocated_mask,
                               planes=int(getattr(pools, "move_planes", 2)))

        with _obs.RECORDER.span("serve.emit"):
            self.pos = st["pos"]
            self.tok = st["last_tok"]
            emitted: List[Tuple[int, int]] = []
            # resolve lazily-admitted rows' deferred first tokens: the
            # sample fed this macro's scan, so the download is a no-wait
            # read; it precedes the row's macro tokens in the stream
            for row, req in rows:
                if req._first_tok is not None:
                    tk = int(req._first_tok[0])
                    req._first_tok = None
                    req.tokens.append(tk)
                    emitted.append((req.rid, tk))
            for t in range(toks_np.shape[0]):
                for row, req in rows:
                    tk = int(toks_np[t, row])
                    if tk >= 0:
                        req.tokens.append(tk)
                        emitted.append((req.rid, tk))
            for row, req in rows:
                req._key = st["keys"][row]
                req._i = int(iters_out[row])
                if stopped[row]:
                    self._retire(req)
        if (r := _obs.RECORDER).enabled:
            # the pages the paged kernel walks: each active row's live
            # pages at launch, against every row's whole table
            live = sum(-(-(int(fl["pos_np"][row]) + 1) // self.page_size)
                       for row, _ in rows)
            r.emit("serve.macro", step=self.step_idx, n_steps=int(n_steps),
                   tokens=len(emitted), active=n_active,
                   fetched=int(fl["fetched"]), wall_ms=macro_wall * 1e3,
                   straggler=straggler, kv_pages_live=live,
                   kv_pages_table=self.max_active * self.n_row_pages,
                   moe_pairs_held=int(counts[self.max_active]),
                   moe_experts_touched=int(counts[self.max_active + 1]))
            r.count("serve.tokens", len(emitted))
        return emitted, payload

    # -- the pipelined macro loop --------------------------------------------
    def _plan_decision(self, payload: Dict):
        """Runs on the DecisionWorker thread.  Strict alternation (the
        dispatch thread only touches the manager/tuner between ``wait``
        and the next ``submit``) makes this lock-free by construction.

        The worker faults (injected delay / crash) fire BEFORE the
        manager/tuner are touched, so a watchdog recovery can recompute
        the boundary synchronously without double-feeding the tuner; the
        live-epoch guard then makes a zombie that wakes *after* a
        recovery publish an inert result instead of racing the dispatch
        thread on shared state."""
        plan = self.fault_plan
        if plan.enabled:
            if (p := plan.fires("worker.delay")) is not None:
                time.sleep(p.value)
            if plan.fires("worker.crash") is not None:
                raise RuntimeError("injected decision-worker crash")
        if payload.get("_epoch", self._live_epoch) != self._live_epoch:
            return self.monitor.manager.period, None
        kw = {k: v for k, v in payload.items() if k != "_epoch"}
        return self.monitor.plan_step(**kw)

    def _worker_recover(self, reason: str):
        """Watchdog recovery: the DecisionWorker hung past the deadline
        or its decision raised.  Walk away from the thread (``abandon``
        for a hang -- joining a wedged thread would stall the loop; a
        clean ``close`` for a crash), bump the live epoch so the zombie
        can never touch shared state, revert the tuner to its last-good
        period (the in-flight sweep's state is unreliable), recompute
        THIS boundary's decision synchronously from the stashed payload,
        and spawn a fresh worker -- unless ``max_worker_restarts`` is
        exhausted, after which the loop stays permanently synchronous
        (degraded mode: correct, just without overlap).  Returns the
        recomputed ``(period, plan)``."""
        self._live_epoch += 1
        w = self._decision_worker
        if reason == "hang":
            w.abandon()
        else:
            w.close(timeout=1.0)
        self._worker_restarts += 1
        self._worker_degraded = self._worker_restarts \
            > self.max_worker_restarts
        self._decision_worker = (None if self._worker_degraded
                                 else DecisionWorker(self._plan_decision))
        if self.monitor.tuner is not None:
            self.monitor.tuner.revert_last_good(
                reason=f"decision-worker-{reason}")
        kw = {k: v for k, v in self._last_payload.items() if k != "_epoch"}
        period, plan = self.monitor.plan_step(**kw)
        if (r := _obs.RECORDER).enabled:
            r.emit("serve.worker_restart", step=self.step_idx,
                   reason=reason, restarts=self._worker_restarts,
                   degraded=self._worker_degraded)
            r.count("serve.worker_restarts")
        return period, plan

    def _step_pipelined(self) -> List[Tuple[int, int]]:
        """One pipelined scheduler step.  Deterministic fixed order:

        1. complete the previous in-flight macro (blocking download,
           token append incl. deferred first tokens, retire) -- the
           worker is idle here, so the retire path's manager/tuner
           touches are safe;
        2. reserve new admissions off the queue (rows/pages held, same
           HBM gate as the synchronous loop);
        3. dispatch the packed prefill for the fresh reservations (an
           async device dispatch -- the host never waits on it);
        4. activate every ready admission LAZILY: the first-token sample
           is pure jnp chained behind its prefill, so a request joins
           the SAME macro its reservation preceded -- no one-macro
           utilisation hole -- and only the int() bookkeeping waits for
           the next boundary (``Request._first_tok``);
        5. launch the next macro over the active set (placement/period
           from the LAST boundary's applied decision -- stale-by-one);
        6. submit the completed macro's masses to the decision worker;
        7. the overlap window: wait+apply the previous decision, advance
           chunked admissions, prefetch the next horizon, stage tables
           -- all behind the scan launched in (5)."""
        fl, self._inflight = self._inflight, None
        emitted: List[Tuple[int, int]] = []
        payload = None
        if fl is not None:
            emitted, payload = self._macro_complete(fl, sync=False)
        self.step_idx += 1
        self._rebalance()
        self._admit_reserve()
        self._admit_prefill_fresh()
        emitted += self._admit_activate()
        if self.active:
            self._inflight = self._macro_launch()
        if payload is not None:
            if self._decision_worker is not None:
                # the payload carries the live epoch (the zombie guard)
                # and is stashed so a watchdog recovery can recompute
                # this boundary synchronously
                payload["_epoch"] = self._live_epoch
                self._last_payload = payload
                self._decision_gen = self._decision_worker.submit(payload)
            else:
                # degraded-permanent mode (restarts exhausted): the
                # boundary decision runs synchronously -- no overlap,
                # same computation
                period, plan = self.monitor.plan_step(
                    **{k: v for k, v in payload.items() if k != "_epoch"})
                self.monitor.apply_decision(plan)
        self._pipeline_overlap()
        return emitted

    def _pipeline_overlap(self) -> None:
        """The overlap window: host-side boundary work dispatched while
        the just-launched scan (if any) runs on device -- every device
        op here consumes the scan's lazy kv outputs and so chains after
        it.  Fixed stage order: the decision apply first (it moves
        placement), chunked-admission progress next, the prefetch last
        (it re-fetches anything the earlier stages evicted), then the
        table staging."""
        if self._decision_gen is not None:
            gen, self._decision_gen = self._decision_gen, None
            with _obs.RECORDER.span("serve.decision_wait"):
                t0 = time.monotonic()
                try:
                    (period, plan), waited = self._decision_worker.wait(
                        gen, timeout=self.watchdog_s)
                except TimeoutError:   # hung worker: watchdog recovery
                    period, plan = self._worker_recover("hang")
                    waited = time.monotonic() - t0
                except Exception:      # crashed worker
                    if self.watchdog_s is None:
                        raise          # no watchdog: fail loud (close()
                                       # still tears down cleanly)
                    period, plan = self._worker_recover("crash")
                    waited = time.monotonic() - t0
                self.monitor.apply_decision(plan)
            if (r := _obs.RECORDER).enabled:
                r.emit("serve.pipeline.decision", step=self.step_idx,
                       generation=gen, period=int(period),
                       bring=0 if plan is None else int(len(plan[0])),
                       evict=0 if plan is None else int(len(plan[1])),
                       wait_ms=waited * 1e3)
        if any(p.chunked and not p.ready for p in self._pending_admits):
            self._admit_chunks()
        fl = self._inflight
        if fl is None:
            return
        # conservative prefetch for the NEXT macro: through this macro's
        # per-row horizon plus one more macro of the same length, capped
        # by each row's remaining budget.  Opportunistic, not a residency
        # guarantee -- the next launch's ensure_resident still backstops
        # (and if the pending decision changes the period, it picks up
        # the difference there, charged as launch-time fetches).
        n_next = fl["n_steps"]
        per_row = {row: min(fl["horizons"][row] + n_next,
                            req.max_new_tokens - len(req.tokens))
                   for row, req in fl["rows"]}
        self._prefetched_next += self.monitor.pools.ensure_resident(
            self._need(fl["pos_np"], 0, per_row=per_row))
        # stage the next boundary's tables: if nothing above re-slotted a
        # page, the next launch's _tables_for is a pure buffer swap
        self._tables_for([row for row, _ in fl["rows"]])

    def _admit_reserve(self) -> None:
        """Pop admittable requests into the pending set: rows and pages
        are reserved NOW (the HBM admission gate counts them, same rule
        as ``_admit``), but the prefill runs inside overlap windows and
        the row only activates at a macro boundary."""
        pools = self.monitor.pools
        with _obs.RECORDER.span("serve.admit"):
            self._expire_queue()
            while self.queue and self.rows_free:
                req = self.queue[0]
                n_exact = self._pages_exact(req)
                n_alloc = self._pages_alloc(req)
                if self._hbm_need + n_exact > pools.effective_hbm:
                    break          # head-of-line: keep arrival order
                gids = pools.alloc(n_alloc, req.rid)
                if gids is None:   # head-of-line: keep arrival order
                    break
                self.queue.popleft()
                row = self.rows_free.pop()
                req.row, req.gids, req.n_pages = row, gids, n_exact
                req.n_alloc = n_alloc
                self._hbm_need += n_exact
                self._map_row(req)
                plen = len(req.prompt)
                # chunking needs prefill_chunk's contract: batched-prefill
                # arch, no shared prefix (chunk-local positions must be
                # absolute), no extra embeds (prefill_chunk takes none)
                chunked = (self._chunk_width is not None
                           and self._batched_prefill and self.prefix == 0
                           and self._ex is None and plen > self._chunk_width)
                self._pending_admits.append(_PendingAdmit(
                    req=req, plen=plen, chunked=chunked,
                    t_reserved=time.monotonic()))
        if (r := _obs.RECORDER).enabled:
            r.gauge("serve.queue_depth", len(self.queue))

    def _admit_prefill_fresh(self) -> None:
        """Boundary-side admission dispatch: one packed prefill over
        every fresh non-chunked reservation, with NO sample sync -- the
        logits stay lazy, so the host moves straight on to the macro
        launch and the scan chains after the prefill's page scatter on
        device (exactly the ordering the synchronous loop gets, minus
        the host stall)."""
        fresh = [p for p in self._pending_admits
                 if not p.ready and not p.chunked and p.logits is None]
        if not fresh:
            return
        if self._batched_prefill:
            self._dispatch_packed_prefill(fresh)
        else:                   # recurrent state: one request at a time
            for p in fresh:
                prompt = jnp.asarray(p.req.prompt, jnp.int32)[None]
                logits, cache1 = mdl.prefill(
                    self.params, self.cfg, prompt, cond=self._cond,
                    extra_embeds=self._ex)
                self._write_prefill_pages_row(cache1, p.req, p.plen)
                p.logits = logits
                p.ready = True

    def _admit_chunks(self) -> None:
        """Overlap-window admission work: ONE bounded chunk per chunked
        admission, dispatched behind the in-flight scan so long-prompt
        prefill never delays a launch.  ``admit_chunk_tokens`` is the
        SLO knob: it caps how much prefill compute any single window
        puts in front of the next boundary, trading admission latency
        for boundary stall."""
        with _obs.RECORDER.span("serve.admit_chunks"):
            for p in self._pending_admits:
                if p.chunked and not p.ready:
                    self._dispatch_chunk(p)

    def _dispatch_packed_prefill(self, pending: List[_PendingAdmit]
                                 ) -> None:
        """Dispatch one packed prefill for a window's non-chunked pending
        admissions -- the same pow2-bucketed pass as ``_prefill``, minus
        the sampling sync (the lazy logits ride in the pending record
        until the boundary)."""
        logits_b, cache_b = self._launch_packed_prefill(
            [p.req.prompt for p in pending])
        self._write_prefill_pages_batched(cache_b,
                                          [p.req for p in pending],
                                          [p.plen for p in pending])
        for i, p in enumerate(pending):
            p.logits = logits_b[i: i + 1]
            p.ready = True

    def _chunk_fn(self, start: int) -> Callable:
        """Jitted ``prefill_chunk`` per (static) chunk start; the
        compile cache is bounded by ``max_len / chunk_width``."""
        return functools.partial(_prefill_chunk, self.params, self.cfg,
                                 start=start)

    def _dispatch_chunk(self, p: _PendingAdmit) -> None:
        """Dispatch ONE bounded chunk of a long-prompt admission: a
        width-``_chunk_width`` slice of the prompt forward-passed against
        the accumulated past, its pages scattered into the pool, the
        past extended -- all lazy, queueing behind the in-flight scan.
        The chunk containing the prompt's final position contributes the
        first-token logits; the last chunk marks the admission ready."""
        t0 = time.monotonic()
        c = self._chunk_width
        lo = p.next_start
        w = min(c, p.plen - lo)
        toks = np.zeros((1, c), np.int32)
        toks[0, :w] = p.req.prompt[lo: lo + w]
        kw = {}
        if self._cond is not None:
            kw["cond"] = self._cond
        logits, cc = self._chunk_fn(lo)(
            jnp.asarray(toks), jnp.asarray([p.plen], jnp.int32), p.past,
            **kw)
        self._write_chunk_pages(p.req, cc, lo, p.plen)
        if lo <= p.plen - 1 < lo + c:
            p.logits = logits
        p.next_start = lo + c
        p.chunk_idx += 1
        done = p.next_start >= p.plen
        p.past = None if done else mdl.chunk_past_extend(p.past, cc)
        if done:
            p.ready = True
        if (r := _obs.RECORDER).enabled:
            r.emit("serve.pipeline.admit_chunk", step=self.step_idx,
                   rid=p.req.rid, chunk=p.chunk_idx - 1, tokens=int(w),
                   total=p.plen, wall_ms=(time.monotonic() - t0) * 1e3,
                   done=done)

    def _write_chunk_pages(self, req: Request, cache_chunk, lo: int,
                           plen: int) -> None:
        """Scatter one admission chunk's cache into the request's pages.
        Chunk starts and widths are page-aligned (the constructor rounds
        ``admit_chunk_tokens`` up), so every page is written by exactly
        one chunk; the final page's tail beyond ``plen`` carries padding
        garbage masked attention never reads (as in the packed
        scatter).  Chunked admission is gated to prefix-free configs, so
        chunk-local positions ARE absolute cache positions."""
        pools = self.monitor.pools
        ps = self.page_size
        npg = self._chunk_width // ps
        p0 = lo // ps
        n_valid = min(npg, -(-(plen - lo) // ps))
        gids_m = np.full((1, npg), PAGE_DROP, np.int32)
        gids_m[0, :n_valid] = req.gids[p0: p0 + n_valid]
        slots = pools.assign_slots(req.gids[p0: p0 + n_valid])
        slots_m = np.full((1, npg), PAGE_DROP, np.int32)
        slots_m[0, :n_valid] = slots
        leaves = self._prefill_leaves(cache_chunk,
                                      mdl.state_slot_meta(self.cfg), 0)
        pools.set_kv(write_pages_batched(
            pools.kv_view(), leaves, jnp.asarray(gids_m),
            jnp.asarray(slots_m)))

    def _admit_activate(self) -> List[Tuple[int, int]]:
        """Boundary half of pipelined admission: install every ready
        pending request's row WITHOUT forcing its first token.  The
        sample is pure jnp chained behind the request's prefill, so
        setting it into ``self.tok`` keeps the whole admission lazy and
        the row joins the macro launched later this same step; the
        int() download / tokens append / emit wait for the next
        boundary (``_macro_complete`` resolves ``req._first_tok``), and
        the device scan's init-time stop check covers a first token
        that already hits EOS or the budget.  MUST run after the
        boundary restored ``tok``/``pos`` from the macro's downloaded
        state, or the whole-array assignment would clobber fresh rows."""
        ready = [p for p in self._pending_admits if p.ready]
        if not ready:
            return []
        self._pending_admits = [p for p in self._pending_admits
                                if not p.ready]
        emitted: List[Tuple[int, int]] = []
        for p in ready:
            req = p.req
            req._key = (req.key if req.key is not None
                        else jax.random.PRNGKey(0))
            tok = E._sample(p.logits[:, 0], req._key, req.temperature)
            self.tok = self.tok.at[req.row].set(tok)
            self.pos = self.pos.at[req.row].set(self.prefix + p.plen)
            self.active[req.row] = req
            self._rows_epoch += 1
            p.logits = None
            if req.max_new_tokens <= 1:
                # the row would only freeze at the scan's init check;
                # cheaper to force the (long-dispatched) sample here and
                # retire without ever joining a macro -- exactly the
                # synchronous admission path for a one-token request
                req.tokens.append(int(tok[0]))
                emitted.append((req.rid, req.tokens[-1]))
                self._retire(req)
            else:
                req._first_tok = tok
        if (r := _obs.RECORDER).enabled:
            now = time.monotonic()
            r.emit("serve.admit", step=self.step_idx, joiners=len(ready),
                   pages=int(sum(p.req.n_alloc for p in ready)),
                   queue_depth=len(self.queue),
                   rids=[p.req.rid for p in ready],
                   # queue wait: submit to the reservation
                   wait_ms=[(p.t_reserved - p.req._t_submit) * 1e3
                            for p in ready],
                   # the batch's WORST reservation-to-activation stall:
                   # the admission-latency price of deferring the sample
                   # sync to a boundary (what admit_chunk_tokens trades
                   # boundary stall against)
                   stall_ms=(now - min(p.t_reserved for p in ready)) * 1e3)
            r.count("serve.admitted", len(ready))
            r.gauge("serve.queue_depth", len(self.queue))
        return emitted

    @property
    def idle(self) -> bool:
        """No work left: nothing queued, in flight, pending admission or
        active.  Drive loops (run(), benchmarks, tests) step until this
        holds -- the pipelined loop keeps tail state (an in-flight macro,
        reserved-but-not-activated admissions) past the last queue/active
        emptiness, so checking those two alone would under-drain it."""
        return not (self.queue or self.active or self._pending_admits
                    or self._frozen or self._inflight is not None)

    def run(self, max_steps: int = 10 ** 6) -> Dict[int, List[int]]:
        """Drive until every submitted request completed (or the step
        budget runs out).  Returns rid -> emitted tokens.  The pipelined
        loop additionally drains its in-flight macro and any pending
        (reserved-but-not-activated) admissions: every step ends with the
        decision worker idle, so post-run manager/tuner state is as
        deterministic as the synchronous loop's."""
        steps = 0
        while not self.idle and steps < max_steps:
            self.step()
            steps += 1
        return {r.rid: list(r.tokens) for r in self.completed}

    def close(self) -> None:
        """Tear down the pipelined loop's background decision worker
        (no-op for the synchronous loop).  Call after the last step;
        tests and benchmarks use it to avoid thread buildup.  Safe
        mid-macro and after a worker error: a pending decision
        generation is dropped (never waited on again), and the worker's
        drain-and-join runs even if its last ``fn`` raised -- the error
        stays published in the dead worker, not re-raised here."""
        self._decision_gen = None
        if self._decision_worker is not None:
            self._decision_worker.close()
            self._decision_worker = None

    def _retire(self, req: Request) -> None:
        req.done = True
        req.status = "completed"
        del self.active[req.row]
        self.rows_free.append(req.row)
        self.completed.append(req)
        if self.paged:
            self._hbm_need -= req.n_pages
            self._gid_tables[req.row, :] = -1
            self._rows_epoch += 1
        if self.monitor is not None:
            self.monitor.release(req.gids)
        if (r := _obs.RECORDER).enabled:
            r.emit("serve.retire", step=self.step_idx, rid=req.rid,
                   tokens=len(req.tokens), status=req.status,
                   deadline_ms=(time.monotonic() - req._t_submit) * 1e3
                   if req._t_submit else 0.0)
            r.count("serve.retired")

    # -- shared-pool data path -----------------------------------------------
    def _mirror(self, req: Request, pages) -> None:
        """Write-through the monitor layer's KV pages of one request from
        the packed cache into the shared pools (host + resident slots)."""
        c = self.cache["segments"][self._si][self._sj]
        ps = self.page_size
        for p in pages:
            if 0 <= p < req.n_pages:
                # slice on device: only the touched page crosses to host
                k = c["k"][-1, req.row, p * ps: (p + 1) * ps]
                v = c["v"][-1, req.row, p * ps: (p + 1) * ps]
                self.monitor.pools.write_page(int(req.gids[p]), k, v)

    def paged_context(self, rid: int, q, *, impl: Optional[str] = None):
        """Monitor-layer attention context for one in-flight request,
        gathered by ``kernels.paged_attention`` *from the shared HBM pool*
        through the request's page table (``slot_of`` indirection).  Pages
        are demand-fetched first; returns (context [1,H,D], fetched).

        In fully-paged mode the pool IS the KV store, so this reads the
        monitor slot's layered HBM leaf; in dense mode it needs the
        ``mirror_pages`` write-through."""
        if not (self.paged or self.mirror_pages):
            raise ValueError("paged_context needs fully-paged decode or "
                             "mirror_pages=True over physical pools: "
                             "otherwise the shared pool holds no KV data")
        if self._si is None:
            raise ValueError(f"{self.cfg.name}: no full-attention layer "
                             "to probe with paged_context")
        req = next((r for r in self.active.values() if r.rid == rid), None)
        if req is None:
            raise KeyError(f"request {rid} is not in flight")
        length = int(np.asarray(self.pos)[req.row])
        n = -(-length // self.page_size)
        # paged mode: pages covering positions [0, length) in table order
        # (shared prefix first); dense-mirror mode: the request's own run
        gids = req.table_gids[:n] if self.paged else req.gids[:n]
        pools = self.monitor.pools
        fetched = pools.ensure_resident(gids)
        # demand-fetched pages are on-demand host reads: charge them
        mgr = self.monitor.manager
        mgr.misses += fetched
        mgr.modeled_time += fetched * mgr.cfg.miss_penalty
        table = jnp.asarray(pools.table(gids), jnp.int32)[None]
        lengths = jnp.asarray([length], jnp.int32)
        if self.paged:
            li = mdl.attn_slot_index(self.cfg, self._si, self._sj)
            k_hbm = pools.kv_layers["k_hbm"][li][-1]
            v_hbm = pools.kv_layers["v_hbm"][li][-1]
        else:
            k_hbm, v_hbm = pools.k_hbm, pools.v_hbm
        out = ops.paged_attention(q, k_hbm, v_hbm, table, lengths, impl=impl)
        return out, fetched


# ---------------------------------------------------------------------------
# model-free traffic simulation (same scheduling core, synthetic masses)
# ---------------------------------------------------------------------------


def _sink_pattern(spec: RequestSpec, n_pages: int) -> np.ndarray:
    return W.attention_sink(spec.new_tokens, n_pages,
                            sink_pages=min(2, n_pages),
                            window_pages=min(4, n_pages),
                            seed=spec.seed, drift_every=1)


def _periodic_pattern(spec: RequestSpec, n_pages: int) -> np.ndarray:
    span = max(1, min(8, n_pages - n_pages // 4))
    return W.periodic_context(spec.new_tokens, n_pages, span_pages=span,
                              period=16, seed=spec.seed)


def _random_pattern(spec: RequestSpec, n_pages: int) -> np.ndarray:
    return W.random_lookup(spec.new_tokens, n_pages,
                           touches=min(3, n_pages), seed=spec.seed)


WORKLOAD_KINDS: Dict[str, Callable[[RequestSpec, int], np.ndarray]] = {
    "sink": _sink_pattern,
    "periodic": _periodic_pattern,
    "random": _random_pattern,
}


@dataclasses.dataclass
class _SynthActive:
    spec: RequestSpec
    gids: np.ndarray
    pattern: np.ndarray                # [lifetime, n_pages]
    t: int = 0


class TrafficScheduler:
    """Model-free continuous batching over a ``core.traffic`` request
    stream: admission (Poisson arrivals, FIFO head-of-line), bucket-
    rounded page-aligned allocation from the shared pool, per-step mass
    merge through the ``TrafficMonitor``, retirement on length.
    Deterministic given the stream -- and admission never depends on
    residency or period, so fixed-period replays of the same stream are
    directly comparable (the brute-force sweep the benchmark ranks the
    online tuner against).

    Allocation mirrors the fully-paged batcher: a request holds
    ``bucket_pages(exact, cap=row_pages)`` global pages (its mass pattern
    only ever touches the exact footprint; the bucket tail is allocation
    slack).  ``row_pages`` defaults to the dense provisioning a packed
    ``max_len`` cache would need for this stream -- the longest request's
    page count -- so ``dense_cache_pages`` is the apples-to-apples
    baseline ``peak_cache_pages`` is compared against."""

    def __init__(self, specs: Sequence[RequestSpec], monitor: TrafficMonitor,
                 *, page_size: int = 16, max_active: int = 8,
                 kinds: Optional[Dict[str, Callable]] = None,
                 bucket: bool = True, row_pages: Optional[int] = None,
                 ttl_steps: Optional[int] = None):
        self.pending = collections.deque(
            sorted(specs, key=lambda s: (s.arrival, s.rid)))
        self.monitor = monitor
        self.page_size = page_size
        self.max_active = max_active
        self.kinds = dict(WORKLOAD_KINDS)
        if kinds:
            self.kinds.update(kinds)
        self.bucket = bucket
        self.row_pages = row_pages if row_pages is not None else max(
            (s.n_pages(page_size) for s in specs), default=1)
        #: admission TTL in steps past arrival: a request still queued
        #: ``ttl_steps`` after it arrived is shed (status "expired")
        #: instead of serving stale work under overload; None = FIFO
        #: forever (the fault-free baseline)
        self.ttl_steps = ttl_steps
        self.active: List[_SynthActive] = []
        self.now = 0
        self.admitted = 0
        self.completed = 0
        self.rejected = 0
        self.shed = 0

    @property
    def peak_cache_pages(self) -> int:
        """Peak pages simultaneously allocated (bucket-rounded rows)."""
        return self.monitor.pools.peak_allocated

    @property
    def dense_cache_pages(self) -> int:
        """What the dense packed-cache layout provisions up front:
        ``max_active`` rows of ``row_pages`` each, held for the whole
        run regardless of occupancy."""
        return self.max_active * self.row_pages

    def _pages_alloc(self, n_exact: int) -> int:
        if not self.bucket:
            return n_exact
        return bucket_pages(n_exact, cap=max(self.row_pages, n_exact))

    def step(self) -> None:
        if self.ttl_steps is not None:
            # expiry order is arrival order (the deque is arrival-sorted
            # and the TTL is uniform), so a head scan sheds exactly the
            # expired prefix
            while (self.pending
                   and self.now > self.pending[0].arrival + self.ttl_steps):
                spec = self.pending.popleft()
                self.rejected += 1
                self.shed += 1
                if (r := _obs.RECORDER).enabled:
                    r.emit("serve.shed", step=self.now, rid=spec.rid,
                           reason="deadline", queue_depth=len(self.pending))
                    r.emit("serve.retire", step=self.now, rid=spec.rid,
                           tokens=0, status="expired", deadline_ms=0.0)
                    r.count("serve.shed_total")
                    r.count("serve.retired")
        joiners = pages = 0
        while (self.pending and self.pending[0].arrival <= self.now
               and len(self.active) < self.max_active):
            spec = self.pending[0]
            n_pages = spec.n_pages(self.page_size)
            n_alloc = self._pages_alloc(n_pages)
            if n_alloc > self.monitor.pools.n_logical:
                # can never fit, not even fully drained: dropping it is the
                # only alternative to blocking the queue forever
                self.pending.popleft()
                self.rejected += 1
                continue
            gids = self.monitor.pools.alloc(n_alloc, spec.rid)
            if gids is None:           # head-of-line: keep arrival order
                break
            self.pending.popleft()
            pattern = self.kinds[spec.kind](spec, n_pages)
            self.admitted += 1
            joiners += 1
            pages += n_alloc
            if pattern.shape[0] == 0:      # zero-lifetime: retire at once
                self.monitor.release(gids)
                self.completed += 1
                continue
            self.active.append(_SynthActive(spec, gids, pattern))
        if joiners and (r := _obs.RECORDER).enabled:
            r.emit("serve.admit", step=self.now, joiners=joiners,
                   pages=pages, queue_depth=len(self.pending))
            r.count("serve.admitted", joiners)
            r.gauge("serve.queue_depth", len(self.pending))

        # idle steps are not fed to the monitor (matching the model-backed
        # batcher): an empty lull's near-zero cost would read as a phase
        # change and churn the tuner through spurious re-profiles
        if self.active:
            # mass patterns span the exact footprint only; a bucket's
            # tail pages are allocation slack and never accrue mass
            merged = self.monitor.merge(
                [(a.gids[: a.pattern.shape[1]], a.pattern[a.t])
                 for a in self.active])
            self.monitor.on_step(merged, n_active=len(self.active))
        self.now += 1

        still: List[_SynthActive] = []
        for a in self.active:
            a.t += 1
            if a.t >= a.pattern.shape[0]:
                self.monitor.release(a.gids)
                self.completed += 1
                if (r := _obs.RECORDER).enabled:
                    r.emit("serve.retire", step=self.now, rid=a.spec.rid,
                           tokens=int(a.pattern.shape[0]),
                           status="completed", deadline_ms=0.0)
                    r.count("serve.retired")
            else:
                still.append(a)
        self.active = still

    def run(self, steps: int) -> "TrafficScheduler":
        for _ in range(steps):
            self.step()
        return self
