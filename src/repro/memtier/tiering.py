"""Cori-tuned HBM <-> host KV-page tiering (the paper's technique, adapted).

Mapping (DESIGN.md S3):
    DRAM            -> HBM working set      (hbm_pages physical slots)
    PMEM            -> host backing store   (all logical pages)
    page scheduler  -> ``TieringManager.maybe_tier`` every ``period`` steps
    accessed bits   -> per-page attention mass from the decode step
    move_pages()    -> ``migrate`` (gather/scatter on the physical pools)
    Cori            -> ``repro.core.cori`` tuning ``period`` from the
                       attention-reuse histogram (step domain)

The page-selection rule is the paper's verbatim: EMA hotness ranks pages,
top-capacity hot pages swap in against LRU residents, swaps capped by
capacity.  Costs are modeled with the same structure as ``core.sim`` but
with TPU-tier constants (HBM vs PCIe-host), since this container has no
real TPU clock: a decode step pays 1 unit per resident-page touch,
``miss_penalty`` per non-resident touch (on-demand host fetch), plus
migration and wakeup costs per tiering period.

Invariants the serving scheduler relies on (pinned by tests/test_sched.py
and tests/test_memtier.py):

  * **Page-ID recycling contract.**  A logical page ID freed by
    ``SharedPagedPools.free`` may be handed to a different request by the
    next ``alloc``.  Every consumer of page IDs must therefore be told
    about the free *before* the ID recycles: ``TieringManager.release``
    clears hotness/recency, ``OnlineTuner.forget_pages`` invalidates the
    reuse chain, and the pool itself drops residency and owner.  A
    recycled ID always starts cold, host-only and unowned.
  * **Active-mask semantics.**  ``maybe_tier(active=...)`` ranks only
    pages some request currently owns; unallocated IDs can never enter
    the working set even when capacity exceeds the allocated footprint.
    With ``active=None`` (single-request pools) every ID is rankable and
    the rule reduces bit-exactly to the paper's paired-swap at fixed
    footprint.
  * **One slot table, many layers.**  In the fully-paged serving path the
    pools carry one KV leaf per attention layer
    (``attach_layered_kv``), but residency is per *logical page*: a page
    is resident for all layers or none, and every migration
    (``migrate_slots``) moves all layers' bytes for that page together.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cori, reuse
from repro.ft.inject import MigrationError, NULL_PLAN
from repro.kernels import ops
from repro.obs import telemetry as _obs

__all__ = ["TierConfig", "TieringManager", "PagedPools", "SharedPagedPools",
           "bucket_pages", "write_pages_batched", "write_state_pages"]


def bucket_pages(n_pages: int, cap: Optional[int] = None) -> int:
    """Shape-bucketed allocation size: round a page count up to the next
    power of two, capped at ``cap`` (the cache-row capacity in pages).

    Buckets bound the number of distinct allocation shapes (so jitted
    decode functions and pool scatter patterns are reused across request
    lengths) at a bounded fragmentation cost: a request never holds more
    than 2x its exact page need, and never more than one full row."""
    if n_pages <= 0:
        raise ValueError(f"cannot bucket {n_pages} pages")
    if cap is not None and n_pages > cap:
        raise ValueError(f"{n_pages} pages exceed the {cap}-page row cap")
    b = 1 << (n_pages - 1).bit_length()
    return min(b, cap) if cap is not None else b


@dataclasses.dataclass(frozen=True)
class TierConfig:
    page_size: int = 16            # tokens per KV page
    hbm_pages: int = 0             # working-set capacity (physical slots)
    period_steps: int = 8          # tiering period (what Cori tunes)
    ema_alpha: float = 0.5
    access_threshold: float = 0.05  # attention mass to count as "accessed"
    # modeled costs (units: one HBM page-read)
    miss_penalty: float = 32.0     # on-demand host fetch (PCIe ~25GB/s vs HBM)
    mig_cost: float = 16.0         # async page migration
    wakeup_cost: float = 4.0       # scheduler wakeup per period
    # a demand-fetch issued through ``ensure_resident`` moves all its
    # pages in ONE gathered host->HBM transfer, so a fetched page is
    # cheaper than a mid-kernel on-demand miss (no per-page latency, the
    # transfer amortises): this is what TrafficMonitor charges per
    # ``fetched`` page
    fetch_cost: float = 24.0


@dataclasses.dataclass
class PagedPools:
    """Physical KV page pools for one representative layer group.

    host pools hold every logical page; the HBM pool holds the resident
    working set.  ``slot_of[logical] == -1`` means host-only."""
    k_host: jnp.ndarray            # [n_logical, page, kv, d]
    v_host: jnp.ndarray
    k_hbm: jnp.ndarray             # [hbm_pages, page, kv, d]
    v_hbm: jnp.ndarray
    slot_of: np.ndarray            # int32[n_logical] -> hbm slot | -1
    page_of_slot: np.ndarray       # int32[hbm_pages] -> logical | -1
    #: bumped whenever slot_of changes (page-table caches key on it)
    slot_epoch: int = 0

    @classmethod
    def create(cls, k_pages, v_pages, hbm_pages: int):
        """Interleaved initial residency (paper SII-B initial placement)."""
        from repro.core.sim import interleaved_indices
        n = k_pages.shape[0]
        init = interleaved_indices(n, hbm_pages).astype(np.int32)
        slot_of = np.full((n,), -1, np.int32)
        slot_of[init] = np.arange(hbm_pages)
        return cls(
            k_host=k_pages, v_host=v_pages,
            k_hbm=k_pages[init], v_hbm=v_pages[init],
            slot_of=slot_of,
            page_of_slot=init.copy())

    def touch_slots(self, slots: np.ndarray) -> None:
        """No-op: the fixed single-request pool has no demand-fetch path,
        so slot recency is meaningless here (SharedPagedPools tracks it)."""

    def migrate_slots(self, slots, logicals) -> None:
        """Copy host pages ``logicals`` into HBM ``slots`` (all pools)."""
        if len(slots) == 0 or self.k_host is None:
            return
        sl, lg = jnp.asarray(slots), jnp.asarray(logicals)
        self.k_hbm = _migrate(self.k_hbm, self.k_host, sl, lg)
        self.v_hbm = _migrate(self.v_hbm, self.v_host, sl, lg)


@jax.jit
def _migrate(pool_hbm, pool_host, slots, logicals):
    """Copy host pages `logicals` into HBM `slots` (the move_pages analogue;
    on real hardware this is the pinned_host->device DMA)."""
    return pool_hbm.at[slots].set(pool_host[logicals])


@jax.jit
def _migrate_stacked(pool_hbm, pool_host, slots, logicals):
    """`_migrate` for layer-stacked pools [R, P, page, KV, D]: one page's
    bytes move for every repeat of the layer slot together."""
    return pool_hbm.at[:, slots].set(pool_host[:, logicals])


@functools.partial(jax.jit, donate_argnums=(0,))
def _migrate_all(kv, slots, logicals):
    """One gathered host->HBM transfer for the WHOLE layered pytree: every
    leaf of every layer gathers its ``logicals`` pages and scatters them
    into ``slots`` inside a single jitted launch (donated, so XLA updates
    the pool buffers in place).  Replaces the per-leaf x per-layer
    ``_migrate_stacked`` loop -- L*leaves dispatches collapse into one,
    which is what makes ``ensure_resident`` cheap enough to run as the
    pipelined prefetch stage.  ``slots``/``logicals`` are padded to a
    power of two to bound recompiles: pad logicals with 0 (the gather is
    harmless), pad slots with ``PAGE_DROP`` so the scatter drops them."""
    out = {k: list(v) for k, v in kv.items()}
    for hk in [k for k in kv if k.endswith("_hbm")]:
        dk = hk[:-4] + "_host"
        for i, h in enumerate(kv[hk]):
            if h is None:
                continue
            out[hk][i] = h.at[:, slots].set(kv[dk][i][:, logicals],
                                            mode="drop")
    return out


class SharedPagedPools:
    """One HBM slot pool shared by *all* in-flight requests' KV pages.

    The multi-request generalisation of ``PagedPools``: logical page IDs
    live in one global space sized ``n_logical`` (the allocator's
    capacity), requests allocate page-aligned runs at admission
    (``alloc``) and return them at retirement (``free``, which also evicts
    any HBM slots they held).  ``slot_of[gid]`` is the per-request
    indirection the paged-attention kernel consumes: a request's page
    table of global IDs maps to physical HBM slots via ``table``.

    Two modes:
      * physical -- ``create(..., like=...)`` allocates host/HBM arrays;
        ``write_page`` mirrors KV data and ``ensure_resident`` demand-
        fetches pages the kernel is about to gather.
      * symbolic -- no arrays (``k_host is None``); only the residency and
        allocation bookkeeping runs.  Used by the traffic simulator where
        thousands of scheduler steps replay without touching KV bytes.

    Unlike ``PagedPools`` (fixed single-request footprint, every slot
    always occupied), slots here can be *free* (``page_of_slot == -1``)
    after a retirement; ``TieringManager.maybe_tier`` fills free slots
    before evicting residents.
    """

    def __init__(self, n_logical: int, hbm_pages: int, *,
                 k_host=None, v_host=None, k_hbm=None, v_hbm=None):
        if hbm_pages > n_logical:
            raise ValueError("HBM slot pool larger than the logical space")
        self.n_logical = int(n_logical)
        self.hbm_pages = int(hbm_pages)
        self.k_host, self.v_host = k_host, v_host
        self.k_hbm, self.v_hbm = k_hbm, v_hbm
        # fully-paged mode: one KV leaf per attention layer slot, all
        # indirected by the SAME slot_of table (see attach_layered_kv)
        self.kv_layers: Optional[Dict[str, List[Optional[jnp.ndarray]]]] = None
        self.layer_meta: Tuple = ()
        #: per-layer leaf-name tuples (set by ``attach_layered``)
        self.layer_leaves: Tuple = ()
        #: leaves moved per page migration (tier.move accounting)
        self.move_planes = 2
        self.slot_of = np.full((n_logical,), -1, np.int32)
        self.page_of_slot = np.full((hbm_pages,), -1, np.int32)
        self.owner_of = np.full((n_logical,), -1, np.int64)
        #: fault-injection plan (chaos harness); inert by default
        self.fault_plan = NULL_PLAN
        #: live capacity in pages -- ``hbm_pages`` normally, lower under an
        #: injected ``pool.squeeze`` (the batcher's pressure logic and the
        #: tiering boundary both budget against this, never above it)
        self.effective_hbm = int(hbm_pages)
        #: migrate retry-with-backoff knobs (the degraded ladder's rung 1)
        self.migrate_retries = 2
        self.retry_backoff_s = 0.001
        #: pages whose fast migration path exhausted its retries serve
        #: pinned-to-host for a cooldown: ``apply_plan`` skips promoting
        #: them and every demand fetch takes the degraded slow path,
        #: priced at ``miss_penalty`` (see ``_pin_until``)
        self._pin_until = np.zeros((n_logical,), np.int64)
        self.pin_cooldown = 64
        #: degraded (retry-exhausted) fetches since the caller last drained
        #: this -- the batcher charges them into the tuner's window
        self.degraded_fetches = 0
        #: bumped on every ``slot_of`` mutation -- page-table caches key
        #: on it to skip the per-boundary rebuild + device upload when no
        #: page moved (see ContinuousBatcher's table cache)
        self.slot_epoch = 0
        # free logical ids, popped lowest-first so reuse is deterministic
        self._free_ids: List[int] = list(range(n_logical - 1, -1, -1))
        # per-slot touch tick for the demand-fetch victim choice
        self._slot_tick = np.zeros((hbm_pages,), np.int64)
        self._tick = 0
        # allocation accounting (bucketed rows: benchmarks compare this
        # peak against the dense max_len provisioning)
        self.allocated_pages = 0
        self.peak_allocated = 0

    # -- constructors --------------------------------------------------------
    @classmethod
    def create(cls, n_logical: int, hbm_pages: int, *,
               page_size: Optional[int] = None, kv_heads: int = 0,
               head_dim: int = 0, dtype=jnp.float32) -> "SharedPagedPools":
        """Physical pools when page geometry is given, symbolic otherwise."""
        if page_size is None:
            return cls(n_logical, hbm_pages)
        shape = (n_logical, page_size, kv_heads, head_dim)
        hshape = (hbm_pages,) + shape[1:]
        return cls(n_logical, hbm_pages,
                   k_host=jnp.zeros(shape, dtype),
                   v_host=jnp.zeros(shape, dtype),
                   k_hbm=jnp.zeros(hshape, dtype),
                   v_hbm=jnp.zeros(hshape, dtype))

    def attach_layered(self, layer_specs: Sequence[Tuple[int, Dict[str,
                       Tuple[int, ...]]]], *, dtype=jnp.float32) -> None:
        """Grow per-layer cache storage for the fully-paged decode path
        from *per-geometry leaf specs*: one ``(repeats, {leaf_name:
        trailing_shape})`` entry per state-bearing layer slot.  A plain
        attention slot attaches ``{"k": (page, KV, D), "v": ...}``; an MLA
        slot attaches compressed ``{"ckv": (page, kv_lora), "krope":
        (page, rope)}`` rows; a recurrent slot attaches one fixed-size
        ``{"state": (state_dim,)}`` page per request.  Every leaf is
        stacked over its slot's ``repeats``: host side
        [R, n_logical, *trailing], HBM side [R, hbm_pages, *trailing].
        All leaves share this pool's single ``slot_of`` table -- a logical
        page is resident for every layer or for none, and migrations move
        all of a page's leaves together.  Layers lacking a leaf hold
        ``None`` in that leaf's per-layer list, so mismatched geometries
        can never cross-contaminate."""
        names: List[str] = []
        for _, leaves in layer_specs:
            for name in leaves:
                if name not in names:
                    names.append(name)
        kv: Dict[str, List[Optional[jnp.ndarray]]] = {}
        for name in names:
            for tier in ("hbm", "host"):
                kv[f"{name}_{tier}"] = []
        for r, leaves in layer_specs:
            for name in names:
                if name in leaves:
                    trail = tuple(int(x) for x in leaves[name])
                    kv[f"{name}_host"].append(
                        jnp.zeros((int(r), self.n_logical) + trail, dtype))
                    kv[f"{name}_hbm"].append(
                        jnp.zeros((int(r), self.hbm_pages) + trail, dtype))
                else:
                    kv[f"{name}_host"].append(None)
                    kv[f"{name}_hbm"].append(None)
        self.kv_layers = kv
        self.layer_meta = tuple(int(r) for r, _ in layer_specs)
        self.layer_leaves = tuple(tuple(leaves) for _, leaves in layer_specs)
        # pages_moved accounting: how many per-page planes (leaves) one
        # logical-page migration moves.  The classic (k, v) geometry is 2.
        self.move_planes = max((len(lv) for lv in self.layer_leaves),
                               default=2)
        if (r := _obs.RECORDER).enabled:
            r.emit("pool.attach", layers=len(self.layer_meta),
                   leaves=",".join(names), planes=self.move_planes)

    def attach_layered_kv(self, layer_repeats: Sequence[int], *,
                          page_size: int, kv_heads: int, head_dim: int,
                          dtype=jnp.float32) -> None:
        """Back-compat wrapper over ``attach_layered`` for the classic
        all-attention geometry: one (k, v) leaf pair per attention layer
        slot, [R, n_logical, page, KV, D] host / [R, hbm_pages, ...] HBM."""
        trail = (int(page_size), int(kv_heads), int(head_dim))
        self.attach_layered([(int(r), {"k": trail, "v": trail})
                             for r in layer_repeats], dtype=dtype)

    def kv_view(self) -> Dict[str, List[jnp.ndarray]]:
        """The layered-KV pytree a jitted paged decode step consumes (and
        returns updated; store it back with ``set_kv``)."""
        if self.kv_layers is None:
            raise ValueError("no layered cache attached (attach_layered)")
        return {k: list(v) for k, v in self.kv_layers.items()}

    def set_kv(self, kv: Dict[str, List[jnp.ndarray]]) -> None:
        self.kv_layers = {k: list(v) for k, v in kv.items()}

    # -- views ---------------------------------------------------------------
    @property
    def physical(self) -> bool:
        return self.k_host is not None or self.kv_layers is not None

    @property
    def resident_mask(self) -> np.ndarray:
        return self.slot_of >= 0

    @property
    def allocated_mask(self) -> np.ndarray:
        return self.owner_of >= 0

    @property
    def free_pages(self) -> int:
        return len(self._free_ids)

    def free_slots(self) -> np.ndarray:
        return np.nonzero(self.page_of_slot < 0)[0].astype(np.int32)

    @property
    def hbm_occupied(self) -> int:
        return int((self.page_of_slot >= 0).sum())

    def host_pinned(self, gids: np.ndarray) -> np.ndarray:
        """bool per gid: pinned to host by a retry-exhausted migration
        (cooldown measured in placement ticks)."""
        return self._pin_until[np.asarray(gids, np.int64)] > self._tick

    def table(self, gids: np.ndarray) -> np.ndarray:
        """Physical HBM slot per global page ID (-1 = host-only)."""
        return self.slot_of[np.asarray(gids, np.int64)]

    # -- allocator -----------------------------------------------------------
    def alloc(self, n_pages: int, owner: int) -> Optional[np.ndarray]:
        """Allocate `n_pages` global page IDs for request `owner`; None when
        the logical space cannot fit the request (caller queues it)."""
        if n_pages > len(self._free_ids):
            return None
        gids = np.asarray([self._free_ids.pop() for _ in range(n_pages)],
                          np.int64)
        self.owner_of[gids] = owner
        self.allocated_pages += n_pages
        self.peak_allocated = max(self.peak_allocated, self.allocated_pages)
        if (r := _obs.RECORDER).enabled:
            r.count("pool.alloc_pages", n_pages)
            r.gauge("pool.allocated_frac",
                    self.allocated_pages / self.n_logical)
        return gids

    def free(self, gids: np.ndarray) -> None:
        """Return a retired request's pages; their HBM slots become free."""
        gids = np.asarray(gids, np.int64)
        slots = self.slot_of[gids]
        held = slots[slots >= 0]
        self.page_of_slot[held] = -1
        self.slot_of[gids] = -1
        if held.size:
            self.slot_epoch += 1
        self.owner_of[gids] = -1
        self._free_ids.extend(sorted(gids.tolist(), reverse=True))
        self.allocated_pages -= int(gids.size)
        if (r := _obs.RECORDER).enabled:
            r.count("pool.free_pages", int(gids.size))
            r.gauge("pool.allocated_frac",
                    self.allocated_pages / self.n_logical)
            r.gauge("pool.hbm_resident_frac",
                    float((self.page_of_slot >= 0).sum()) / self.hbm_pages)

    def demote(self, gids: np.ndarray) -> int:
        """Release the HBM slots of ``gids`` WITHOUT freeing the
        allocation: the preemption primitive.  The host copy is
        write-through (every decode step updates both tiers), so dropping
        the slots moves no data and loses no bytes -- a frozen request's
        cache survives intact and the next ``ensure_resident`` fetches it
        back, which is exactly the Cori-visible data movement preemption
        is supposed to be.  Returns the number of slots released."""
        gids = np.asarray(gids, np.int64)
        slots = self.slot_of[gids]
        held = slots[slots >= 0]
        self.page_of_slot[held] = -1
        self.slot_of[gids] = -1
        if held.size:
            self.slot_epoch += 1
            if (r := _obs.RECORDER).enabled:
                r.gauge("pool.hbm_resident_frac",
                        float((self.page_of_slot >= 0).sum())
                        / self.hbm_pages)
        return int(held.size)

    # -- physical data path --------------------------------------------------
    def write_page(self, gid: int, k_page, v_page) -> None:
        """Write one logical page's KV data (host copy; mirrored to the HBM
        slot when resident, the write-through of a decode-step append).
        Legacy single-layer pools only -- the fully-paged path writes its
        layered leaves inside the jitted decode step instead."""
        if self.k_host is None:
            return
        self.k_host = self.k_host.at[gid].set(k_page)
        self.v_host = self.v_host.at[gid].set(v_page)
        slot = int(self.slot_of[gid])
        if slot >= 0:
            self.k_hbm = self.k_hbm.at[slot].set(k_page)
            self.v_hbm = self.v_hbm.at[slot].set(v_page)

    def touch_slots(self, slots: np.ndarray) -> None:
        """Mark slots recently-used for the demand-fetch victim choice
        (called by the tiering pass so freshly-migrated hot pages are not
        the first LRU victims)."""
        self._tick += 1
        self._slot_tick[np.asarray(slots, np.int64)] = self._tick

    def migrate_slots(self, slots, logicals, *, degraded: bool = False)\
            -> None:
        """Copy host pages ``logicals`` into HBM ``slots`` on EVERY
        physical pool: the legacy monitor-layer pair and, in fully-paged
        mode, each attention layer's leaf (one page's bytes move for all
        layers together -- the page is the migration unit, not the
        (page, layer) pair).

        ``degraded=True`` is the retry-exhausted slow path: it models a
        synchronous per-page copy that cannot fail, so the injected
        transport faults are bypassed (the bytes moved are identical --
        only the modeled price differs, charged by the caller)."""
        if len(slots) == 0:
            return
        if not degraded and (plan := self.fault_plan).enabled:
            if (p := plan.fires("pool.migrate_slow")) is not None:
                time.sleep(float(p.value))
            if plan.fires("pool.migrate_fail") is not None:
                raise MigrationError(
                    f"injected migrate_slots failure ({len(slots)} pages)")
        sl, lg = jnp.asarray(slots), jnp.asarray(logicals)
        if self.k_host is not None:
            self.k_hbm = _migrate(self.k_hbm, self.k_host, sl, lg)
            self.v_hbm = _migrate(self.v_hbm, self.v_host, sl, lg)
        if self.kv_layers is not None:
            # one gathered transfer for every leaf of every layer: pad the
            # index vectors to a power of two so the jitted launch is
            # reused across fetch sizes (dropped-scatter padding)
            sl_np = np.asarray(slots, np.int32)
            lg_np = np.asarray(logicals, np.int32)
            pad = (1 << max(0, int(sl_np.size - 1).bit_length())) - sl_np.size
            if pad > 0:
                sl_np = np.concatenate(
                    [sl_np, np.full(pad, PAGE_DROP, np.int32)])
                lg_np = np.concatenate([lg_np, np.zeros(pad, np.int32)])
            self.set_kv(_migrate_all(self.kv_view(), jnp.asarray(sl_np),
                                     jnp.asarray(lg_np)))

    def _place(self, gids: np.ndarray) -> Tuple[List[int], np.ndarray]:
        """Slot bookkeeping shared by ``ensure_resident`` and
        ``assign_slots``: give every non-resident page in ``gids`` an HBM
        slot (free slots first, then evict the least-recently-ensured
        resident outside ``gids``).  Returns (slots, missing)."""
        gids = np.asarray(gids, np.int64)
        if gids.size > self.hbm_pages:
            raise ValueError(f"{gids.size} pages cannot fit the "
                             f"{self.hbm_pages}-slot HBM pool")
        self._tick += 1
        missing = gids[self.slot_of[gids] < 0]
        # slot choice is sequential (each fetch consumes a slot), but the
        # device copies batch into one gather/scatter per pool
        slots: List[int] = []
        for gid in missing.tolist():
            free = np.nonzero(self.page_of_slot < 0)[0]
            occupied = self.hbm_pages - free.size
            if free.size and occupied < self.effective_hbm:
                slot = int(free[0])
            else:
                # at (squeezed) capacity: evict the least-recently-ensured
                # occupied slot outside the protected set; when every
                # occupied slot is protected (a squeeze below the working
                # set), overflow into a free slot rather than fail
                prot = np.zeros(self.hbm_pages, bool)
                prot[self.slot_of[gids[self.slot_of[gids] >= 0]]] = True
                victims = np.nonzero(~prot & (self.page_of_slot >= 0))[0]
                if victims.size:
                    slot = int(victims[np.argmin(self._slot_tick[victims])])
                    self.slot_of[self.page_of_slot[slot]] = -1
                else:
                    slot = int(free[0])
            self.slot_of[gid] = slot
            self.page_of_slot[slot] = gid
            slots.append(slot)
        if missing.size:
            self.slot_epoch += 1
        self._slot_tick[self.slot_of[gids]] = self._tick
        return slots, missing

    def ensure_resident(self, gids: np.ndarray) -> int:
        """Demand-fetch: make every page in `gids` HBM-resident (free slots
        first, then evict the least-recently-ensured resident outside
        `gids`).  Returns the number of pages fetched -- the caller charges
        them as misses.  Raises if `gids` alone exceed the slot pool.

        A failing ``migrate_slots`` (injected transport fault) is retried
        with exponential backoff; on exhaustion the fetch falls back to
        the degraded slow path -- the bytes still move (token parity is
        never traded away), but the pages pin to host for a cooldown and
        the fetch is counted in ``degraded_fetches`` so the serving loop
        can charge it at ``miss_penalty`` into the tuner's window."""
        with _obs.RECORDER.span("pool.ensure_resident"):
            slots, missing = self._place(gids)
            if missing.size:
                self._migrate_with_retry(slots, missing)
        if missing.size and (r := _obs.RECORDER).enabled:
            r.count("pool.fetch_misses", int(missing.size))
            r.gauge("pool.hbm_resident_frac",
                    float((self.page_of_slot >= 0).sum()) / self.hbm_pages)
        return int(missing.size)

    def _migrate_with_retry(self, slots, logicals) -> None:
        """``migrate_slots`` with bounded retry-with-backoff, then the
        degraded pinned-to-host fallback (see ``ensure_resident``)."""
        delay = self.retry_backoff_s
        for attempt in range(self.migrate_retries + 1):
            try:
                self.migrate_slots(slots, logicals)
                return
            except MigrationError:
                if attempt < self.migrate_retries and delay > 0:
                    time.sleep(delay)
                    delay *= 2
        self.migrate_slots(slots, logicals, degraded=True)
        lg = np.asarray(logicals, np.int64)
        self._pin_until[lg] = self._tick + self.pin_cooldown
        self.degraded_fetches += int(lg.size)
        if (r := _obs.RECORDER).enabled:
            r.count("pool.degraded_fetches", int(lg.size))

    def assign_slots(self, gids: np.ndarray) -> np.ndarray:
        """``ensure_resident`` without the host->HBM byte copy: the caller
        is about to overwrite the pages' content on BOTH tiers in one
        device scatter (``write_pages_batched``), so migrating the stale
        bytes first would be wasted PCIe traffic.  Returns the HBM slot of
        every page in ``gids`` (all resident on return)."""
        self._place(gids)
        return self.slot_of[np.asarray(gids, np.int64)].copy()


PAGE_DROP = np.int32(2 ** 30)      # out-of-range scatter index => dropped


@functools.partial(jax.jit, donate_argnums=(0,))
def write_pages_batched(kv, new_leaves, gids, slots):
    """On-device prefill scatter: write a packed-prefill step's cache rows
    for EVERY token-paged leaf and EVERY joiner straight into the layered
    page pools, host and HBM tiers together, in one jitted gather/scatter.

    kv:          the layered pool pytree (``SharedPagedPools.kv_view``;
                 donated -- XLA updates the pool buffers in place).
    new_leaves:  {leaf_name: [per-layer arrays or None]}, each array
                 [R, J, smax, *rest]: the batched-prefill cache rows of
                 the J joiners (right-padded to smax).  ``rest`` is the
                 leaf's per-token trailing shape -- (KV, D) for k/v,
                 (kv_lora,) for MLA ckv, (rope,) for krope.
    gids/slots:  int32[J, n_max] logical page ids / HBM slot ids per
                 joiner page; entries >= the pool size (``PAGE_DROP``)
                 are dropped -- the ragged padding of short prompts.

    Replaces the host-side per-request x per-layer x per-tensor ``.at``
    loop: J*L*leaves separate dispatches collapse into one launch, and the
    prefill bytes never take the host detour (on TPU they go HBM->HBM).
    """
    j, n_max = gids.shape
    gidf = gids.reshape(-1)
    slotf = slots.reshape(-1)
    out = {k: list(v) for k, v in kv.items()}
    for name, layers in new_leaves.items():
        for li, new in enumerate(layers):
            if new is None:
                continue
            ps = kv[f"{name}_host"][li].shape[2]
            r, _, smax = new.shape[:3]
            rest = new.shape[3:]
            pad = n_max * ps - smax
            if pad > 0:
                new = jnp.pad(new, ((0, 0), (0, 0), (0, pad))
                              + ((0, 0),) * len(rest))
            pages = new[:, :, : n_max * ps].reshape((r, j * n_max, ps)
                                                    + rest)
            out[f"{name}_host"][li] = out[f"{name}_host"][li].at[
                :, gidf].set(pages, mode="drop")
            out[f"{name}_hbm"][li] = out[f"{name}_hbm"][li].at[
                :, slotf].set(pages, mode="drop")
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def write_state_pages(kv, states, gids, slots):
    """Scatter recurrent state pages into the pool, both tiers at once.
    ``states``: one [R, J, state_dim] leaf (or None) per layer slot;
    ``gids``/``slots``: int32[J] -- each joiner's single state page
    (``PAGE_DROP`` entries are dropped)."""
    out = {k: list(v) for k, v in kv.items()}
    for li, st in enumerate(states):
        if st is None:
            continue
        out["state_host"][li] = out["state_host"][li].at[:, gids].set(
            st, mode="drop")
        out["state_hbm"][li] = out["state_hbm"][li].at[:, slots].set(
            st, mode="drop")
    return out


class TieringManager:
    """Periodic page scheduler over a PagedPools working set."""

    _obs_count = 0          # process-wide id counter for telemetry streams

    def __init__(self, n_logical: int, cfg: TierConfig,
                 access_log_len: int = 65536):
        self.cfg = cfg
        self.n = n_logical
        self.hotness = np.zeros(n_logical, np.float64)
        self.last_access = np.full(n_logical, -1.0)
        self.step = 0
        # accessed page ids per step, bounded: the manager lives inside the
        # serving loop, and the online path reads reuse from the tuner's
        # StreamingReuseCollector, not from this log (which feeds the
        # offline `reuse_histogram`/`cori_candidates` flow)
        self.access_log: "collections.deque[np.ndarray]" = collections.deque(
            maxlen=access_log_len)
        self.counts_since_tier = np.zeros(n_logical, np.float64)
        # live tiering period (what online Cori drives); counted against the
        # steps elapsed since the last tier so period changes apply cleanly
        # mid-run
        self.period = max(1, int(cfg.period_steps))
        self._since_tier = 0
        # accounting
        self.migrations = 0
        self.modeled_time = 0.0
        self.data_moved_pages = 0
        self.hits = 0
        self.misses = 0
        TieringManager._obs_count += 1
        #: short id tagging this instance's telemetry events ("m1", ...)
        self.obs_id = f"m{TieringManager._obs_count}"

    def set_period(self, period_steps: int) -> None:
        """Change the tiering period live (the online-Cori control knob)."""
        self.period = max(1, int(period_steps))

    def _tier_due(self) -> bool:
        if self._since_tier < self.period:
            return False
        self._since_tier = 0
        return True

    # -- monitor -----------------------------------------------------------
    def on_step(self, page_mass: np.ndarray, resident: np.ndarray,
                weight: float = 1.0):
        """page_mass: f32[n_logical] attention mass this decode step;
        resident: bool[n_logical].

        ``weight`` is the number of token-steps this mass sample spans
        (1 on the per-token path; the macro length when accessed bits are
        sampled once per movement period).  Hotness counts and hit/miss
        service costs scale by it, so a page touched every token accrues
        the same modeled cost whether the host observed it once or
        ``weight`` times -- without this, a longer period would look
        cheaper purely because it was sampled less often."""
        accessed = page_mass >= self.cfg.access_threshold
        ids = np.nonzero(accessed)[0].astype(np.int32)
        self.access_log.append(ids)
        self.counts_since_tier[accessed] += weight
        self.last_access[accessed] = self.step
        hits = accessed & resident
        misses = accessed & ~resident
        self.hits += int(weight * hits.sum())
        self.misses += int(weight * misses.sum())
        self.modeled_time += weight * (hits.sum() * 1.0
                                       + misses.sum() * self.cfg.miss_penalty)
        self.step += 1
        self._since_tier += 1

    # -- multi-request bookkeeping -------------------------------------------
    def release(self, ids: np.ndarray) -> None:
        """Forget retired pages (a request left the system): their hotness
        must not keep dead logical IDs ranked into the working set, and a
        recycled ID must start cold.  The bounded ``access_log`` is left
        as-is -- it feeds the offline histogram flow only, which the
        multi-request scheduler does not use (it reads reuse from the
        OnlineTuner's collector, which gets its own ``forget``)."""
        ids = np.asarray(ids, np.int64)
        self.hotness[ids] = 0.0
        self.counts_since_tier[ids] = 0.0
        self.last_access[ids] = -1.0

    # -- the page scheduler (paper SII-B swap rule) --------------------------
    def _rank_desired(self, resident: np.ndarray,
                      active: Optional[np.ndarray] = None) -> np.ndarray:
        """EMA-update hotness and rank the desired working set (the paper's
        swap rule): hotness primary, recency secondary, residency tertiary.
        With an ``active`` mask (multi-request mode) only allocated pages
        are rankable, so the desired set may be smaller than capacity."""
        a = self.cfg.ema_alpha
        self.hotness = a * self.counts_since_tier + (1 - a) * self.hotness
        self.counts_since_tier[:] = 0.0
        score = (self.hotness * 1e6
                 + (self.last_access + 1) / (self.step + 1)
                 + 0.5 * resident)
        desired_set = np.zeros(self.n, bool)
        if active is None:
            desired = np.argsort(-score, kind="stable")[: self.cfg.hbm_pages]
        else:
            ids = np.nonzero(active)[0]
            order = np.argsort(-score[ids], kind="stable")
            desired = ids[order[: self.cfg.hbm_pages]]
        desired_set[desired] = True
        return desired_set

    def _plan_swaps(self, resident: np.ndarray, desired_set: np.ndarray,
                    n_free: int) -> Tuple[np.ndarray, np.ndarray]:
        """(bring, evict) realising the desired set: fill free capacity
        first, then evict lazily (a resident-but-undesired page costs
        nothing to keep and can only save future misses).  Because the
        desired set never exceeds capacity, every desired page is brought
        in.  ``n_free == 0`` reduces to the classic paired-swap rule."""
        bring = np.nonzero(desired_set & ~resident)[0]
        evict = np.nonzero(resident & ~desired_set)[0]
        n_bring = min(len(bring), n_free + len(evict))
        n_evict = max(0, n_bring - n_free)
        return bring[:n_bring], evict[:n_evict]

    def plan_tier(self, resident: np.ndarray, n_free: int,
                  active: Optional[np.ndarray] = None, *,
                  planes: int = 2, force: bool = False
                  ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """The decision half of ``maybe_tier``: gate on the period cadence,
        EMA-rank, plan the swaps, and charge the period's modeled cost --
        all from a residency *snapshot*, never touching a pool.  This is
        what the pipelined serving loop runs on its background decision
        thread (the pools stay owned by the dispatch thread).  Returns
        ``(bring, evict)``, or ``None`` when no boundary is due.  Cost is
        charged at plan time: the plan is deterministic from the snapshot,
        so sync and async modes account identically."""
        if self.step == 0:
            return None
        if force:
            self._since_tier = 0
        elif not self._tier_due():
            return None
        cfg = self.cfg
        desired_set = self._rank_desired(resident, active)
        bring, evict = self._plan_swaps(resident, desired_set, int(n_free))
        n_mig = len(bring)
        self.migrations += int(n_mig)
        # planes x = one plane per leaf of the pool's geometry (k + v for
        # classic attention, ckv + krope for MLA, 1 for state-only pools);
        # evictions move no data (the host copy is write-through, dropping
        # a slot is free)
        self.data_moved_pages += planes * int(n_mig)
        self.modeled_time += n_mig * cfg.mig_cost + cfg.wakeup_cost
        if (r := _obs.RECORDER).enabled:
            r.emit("tier.move", manager=self.obs_id, step=self.step,
                   period=self.period, promoted=int(n_mig),
                   evicted=int(len(evict)), pages_moved=planes * int(n_mig),
                   cost=float(n_mig * cfg.mig_cost + cfg.wakeup_cost))
            r.count("tier.pages_moved", planes * int(n_mig))
        return bring, evict

    def apply_plan(self, pools: PagedPools, bring: np.ndarray,
                   evict: np.ndarray) -> None:
        """Actuate a ``plan_tier`` decision on the live pools, revalidating
        against state that may have moved since the snapshot was taken (in
        async mode requests retire and demand-fetches land between plan
        and apply): bring entries a demand-fetch already made resident and
        evict entries that already left HBM are dropped, and the free-slot
        arithmetic is recomputed against the live pool.  (A bring of a
        since-freed ID is deliberately NOT filtered: the sync rule can
        promote score-zero unallocated IDs into spare capacity, and the
        write-through invariant makes the stale copy harmless.)  On the
        synchronous path the snapshot IS the live state and the
        revalidation passes everything through unchanged."""
        resident = pools.slot_of >= 0
        bring = np.asarray(bring, np.int64)
        evict = np.asarray(evict, np.int64)
        bring = bring[~resident[bring]]
        evict = evict[resident[evict]]
        if hasattr(pools, "host_pinned"):
            # retry-exhausted pages sit out the promotion plan until their
            # cooldown lapses (they still demand-fetch via the degraded
            # path when the kernel needs them)
            bring = bring[~pools.host_pinned(bring)]
        free_slots = np.nonzero(pools.page_of_slot < 0)[0]
        n_free = len(free_slots)
        if hasattr(pools, "effective_hbm"):
            # a capacity squeeze shrinks usable spare slots; swaps against
            # evictions stay allowed (occupancy does not grow)
            occupied = pools.page_of_slot.size - n_free
            n_free = min(n_free, max(0, pools.effective_hbm - occupied))
        n_bring = min(len(bring), n_free + len(evict))
        n_evict = max(0, n_bring - n_free)
        bring, evict = bring[:n_bring], evict[:n_evict]
        n_mig = len(bring)
        if not n_mig:
            return
        evict_slots = pools.slot_of[evict].copy()
        slots = np.concatenate([
            free_slots[: n_mig - len(evict)],
            evict_slots]).astype(pools.slot_of.dtype)
        pools.slot_of[evict] = -1
        pools.slot_of[bring] = slots
        pools.page_of_slot[slots] = bring
        pools.slot_epoch = getattr(pools, "slot_epoch", 0) + 1
        pools.touch_slots(slots)   # shared pools track slot recency
        try:
            pools.migrate_slots(slots, bring)
        except MigrationError as e:
            # roll the slot bookkeeping back: the promoted pages stay
            # host-resident (a later demand fetch will retry them through
            # the backoff path) and the evicted residents keep their slots
            pools.slot_of[bring] = -1
            pools.page_of_slot[slots] = -1
            pools.slot_of[evict] = evict_slots
            pools.page_of_slot[evict_slots] = evict
            pools.slot_epoch += 1
            if (r := _obs.RECORDER).enabled:
                r.emit("tier.move_failed", manager=self.obs_id,
                       step=self.step, pages=int(n_mig), attempts=1,
                       detail=str(e))
                r.count("tier.moves_failed")

    def maybe_tier(self, pools: PagedPools,
                   active: Optional[np.ndarray] = None,
                   force: bool = False) -> PagedPools:
        """``force=True`` tiers regardless of the step cadence -- the
        macro-step serving loop wakes the host exactly once per movement
        period, so every wakeup IS a tiering boundary."""
        n_free = int((pools.page_of_slot < 0).sum())
        if hasattr(pools, "effective_hbm"):
            occupied = pools.page_of_slot.size - n_free
            n_free = min(n_free, max(0, pools.effective_hbm - occupied))
        plan = self.plan_tier(pools.slot_of >= 0, n_free, active,
                              planes=int(getattr(pools, "move_planes", 2)),
                              force=force)
        if plan is not None:
            self.apply_plan(pools, *plan)
        return pools

    def maybe_tier_symbolic(self, resident: np.ndarray,
                            active: Optional[np.ndarray] = None) -> bool:
        """Tiering over symbolic residency (no physical pools): same swap
        rule and accounting as ``maybe_tier``, used for fast period trials
        and the traffic simulator.  Mutates ``resident`` in place; returns
        whether a tier happened."""
        if self.step == 0 or not self._tier_due():
            return False
        desired_set = self._rank_desired(resident, active)
        n_free = self.cfg.hbm_pages - int(resident.sum())
        bring, evict = self._plan_swaps(resident, desired_set, n_free)
        n_mig = len(bring)
        self.migrations += n_mig
        self.data_moved_pages += 2 * n_mig
        self.modeled_time += n_mig * self.cfg.mig_cost + self.cfg.wakeup_cost
        if (r := _obs.RECORDER).enabled:
            r.emit("tier.move", manager=self.obs_id, step=self.step,
                   period=self.period, promoted=int(n_mig),
                   evicted=int(len(evict)), pages_moved=2 * int(n_mig),
                   cost=float(n_mig * self.cfg.mig_cost
                              + self.cfg.wakeup_cost))
            r.count("tier.pages_moved", 2 * int(n_mig))
        resident[evict] = False
        resident[bring] = True
        return True

    # -- Cori integration ----------------------------------------------------
    def reuse_histogram(self, bin_width: int = 4) -> reuse.ReuseHistogram:
        """Reuse distances in the decode-step domain from the access log."""
        last = np.full(self.n, -1)
        gaps: List[int] = []
        for t, ids in enumerate(self.access_log):
            prev = last[ids]
            gaps.extend((t - prev[prev >= 0]).tolist())
            last[ids] = t
        h = reuse.loop_duration_histogram(np.asarray(gaps, np.int64),
                                          bin_width=bin_width)
        return reuse.prune_insignificant(h)

    def cori_candidates(self, horizon_steps: int) -> np.ndarray:
        hist = self.reuse_histogram()
        dr = cori.dominant_reuse(hist)
        return cori.candidate_periods(dr, float(horizon_steps),
                                      min_period=1.0)

