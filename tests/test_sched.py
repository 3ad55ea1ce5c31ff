"""Continuous-batching scheduler: shared page pool, traffic-fed tuning.

Covers the PR-2 tentpole: SharedPagedPools allocation/eviction across
requests, multi-request tiering with free slots and active masks, global
page-ID reuse collection (including ID recycling), the TrafficScheduler's
admission/retire path, the end-state acceptance vs a fixed-period sweep,
and the model-backed ContinuousBatcher's token parity with per-request
generate over the shared pool."""
import dataclasses

import numpy as np
import pytest

from repro.core import OnlineTuner, StreamingReuseCollector, RequestSpec
from repro.core.traffic import poisson_request_stream, shifting_mix_stream
from repro.memtier import (SharedPagedPools, TierConfig, TieringManager)
from repro.serve.sched import (TrafficMonitor, TrafficScheduler,
                               WORKLOAD_KINDS)

CFG = TierConfig(page_size=16, hbm_pages=8, period_steps=4)


# ---------------------------------------------------------------------------
# SharedPagedPools: allocation, eviction, recycling
# ---------------------------------------------------------------------------


def test_shared_pool_alloc_free_recycle():
    pools = SharedPagedPools.create(8, 4)
    a = pools.alloc(3, owner=0)
    b = pools.alloc(3, owner=1)
    np.testing.assert_array_equal(a, [0, 1, 2])
    np.testing.assert_array_equal(b, [3, 4, 5])
    assert pools.alloc(3, owner=2) is None, "over-capacity must queue"
    assert pools.free_pages == 2
    pools.free(a)
    c = pools.alloc(4, owner=2)
    np.testing.assert_array_equal(c, [0, 1, 2, 6])  # freed ids recycle
    assert (pools.owner_of[c] == 2).all()


def test_shared_pool_free_evicts_slots():
    pools = SharedPagedPools.create(8, 4)
    gids = pools.alloc(4, owner=0)
    pools.ensure_resident(gids)
    assert (pools.slot_of[gids] >= 0).all()
    assert len(pools.free_slots()) == 0
    pools.free(gids)
    assert (pools.slot_of[gids] == -1).all()
    assert len(pools.free_slots()) == 4, "retired pages release their slots"


def test_ensure_resident_demand_fetch_counts_and_evicts():
    pools = SharedPagedPools.create(16, 4)
    a = pools.alloc(4, owner=0)
    b = pools.alloc(4, owner=1)
    assert pools.ensure_resident(a) == 4
    assert pools.ensure_resident(a) == 0, "already resident: no fetch"
    assert pools.ensure_resident(b[:2]) == 2, "evicts a's LRU slots"
    resident_b = pools.slot_of[b[:2]]
    assert (resident_b >= 0).all()
    assert (pools.slot_of[a] >= 0).sum() == 2
    with pytest.raises(ValueError, match="cannot fit"):
        pools.ensure_resident(np.arange(5))


def test_multi_request_tiering_fills_freed_slots_without_evicting():
    """After a retirement, maybe_tier brings new hot pages into the freed
    slots and keeps still-useful residents (lazy eviction)."""
    pools = SharedPagedPools.create(16, 4)
    mgr = TieringManager(16, dataclasses.replace(CFG, hbm_pages=4,
                                                 period_steps=1))
    a = pools.alloc(4, owner=0)
    mass = np.zeros(16, np.float32)
    mass[a] = 1.0
    for _ in range(4):
        mgr.on_step(mass, pools.resident_mask)
        mgr.maybe_tier(pools, active=pools.allocated_mask)
    assert (pools.slot_of[a] >= 0).all()
    # request 0 retires two pages; request 1 arrives hot
    mgr.release(a[2:])
    pools.free(a[2:])
    b = pools.alloc(2, owner=1)
    migs = mgr.migrations
    mass = np.zeros(16, np.float32)
    mass[a[:2]] = 1.0
    mass[b] = 1.0
    for _ in range(4):
        mgr.on_step(mass, pools.resident_mask)
        mgr.maybe_tier(pools, active=pools.allocated_mask)
    assert (pools.slot_of[b] >= 0).all(), "new request's pages tier in"
    assert (pools.slot_of[a[:2]] >= 0).all(), "live residents not evicted"
    assert mgr.migrations - migs == 2, "exactly the freed slots were filled"


def test_active_mask_keeps_unallocated_pages_out():
    """Pages no request owns must never enter the working set even when
    capacity exceeds the allocated footprint."""
    pools = SharedPagedPools.create(32, 8)
    mgr = TieringManager(32, dataclasses.replace(CFG, hbm_pages=8,
                                                 period_steps=1))
    gids = pools.alloc(3, owner=0)
    mass = np.zeros(32, np.float32)
    mass[gids] = 1.0
    for _ in range(6):
        mgr.on_step(mass, pools.resident_mask)
        mgr.maybe_tier(pools, active=pools.allocated_mask)
    resident = np.nonzero(pools.resident_mask)[0]
    assert set(resident.tolist()) <= set(gids.tolist())


# ---------------------------------------------------------------------------
# global page-ID reuse collection and recycling
# ---------------------------------------------------------------------------


def test_collector_forget_blocks_cross_owner_gaps():
    col = StreamingReuseCollector(8, bin_width=1)
    col.observe(np.array([3]))          # owner A touches page 3 at t=0
    col.forget(np.array([3]))           # A retires, id 3 recycled
    col.observe(np.array([3]))          # owner B touches page 3 at t=1
    assert col.num_samples == 0, "cross-owner gap must not be recorded"
    col.observe(np.array([3]))          # B re-touches: a real gap
    assert col.num_samples == 1


def test_tuner_forget_pages_delegates():
    tuner = OnlineTuner(8, bin_width=1)
    tuner.on_step(accessed_ids=np.array([2]), cost=1.0)
    tuner.forget_pages(np.array([2]))
    tuner.on_step(accessed_ids=np.array([2]), cost=1.0)
    assert tuner.collector.num_samples == 0


def test_monitor_release_clears_everything():
    pools = SharedPagedPools.create(16, 4)
    mgr = TieringManager(16, dataclasses.replace(CFG, hbm_pages=4,
                                                 period_steps=1))
    tuner = OnlineTuner(16, bin_width=1)
    mon = TrafficMonitor(pools, mgr, tuner)
    gids = pools.alloc(3, owner=7)
    mass = np.zeros(16, np.float32)
    mass[gids] = 1.0
    for _ in range(3):
        mon.on_step(mass, n_active=1)
    assert mgr.hotness[gids].sum() > 0
    mon.release(gids)
    assert mgr.hotness[gids].sum() == 0
    assert (mgr.last_access[gids] == -1).all()
    assert (tuner.collector.last_access[gids] == -1).all()
    assert pools.free_pages == 16
    assert (pools.slot_of[gids] == -1).all()


def test_monitor_merge_is_max_per_page():
    pools = SharedPagedPools.create(8, 4)
    mgr = TieringManager(8, CFG)
    mon = TrafficMonitor(pools, mgr)
    m = mon.merge([(np.array([0, 1]), np.array([0.5, 0.2], np.float32)),
                   (np.array([1, 2]), np.array([0.9, 0.1], np.float32))])
    np.testing.assert_allclose(m[:4], [0.5, 0.9, 0.1, 0.0])


# ---------------------------------------------------------------------------
# traffic stream + scheduler
# ---------------------------------------------------------------------------


def test_poisson_stream_reproducible_and_phased():
    a = poisson_request_stream(100, 0.2, {"sink": 1.0}, seed=3)
    b = poisson_request_stream(100, 0.2, {"sink": 1.0}, seed=3)
    assert a == b
    mix = shifting_mix_stream([(50, 0.2, {"random": 1.0}),
                               (50, 0.2, {"sink": 1.0})], seed=1)
    assert all(s.kind == "random" for s in mix if s.arrival < 50)
    assert all(s.kind == "sink" for s in mix if s.arrival >= 50)
    assert [s.rid for s in mix] == list(range(len(mix)))
    spec = RequestSpec(rid=0, arrival=0, prompt_len=17, new_tokens=30,
                       kind="sink", seed=0)
    assert spec.n_pages(16) == 3, "page-aligned allocation rounds up"


def _traffic(specs, steps, *, period=8, tuner=None, n_logical=128,
             hbm=16, page=16, max_active=6, probe_at=None):
    pools = SharedPagedPools.create(n_logical, hbm)
    mgr = TieringManager(n_logical, TierConfig(
        page_size=page, hbm_pages=hbm, period_steps=period))
    sched = TrafficScheduler(specs, TrafficMonitor(pools, mgr, tuner),
                             page_size=page, max_active=max_active)
    probe = 0.0
    for t in range(steps):
        if t == probe_at:
            probe = mgr.modeled_time
        sched.step()
    return sched, mgr, probe


def test_traffic_scheduler_admits_and_retires():
    specs = poisson_request_stream(120, 0.15, {"sink": 0.5, "random": 0.5},
                                   prompt_len=(8, 32), new_tokens=(16, 40),
                                   seed=2)
    sched, mgr, _ = _traffic(specs, 400)
    assert sched.admitted == len(specs)
    assert sched.completed == len(specs), "all requests must drain"
    assert sched.monitor.pools.free_pages == 128, "all pages returned"
    assert mgr.hits + mgr.misses > 0


def test_traffic_scheduler_head_of_line_admission_order():
    """Admission is FIFO even when a later, smaller request would fit."""
    specs = [RequestSpec(0, 0, 40 * 16 - 8, 8, "sink", 0),    # 40 pages
             RequestSpec(1, 0, 40 * 16 - 8, 8, "sink", 1),    # 40 pages
             RequestSpec(2, 0, 8, 8, "sink", 2)]              # 1 page
    sched, _, _ = _traffic(specs, 3, n_logical=64, hbm=16)
    assert sched.admitted == 1, "head-of-line blocks; order is preserved"


def test_impossible_requests_rejected_not_deadlocked():
    """A request larger than the whole logical space can never admit; it is
    dropped (TrafficScheduler) or refused at submit (ContinuousBatcher)
    instead of blocking the queue forever."""
    specs = [RequestSpec(0, 0, 100 * 16 - 8, 8, "sink", 0),   # 100 pages
             RequestSpec(1, 0, 8, 8, "sink", 1)]              # 1 page
    sched, _, _ = _traffic(specs, 3, n_logical=64, hbm=16)
    assert sched.rejected == 1
    assert sched.admitted == 1, "the queue keeps moving"


def test_traffic_replay_deterministic():
    specs = poisson_request_stream(80, 0.2, {"sink": 1.0}, seed=5)
    _, m1, _ = _traffic(specs, 200)
    _, m2, _ = _traffic(specs, 200)
    assert m1.modeled_time == m2.modeled_time
    assert m1.migrations == m2.migrations


def test_admission_independent_of_period():
    """Fixed-period replays of one stream admit/retire identically -- the
    property that makes the brute-force sweep comparable."""
    specs = poisson_request_stream(100, 0.2, {"sink": 1.0}, seed=4)
    s1, _, _ = _traffic(specs, 300, period=1)
    s2, _, _ = _traffic(specs, 300, period=64)
    assert (s1.admitted, s1.completed) == (s2.admitted, s2.completed)


# ---------------------------------------------------------------------------
# the acceptance: scheduler-fed tuner vs brute-force sweep
# ---------------------------------------------------------------------------


def test_traffic_online_tuner_within_5pct_of_best_fixed():
    """PR-2 acceptance: on a Poisson stream whose mix shifts mid-run, the
    scheduler-fed OnlineTuner's end-state modeled cost is within 5% of the
    best fixed period found by sweeping."""
    phase = 700
    steps, window = 2 * phase, 150
    lo = steps - window
    specs = shifting_mix_stream(
        [(phase, 0.10, {"random": 1.0}), (phase, 0.10, {"sink": 1.0})],
        prompt_len=(16, 48), new_tokens=(40, 100), seed=0)
    kw = dict(n_logical=256, hbm=32, page=16, max_active=8)

    tuner = OnlineTuner(256, default_period=8, drift_ratio=1.5,
                        drift_patience=3)
    _, mgr, probe = _traffic(specs, steps, tuner=tuner, probe_at=lo, **kw)
    online_steady = (mgr.modeled_time - probe) / window
    assert tuner.retunes >= 2, "the mix shift must trigger a re-tune"

    best = np.inf
    for p in (1, 2, 4, 8, 16, 32, 64):
        _, m, pr = _traffic(specs, steps, period=p, probe_at=lo, **kw)
        best = min(best, (m.modeled_time - pr) / window)
    assert online_steady <= 1.05 * best, \
        f"online {online_steady:.1f} vs best fixed {best:.1f}"


# ---------------------------------------------------------------------------
# model-backed ContinuousBatcher (token parity over the shared pool)
# ---------------------------------------------------------------------------


def _tiny_serving_stack(cfg, params, *, n_logical=48, hbm=16, page=4):
    pools = SharedPagedPools.create(n_logical, hbm, page_size=page,
                                    kv_heads=cfg.num_kv_heads,
                                    head_dim=cfg.head_dim)
    mgr = TieringManager(n_logical, TierConfig(page_size=page,
                                               hbm_pages=hbm,
                                               period_steps=2))
    tuner = OnlineTuner(n_logical, default_period=2, profile_steps=8,
                        trial_steps=4)
    return TrafficMonitor(pools, mgr, tuner)


def test_batcher_token_parity_with_generate():
    """Multi-request decode over SharedPagedPools emits token-identical
    output to per-request generate (greedy and temperature sampling),
    across staggered admission and row reuse."""
    import jax
    import jax.numpy as jnp
    import repro.configs as C
    from repro.models import model as mdl
    from repro.serve.engine import generate
    from repro.serve.sched import ContinuousBatcher, Request

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (6, 9, 5)]
    keys = [jax.random.PRNGKey(10 + i) for i in range(3)]
    steps = [6, 4, 7]
    temps = [0.0, 0.7, 0.7]

    mon = _tiny_serving_stack(cfg, params)
    b = ContinuousBatcher(params, cfg, max_active=2, max_len=32, page_size=4,
                          monitor=mon, mirror_pages=True)
    b.submit(Request(rid=0, prompt=prompts[0], max_new_tokens=steps[0],
                     key=keys[0], temperature=temps[0]))
    b.submit(Request(rid=1, prompt=prompts[1], max_new_tokens=steps[1],
                     key=keys[1], temperature=temps[1]))
    events = []
    for t in range(40):
        if t == 2:   # joins mid-flight, lands in a recycled row
            b.submit(Request(rid=2, prompt=prompts[2],
                             max_new_tokens=steps[2], key=keys[2],
                             temperature=temps[2]))
        events.extend(b.step())
        if not b.queue and not b.active:
            break
    got = {r.rid: r.tokens for r in b.completed}
    for i in range(3):
        ref = np.asarray(generate(params, cfg, jnp.asarray(prompts[i])[None],
                                  steps=steps[i], temperature=temps[i],
                                  key=keys[i]))[0].tolist()
        assert got[i] == ref, f"request {i} diverged from generate"
        streamed = [tok for rid, tok in events if rid == i]
        assert streamed == ref, \
            f"step()'s emitted stream must carry request {i}'s full output"
    assert mon.pools.free_pages == mon.pools.n_logical


def test_batcher_retires_on_eos():
    """A sampled EOS retires the request early (pages released, row
    recycled), truncating exactly at the EOS token of the generate-
    equivalent stream."""
    import jax
    import jax.numpy as jnp
    import repro.configs as C
    from repro.models import model as mdl
    from repro.serve.engine import generate
    from repro.serve.sched import ContinuousBatcher, Request

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)
    key = jax.random.PRNGKey(5)
    ref = np.asarray(generate(params, cfg, jnp.asarray(prompt)[None],
                              steps=8, key=key))[0].tolist()
    eos = ref[2]       # make the third greedy token the EOS

    mon = _tiny_serving_stack(cfg, params)
    b = ContinuousBatcher(params, cfg, max_active=2, max_len=32, page_size=4,
                          monitor=mon, mirror_pages=True)
    b.submit(Request(rid=0, prompt=prompt, max_new_tokens=8, key=key,
                     eos_id=eos))
    got = b.run()
    k = ref.index(eos) + 1
    assert got[0] == ref[:k], "EOS must truncate the generate stream"
    assert mon.pools.free_pages == mon.pools.n_logical, \
        "early retirement must release the pages"
    assert b.rows_free == list(range(b.max_active - 1, -1, -1)) or \
        sorted(b.rows_free) == list(range(b.max_active))


def test_batcher_paged_kernel_gathers_shared_pool():
    """kernels.paged_attention over the shared HBM pool (slot_of
    indirection through a request's page table) matches the host-pool
    reference for an in-flight request with interleaved allocations.  In
    fully-paged mode the host copy lives in the monitor slot's layered
    leaf (the pool IS the KV store)."""
    import jax
    import jax.numpy as jnp
    import repro.configs as C
    from repro.kernels import ops
    from repro.models import model as mdl
    from repro.serve.sched import ContinuousBatcher, Request

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    mon = _tiny_serving_stack(cfg, params)
    b = ContinuousBatcher(params, cfg, max_active=2, max_len=32, page_size=4,
                          monitor=mon)
    assert b.paged, "gemma3 (all-attention) must take the fully-paged path"
    for i in range(2):
        prompt = rng.integers(0, cfg.vocab_size, size=7 + i).astype(np.int32)
        b.submit(Request(rid=i, prompt=prompt, max_new_tokens=8,
                         key=jax.random.PRNGKey(i)))
    for _ in range(4):
        b.step()
    page = b.page_size
    li = mdl.attn_slot_index(cfg, b._si, b._sj)
    k_host = mon.pools.kv_layers["k_host"][li][-1]
    v_host = mon.pools.kv_layers["v_host"][li][-1]
    for req in list(b.active.values()):
        q = jax.random.normal(jax.random.PRNGKey(40 + req.rid),
                              (1, cfg.num_heads, cfg.head_dim))
        out, _ = b.paged_context(req.rid, q)
        length = int(np.asarray(b.pos)[req.row])
        n = -(-length // page)
        tbl = jnp.asarray(req.gids[:n], jnp.int32)[None]
        ref = ops.paged_attention(q, k_host, v_host,
                                  tbl, jnp.asarray([length], jnp.int32),
                                  impl="reference")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


def test_batcher_dense_and_paged_paths_token_identical():
    """The fully-paged decode (every layer off the shared slot pool) and
    the dense per-request-row path emit bit-identical token streams for
    the same request set -- the tentpole parity bar.  Includes a prompt
    with plen % window >= 2 (the window-ring case) and temperature
    sampling."""
    import jax
    import repro.configs as C
    from repro.models import model as mdl
    from repro.serve.sched import ContinuousBatcher, Request

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (10, 6, 9)]

    def run(paged):
        b = ContinuousBatcher(params, cfg, max_active=2, max_len=32,
                              page_size=4,
                              monitor=_tiny_serving_stack(cfg, params),
                              mirror_pages=not paged, paged=paged)
        assert b.paged == paged
        for i, p in enumerate(prompts):
            b.submit(Request(rid=i, prompt=p, max_new_tokens=5 + i,
                             key=jax.random.PRNGKey(20 + i),
                             temperature=0.0 if i == 0 else 0.8))
        return b.run()

    dense, paged = run(False), run(True)
    assert dense == paged, "dense and fully-paged decode must agree"


def test_paged_decode_multi_repeat_layer_order():
    """With repeats > 1 the paged decode must execute the whole pattern
    per repeat (matching decode_step's scan), not each slot across all
    its repeats -- pinned against per-request generate on a 2-repeat
    variant of the gemma3 pattern (stacked [R, ...] pool leaves driven
    through lax.scan)."""
    import jax
    import jax.numpy as jnp
    import repro.configs as C
    from repro.models import model as mdl
    from repro.serve.engine import generate
    from repro.serve.sched import ContinuousBatcher, Request

    cfg = C.reduced("gemma3-12b")
    cfg = dataclasses.replace(
        cfg, segments=tuple((pat, 2) for pat, _ in cfg.segments))
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (10, 6)]
    b = ContinuousBatcher(params, cfg, max_active=2, max_len=32, page_size=4,
                          monitor=_tiny_serving_stack(cfg, params))
    assert b.paged
    for i, p in enumerate(prompts):
        b.submit(Request(rid=i, prompt=p, max_new_tokens=6,
                         key=jax.random.PRNGKey(i), temperature=0.5 * i))
    got = b.run()
    for i, p in enumerate(prompts):
        ref = np.asarray(generate(params, cfg, jnp.asarray(p)[None], steps=6,
                                  temperature=0.5 * i,
                                  key=jax.random.PRNGKey(i)))[0].tolist()
        assert got[i] == ref, f"request {i} diverged with repeats=2"


def test_admission_prefills_in_one_packed_pass(monkeypatch):
    """Joiners of one scheduler step share a single batched prefill
    forward pass (no per-request prefill loop)."""
    import jax
    import repro.configs as C
    from repro.models import model as mdl
    from repro.serve import sched as S

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    calls = {"batched": 0, "single": 0}
    # the batcher dispatches the module's jitted packed prefill: count
    # dispatches (a trace-time count would miss a cached compile)
    orig_b, orig_1 = S._prefill_batched, mdl.prefill

    def count_b(*a, **k):
        calls["batched"] += 1
        return orig_b(*a, **k)

    def count_1(*a, **k):
        calls["single"] += 1
        return orig_1(*a, **k)

    monkeypatch.setattr(S, "_prefill_batched", count_b)
    monkeypatch.setattr(mdl, "prefill", count_1)
    b = S.ContinuousBatcher(params, cfg, max_active=3, max_len=32,
                            page_size=4,
                            monitor=_tiny_serving_stack(cfg, params,
                                                        n_logical=64,
                                                        hbm=16))
    for i in range(3):
        prompt = rng.integers(0, cfg.vocab_size, size=5 + i).astype(np.int32)
        b.submit(S.Request(rid=i, prompt=prompt, max_new_tokens=3))
    b.step()
    assert len(b.active) + sum(r.done for r in b.completed) == 3
    assert calls == {"batched": 1, "single": 0}, \
        "three same-step joiners must share one packed prefill"


def test_sync_step_spans_name_each_part_of_the_boundary():
    """One synchronous macro step with a joiner opens the served path's
    spans in order, each under its parent, ``serve.admit`` names the
    joiner with its queue wait, and ``serve.macro`` counts the live pages
    of the table."""
    import jax
    import repro.configs as C
    from repro.models import model as mdl
    from repro.obs import telemetry as _obs
    from repro.serve import sched as S

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prev = _obs.get()
    rec = _obs.install(_obs.Recorder(enabled=True))
    try:
        b = S.ContinuousBatcher(params, cfg, max_active=2, max_len=32,
                                page_size=4,
                                monitor=_tiny_serving_stack(cfg, params))
        b.submit(S.Request(rid=7, max_new_tokens=4, prompt=rng.integers(
            0, cfg.vocab_size, size=6).astype(np.int32)))
        b.step()
    finally:
        _obs.install(prev)
    spans = [(e["name"], e["parent"]) for e in rec.events("obs.span")]
    # spans land as they close: children before their parent
    assert spans == [
        ("serve.rebalance", "serve.step"),
        ("serve.admit", "serve.step"),
        ("serve.prefill.launch", "serve.prefill"),
        ("pool.write_prefill", "serve.prefill"),
        ("serve.prefill.first_tokens", "serve.prefill"),
        ("serve.prefill", "serve.step"),
        ("pool.ensure_resident", "serve.step"),
        ("serve.tables", "serve.step"),
        ("serve.macro.launch", "serve.step"),
        ("serve.macro.wait", "serve.step"),
        ("tier.account", "serve.monitor"),
        ("tier.maybe_tier", "serve.monitor"),
        ("tuner.on_step", "serve.monitor"),
        ("serve.monitor", "serve.step"),
        ("serve.emit", "serve.step"),
        ("serve.step", ""),
    ]
    (admit,) = rec.events("serve.admit")
    assert admit["rids"] == [7] and len(admit["wait_ms"]) == 1
    step_ms = rec.events("obs.span")[-1]["ms"]
    assert 0.0 <= admit["wait_ms"][0] < step_ms
    assert "wall_ms" not in admit
    assert "serve.step_s" not in rec.hists
    # the macro counts the pages the kernel walks: the row's 6-token
    # prompt plus the decoding token fill 2 pages of 4, of a 2 x 8 table
    (macro,) = rec.events("serve.macro")
    assert (macro["kv_pages_live"], macro["kv_pages_table"]) == (2, 16)


# ---------------------------------------------------------------------------
# shape-bucketed allocation (property tests)
# ---------------------------------------------------------------------------


def test_bucket_pages_rounding():
    from repro.memtier import bucket_pages
    assert [bucket_pages(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]
    assert bucket_pages(9, cap=10) == 10
    assert bucket_pages(10, cap=10) == 10
    with pytest.raises(ValueError):
        bucket_pages(0)
    with pytest.raises(ValueError):
        bucket_pages(11, cap=10)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_bucketed_allocation_never_exceeds_bucket_sum(seed):
    """Property: at every scheduler step, the pages held by the pool
    equal the sum of the in-flight requests' bucket-rounded footprints --
    never more -- and the peak never exceeds the bucket-rounded sum of
    any co-resident set."""
    from repro.memtier import bucket_pages
    specs = poisson_request_stream(
        60, 0.3, {"sink": 0.5, "random": 0.5}, prompt_len=(4, 90),
        new_tokens=(8, 70), seed=seed)
    pools = SharedPagedPools.create(256, 16)
    mgr = TieringManager(256, CFG)
    sched = TrafficScheduler(specs, TrafficMonitor(pools, mgr),
                             page_size=16, max_active=6)
    cap = sched.row_pages
    for _ in range(300):
        sched.step()
        expect = sum(bucket_pages(a.pattern.shape[1], cap=max(cap,
                                                              a.pattern.shape[1]))
                     for a in sched.active)
        held = pools.n_logical - pools.free_pages
        assert held == expect == pools.allocated_pages
    assert sched.completed == sched.admitted
    assert pools.peak_allocated <= sum(
        bucket_pages(s.n_pages(16), cap=max(cap, s.n_pages(16)))
        for s in specs)


@pytest.mark.parametrize("seed", [0, 5])
def test_bucketed_retire_readmit_recycles_without_leak(seed):
    """Property: draining the stream returns every bucket-rounded page
    (allocated_pages == 0, free_pages == n_logical), and a second stream
    over the same pool admits cleanly from recycled IDs."""
    pools = SharedPagedPools.create(128, 16)
    mgr = TieringManager(128, CFG)
    mon = TrafficMonitor(pools, mgr)
    for round_ in range(2):
        specs = poisson_request_stream(
            40, 0.4, {"sink": 1.0}, prompt_len=(4, 60), new_tokens=(8, 40),
            seed=seed + round_)
        sched = TrafficScheduler(specs, mon, page_size=16, max_active=5)
        sched.run(400)
        assert sched.completed == sched.admitted == len(specs)
        assert pools.free_pages == pools.n_logical, "bucket pages leaked"
        assert pools.allocated_pages == 0


def test_macro_step_token_parity_with_per_token_paged():
    """Macro-step decode (one device launch per movement period, on-device
    sampling/EOS/length masking) emits bit-identical streams to the
    per-token paged loop AND per-request generate -- across staggered
    admission, temperature sampling and the window-ring prompt case."""
    import jax
    import jax.numpy as jnp
    import repro.configs as C
    from repro.models import model as mdl
    from repro.serve.engine import generate
    from repro.serve.sched import ContinuousBatcher, Request

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (10, 6, 9)]          # 10 % window(8) == 2: ring case

    def run(macro):
        b = ContinuousBatcher(params, cfg, max_active=2, max_len=32,
                              page_size=4,
                              monitor=_tiny_serving_stack(cfg, params),
                              macro=macro)
        assert b.paged and b.macro == macro
        for i, p in enumerate(prompts[:2]):
            b.submit(Request(rid=i, prompt=p, max_new_tokens=5 + i,
                             key=jax.random.PRNGKey(30 + i),
                             temperature=0.0 if i == 0 else 0.8))
        out = {}
        for t in range(60):
            if t == 1:                      # staggered join
                b.submit(Request(rid=2, prompt=prompts[2],
                                 max_new_tokens=7,
                                 key=jax.random.PRNGKey(32),
                                 temperature=0.8))
            b.step()
            if not b.queue and not b.active:
                break
        return {r.rid: list(r.tokens) for r in b.completed}

    per_token, macro = run(False), run(True)
    assert per_token == macro, "macro-step diverged from per-token paged"
    for i, p in enumerate(prompts):
        steps = [5, 6, 7][i]
        temp = 0.0 if i == 0 else 0.8
        ref = np.asarray(generate(params, cfg, jnp.asarray(p)[None],
                                  steps=steps, temperature=temp,
                                  key=jax.random.PRNGKey(30 + i)))[0].tolist()
        assert macro[i] == ref, f"request {i} diverged from generate"


def test_macro_step_eos_retires_mid_macro():
    """A sampled EOS stops a row inside the macro launch: the emitted
    stream truncates exactly at the EOS token and the row's pages are
    released at the macro boundary."""
    import jax
    import jax.numpy as jnp
    import repro.configs as C
    from repro.models import model as mdl
    from repro.serve.engine import generate
    from repro.serve.sched import ContinuousBatcher, Request

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)
    key = jax.random.PRNGKey(5)
    ref = np.asarray(generate(params, cfg, jnp.asarray(prompt)[None],
                              steps=8, key=key))[0].tolist()
    eos = ref[3]                 # stops inside the first macro launch

    mon = _tiny_serving_stack(cfg, params)
    b = ContinuousBatcher(params, cfg, max_active=2, max_len=32, page_size=4,
                          monitor=mon, macro=True, macro_steps=8)
    b.submit(Request(rid=0, prompt=prompt, max_new_tokens=8, key=key,
                     eos_id=eos))
    got = b.run()
    assert got[0] == ref[: ref.index(eos) + 1]
    assert mon.pools.free_pages == mon.pools.n_logical


def test_macro_step_merges_once_per_period(monkeypatch):
    """The host-side mass merge collapses to ONE call per movement period
    (vs one per token on the per-token path), and the monitor is fed
    through on_macro_step with a forced tier at the boundary."""
    import jax
    import repro.configs as C
    from repro.models import model as mdl
    from repro.serve.sched import ContinuousBatcher, Request

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    counts = {"merge": 0}

    mon = _tiny_serving_stack(cfg, params)
    orig = mon.merge

    def counting_merge(contribs):
        counts["merge"] += 1
        return orig(contribs)

    monkeypatch.setattr(mon, "merge", counting_merge)
    b = ContinuousBatcher(params, cfg, max_active=2, max_len=32, page_size=4,
                          monitor=mon, macro=True, macro_steps=8)
    b.submit(Request(rid=0,
                     prompt=rng.integers(0, cfg.vocab_size, size=8)
                     .astype(np.int32), max_new_tokens=16))
    got = b.run()
    assert len(got[0]) == 16
    # 16 tokens = 1 prefill sample + 15 decode steps in ceil(15/8) = 2
    # macro launches -> 2 merges, not 15
    assert counts["merge"] == 2, counts
    assert mon.tuner.collector.num_samples > 0
    assert mon.manager.hits > 0


def test_collector_dt_records_gaps_in_token_steps():
    """Macro feeding (one observe per movement period, dt = macro length)
    must leave reuse gaps denominated in TOKEN steps -- the same unit the
    derived period is actuated in -- not in observe calls."""
    col = StreamingReuseCollector(4, bin_width=1)
    col.observe(np.array([1]), dt=8)
    col.observe(np.array([1]), dt=8)
    assert col.step == 16, "the clock advances by dt, not by calls"
    assert col.num_samples == 1
    assert col._gaps[-1][1] == 8, "gap == the macro span in tokens"


def test_tuner_dt_advances_windows_in_token_steps():
    """OnlineTuner windows (profile/trial) count token-steps under macro
    feeding: a 16-token profile completes after two 8-token macros."""
    tuner = OnlineTuner(8, profile_steps=16, trial_steps=4, bin_width=1)
    mass = np.zeros(8, np.float32)
    mass[2] = 1.0
    tuner.on_step(page_mass=mass, cost=8.0, dt=8)
    assert tuner.state == tuner.PROFILE
    tuner.on_step(page_mass=mass, cost=8.0, dt=8)
    assert tuner.state == tuner.TRIAL, \
        "16 token-steps profiled in 2 macro feeds must start trials"


def test_paged_attention_window_and_softcap_match_reference():
    """The Pallas kernel's sliding-window mask and tanh softcap (the
    local-layer path of fully-paged decode) match the jnp oracle."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    key = jax.random.PRNGKey(3)
    n, page, kvh, d, h = 8, 4, 2, 8, 4
    k = jax.random.normal(key, (n, page, kvh, d))
    v = jax.random.normal(jax.random.fold_in(key, 1), (n, page, kvh, d))
    q = jax.random.normal(jax.random.fold_in(key, 2), (2, h, d))
    tbl = jnp.asarray([[2, 0, 4, 6], [5, 1, -1, -1]], jnp.int32)
    lengths = jnp.asarray([4 * page - 1, 2 * page], jnp.int32)
    for window in (3, 8):
        for softcap in (0.0, 5.0):
            out = ops.paged_attention(q, k, v, tbl, lengths, window=window,
                                      softcap=softcap, impl="interpret")
            ref = ops.paged_attention(q, k, v, tbl, lengths, window=window,
                                      softcap=softcap, impl="reference")
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5)
            assert not np.isnan(np.asarray(out)).any()


def test_paged_masses_reach_tuner_from_all_layers():
    """In fully-paged mode the reuse signal comes from the decode step
    itself (all attention layers, head-normalised): the tuner's collector
    must accumulate samples without engine.make_monitor ever running."""
    import jax
    import repro.configs as C
    from repro.models import model as mdl
    from repro.serve.sched import ContinuousBatcher, Request

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    mon = _tiny_serving_stack(cfg, params)
    b = ContinuousBatcher(params, cfg, max_active=2, max_len=32, page_size=4,
                          monitor=mon)
    assert b.paged and b._mon_fn is None
    b.submit(Request(rid=0,
                     prompt=rng.integers(0, cfg.vocab_size, size=8)
                     .astype(np.int32), max_new_tokens=10))
    b.run()
    assert mon.tuner.collector.num_samples > 0, \
        "all-layer masses never reached the reuse collector"
    assert mon.manager.hits > 0


def test_relative_mass_threshold_is_occupancy_stable():
    """`OnlineTuner(rel_threshold=True)` cuts accessed sets at a fraction
    of the step's peak mass: scaling every mass down (more layers / more
    in-flight requests diluting the normalised signal) must not change
    which pages count as accessed, while the absolute cut loses them."""
    from repro.core import OnlineTuner, StreamingReuseCollector

    mass = np.zeros(16, np.float32)
    mass[[2, 5]] = [1.0, 0.4]
    for scale in (1.0, 0.01):
        rel = StreamingReuseCollector(16, bin_width=1)
        rel.observe_mass(mass * scale, 0.2, relative=True)
        rel.observe_mass(mass * scale, 0.2, relative=True)
        assert rel.num_samples == 2, f"relative cut drifted at x{scale}"
    absd = StreamingReuseCollector(16, bin_width=1)
    absd.observe_mass(mass * 0.01, 0.2)
    absd.observe_mass(mass * 0.01, 0.2)
    assert absd.num_samples == 0, "absolute cut should lose diluted masses"

    tuner = OnlineTuner(16, rel_threshold=True, access_threshold=0.2,
                        bin_width=1)
    tuner.on_step(page_mass=mass * 0.01, cost=1.0)
    tuner.on_step(page_mass=mass * 0.01, cost=1.0)
    assert tuner.collector.num_samples == 2


def test_layered_only_pool_rejects_legacy_mirror():
    """A pool with only layered leaves (no legacy k_host pair) is
    physical, but the dense write-through mirror must not engage on it --
    mirror_pages quietly stays off instead of crashing in write_page."""
    import jax
    import repro.configs as C
    from repro.models import model as mdl
    from repro.serve.sched import ContinuousBatcher, Request

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    pools = SharedPagedPools.create(48, 16)      # bare: no legacy arrays
    mgr = TieringManager(48, dataclasses.replace(CFG, page_size=4,
                                                 hbm_pages=16))
    mon = TrafficMonitor(pools, mgr)
    paged = ContinuousBatcher(params, cfg, max_active=1, max_len=32,
                              page_size=4, monitor=mon)
    assert paged.paged and pools.physical
    dense = ContinuousBatcher(params, cfg, max_active=1, max_len=32,
                              page_size=4, monitor=mon, mirror_pages=True,
                              paged=False)
    assert not dense.mirror_pages, "no legacy arrays: mirror must not arm"
    dense.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                         max_new_tokens=2))
    dense.run()          # would crash in write_page without the guard
    assert pools.free_pages == pools.n_logical


def test_paged_attention_tolerates_ragged_minus_one_padding():
    """Ragged multi-request page tables pad short rows with -1; the kernel
    wrapper clamps them (they are masked by lengths) instead of gathering
    out of bounds."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    key = jax.random.PRNGKey(0)
    n, page, kvh, d, h = 6, 4, 2, 8, 4
    k = jax.random.normal(key, (n, page, kvh, d))
    v = jax.random.normal(jax.random.fold_in(key, 1), (n, page, kvh, d))
    q = jax.random.normal(jax.random.fold_in(key, 2), (2, h, d))
    # row 0 uses 3 pages, row 1 only 1 -- padded with -1
    tbl = jnp.asarray([[2, 0, 4], [5, -1, -1]], jnp.int32)
    lengths = jnp.asarray([3 * page, page], jnp.int32)
    out = ops.paged_attention(q, k, v, tbl, lengths, impl="interpret")
    ref = ops.paged_attention(q, k, v, jnp.maximum(tbl, 0), lengths,
                              impl="reference")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    assert not np.isnan(np.asarray(out)).any()
