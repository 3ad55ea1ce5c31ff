"""Pipelined macro serving loop: overlap must never change the tokens.

Covers the pipelined-loop tentpole: the DecisionWorker hand-off protocol
(ordered generations, exception propagation, close semantics, a
stress-hammered fake dispatch thread), pipelined-vs-synchronous token
parity including chunked long-prompt admission under staggered arrival
(the async-decision determinism contract: overlap changes *when* work
happens, never *what* is computed), the epoch-keyed page-table upload
cache, and the batched-transfer miss pricing in TrafficMonitor.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import OnlineTuner
from repro.memtier import SharedPagedPools, TierConfig, TieringManager
from repro.serve.pipeline import DecisionWorker
from repro.serve.sched import TrafficMonitor


# ---------------------------------------------------------------------------
# DecisionWorker: the hand-off protocol, without a model
# ---------------------------------------------------------------------------


def test_decision_worker_orders_generations():
    with DecisionWorker(lambda p: p * 2) as w:
        gens = [w.submit(i) for i in range(8)]
        assert gens == list(range(8)), "generations number submissions"
        # out-of-order waits resolve: results are keyed, not streamed
        for g in reversed(gens):
            result, waited = w.wait(g)
            assert result == g * 2
            assert waited >= 0.0


def test_decision_worker_propagates_exceptions():
    def fn(p):
        if p == "boom":
            raise ValueError("boom payload")
        return p

    with DecisionWorker(fn) as w:
        ok = w.submit("fine")
        bad = w.submit("boom")
        assert w.wait(ok)[0] == "fine"
        with pytest.raises(ValueError, match="boom payload"):
            w.wait(bad)
        # the worker survives a failed generation
        again = w.submit("fine")
        assert w.wait(again)[0] == "fine"


def test_decision_worker_close_and_timeout():
    w = DecisionWorker(lambda p: p)
    g = w.submit(1)
    assert w.wait(g)[0] == 1
    with pytest.raises(TimeoutError):
        w.wait(g + 1, timeout=0.01)   # never submitted
    w.close()
    with pytest.raises(RuntimeError):
        w.submit(2)
    w.close()                          # idempotent


def test_decision_worker_handoff_stress():
    """Hammer the submit/wait hand-off from a fake dispatch thread: many
    generations, strict alternation exactly as the pipelined loop drives
    it (submit -> overlap work -> wait), plus a burst phase with several
    generations in flight.  Every result must match its payload."""
    def fn(p):
        # vary service time so the dispatch thread races ahead and
        # behind the worker in turn
        time.sleep((p % 3) * 1e-4)
        return ("done", p)

    failures = []

    def dispatch(n):
        try:
            with DecisionWorker(fn) as w:
                # phase 1: strict alternation (the pipelined loop's shape)
                for i in range(n):
                    g = w.submit(i)
                    result, _ = w.wait(g, timeout=10.0)
                    assert result == ("done", i), result
                # phase 2: a burst of in-flight generations
                gens = [w.submit(100 + i) for i in range(16)]
                for i, g in enumerate(gens):
                    result, _ = w.wait(g, timeout=10.0)
                    assert result == ("done", 100 + i), result
        except BaseException as e:      # surface into the test thread
            failures.append(e)

    threads = [threading.Thread(target=dispatch, args=(50,))
               for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    assert not failures, failures


# ---------------------------------------------------------------------------
# TrafficMonitor: batched-transfer miss pricing
# ---------------------------------------------------------------------------


def _mini_monitor():
    pools = SharedPagedPools.create(16, 8)
    mgr = TieringManager(16, TierConfig(page_size=16, hbm_pages=8,
                                        period_steps=4))
    return TrafficMonitor(pools, mgr)


def test_on_step_charges_fetches_at_fetch_cost():
    """Demand fetches are priced at ``fetch_cost`` (the pools batch every
    ensure_resident call into one gathered transfer), NOT at the
    synchronous mid-decode ``miss_penalty``."""
    mass = np.zeros(16, np.float32)
    base, fetched = _mini_monitor(), _mini_monitor()
    base.on_step(mass, n_active=1)
    fetched.on_step(mass, n_active=1, fetched=5)
    mgr = fetched.manager
    assert mgr.misses - base.manager.misses == 5
    extra = mgr.modeled_time - base.manager.modeled_time
    assert extra == pytest.approx(5 * mgr.cfg.fetch_cost)
    assert mgr.cfg.fetch_cost < mgr.cfg.miss_penalty


def test_plan_step_accounts_like_on_macro_step():
    """The worker half (plan, no pool mutation) and the synchronous
    boundary must charge identically from the same snapshot -- cost is
    charged at plan time so sync and async account the same."""
    rng = np.random.default_rng(0)
    sync_m, pipe_m = _mini_monitor(), _mini_monitor()
    for s in range(6):
        mass = rng.random(16).astype(np.float32)
        sync_m.on_macro_step(mass, n_active=2.0, n_tokens=4, fetched=3)
        pools = pipe_m.pools
        period, plan = pipe_m.plan_step(
            mass, n_active=2.0, n_tokens=4, fetched=3,
            resident=pools.slot_of >= 0,
            n_free=int((pools.page_of_slot < 0).sum()),
            active=pools.allocated_mask, planes=2)
        pipe_m.apply_decision(plan)
        assert period == sync_m.manager.period
    assert pipe_m.manager.modeled_time == sync_m.manager.modeled_time
    assert pipe_m.manager.misses == sync_m.manager.misses
    np.testing.assert_array_equal(pipe_m.pools.slot_of,
                                  sync_m.pools.slot_of)


# ---------------------------------------------------------------------------
# pipelined ContinuousBatcher: token parity with the synchronous loop
# ---------------------------------------------------------------------------


def _serving_stack(cfg, *, n_logical=48, hbm=16, page=4):
    pools = SharedPagedPools.create(n_logical, hbm, page_size=page,
                                    kv_heads=cfg.num_kv_heads,
                                    head_dim=cfg.head_dim)
    mgr = TieringManager(n_logical, TierConfig(page_size=page,
                                               hbm_pages=hbm,
                                               period_steps=2))
    tuner = OnlineTuner(n_logical, default_period=2, profile_steps=8,
                        trial_steps=4)
    return TrafficMonitor(pools, mgr, tuner)


def _drive(params, cfg, reqs, *, pipeline, admit_chunk_tokens=None):
    """Run one batcher over the staggered request set; returns
    (rid -> tokens, monitor)."""
    from repro.serve.sched import ContinuousBatcher, Request

    mon = _serving_stack(cfg)
    b = ContinuousBatcher(params, cfg, max_active=2, max_len=32,
                          page_size=4, monitor=mon, pipeline=pipeline,
                          admit_chunk_tokens=admit_chunk_tokens)
    try:
        for at, req in reqs:
            if at == 0:
                b.submit(Request(**req))
        for t in range(1, 80):
            for at, req in reqs:
                if at == t:         # staggered admission mid-flight
                    b.submit(Request(**req))
            b.step()
            if b.idle:
                break
        assert b.idle, "must drain"
        got = {r.rid: list(r.tokens) for r in b.completed}
        assert mon.pools.free_pages == mon.pools.n_logical, \
            "every page must come back to the pool"
    finally:
        b.close()
    return got, mon


def test_pipelined_token_parity_with_synchronous():
    """The tentpole bar: the pipelined loop (async decisions, lazy
    same-boundary admission, overlap prefetch) emits rid-for-rid
    token-identical
    streams to the synchronous macro loop AND to per-request generate,
    under staggered admission, row reuse and mixed temperatures; chunked
    long-prompt admission preserves the same streams."""
    import jax
    import jax.numpy as jnp
    import repro.configs as C
    from repro.models import model as mdl
    from repro.serve.engine import generate

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    plens = (6, 9, 5, 14)          # 14 > chunk width: chunked admission
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in plens]
    steps = [6, 4, 7, 5]
    temps = [0.0, 0.7, 0.7, 0.0]
    reqs = [(0 if i < 2 else 2,
             dict(rid=i, prompt=prompts[i], max_new_tokens=steps[i],
                  key=jax.random.PRNGKey(10 + i), temperature=temps[i]))
            for i in range(4)]

    sync, _ = _drive(params, cfg, reqs, pipeline=False)
    pipe, _ = _drive(params, cfg, reqs, pipeline=True)
    chunk, _ = _drive(params, cfg, reqs, pipeline=True,
                      admit_chunk_tokens=4)
    assert pipe == sync, "pipelined loop must be token-identical"
    assert chunk == sync, "chunked admission must be token-identical"
    for i in range(4):             # dense reference: generate per request
        ref = np.asarray(generate(params, cfg,
                                  jnp.asarray(prompts[i])[None],
                                  steps=steps[i], temperature=temps[i],
                                  key=jax.random.PRNGKey(10 + i))
                         )[0].tolist()
        assert pipe[i] == ref, f"request {i} diverged from generate"


def test_pipelined_table_upload_cache():
    """The epoch-keyed table cache: boundaries where tiering moved no
    page and no row changed skip the rebuild+upload (counted), and the
    pipelined run times its overlap stages as spans beside its
    decision/chunk events."""
    import jax
    import repro.configs as C
    from repro.models import model as mdl
    from repro.obs import telemetry as _obs
    from repro.serve.sched import ContinuousBatcher, Request

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    rec = _obs.install(_obs.Recorder(enabled=True))
    try:
        mon = _serving_stack(cfg)
        b = ContinuousBatcher(params, cfg, max_active=2, max_len=32,
                              page_size=4, monitor=mon, pipeline=True,
                              admit_chunk_tokens=4)
        for i, n in enumerate((6, 14)):
            b.submit(Request(
                rid=i, max_new_tokens=6,
                prompt=rng.integers(0, cfg.vocab_size,
                                    size=n).astype(np.int32)))
        b.run(max_steps=60)
        b.close()
        counters = rec.summary()["counters"]
        assert counters.get("pool.table_upload.performed", 0) >= 1
        assert counters.get("pool.table_upload.skipped", 0) >= 1, \
            "quiet boundaries must reuse the staged upload"
        types = {e["type"] for e in rec.events()}
        assert {"serve.pipeline.decision",
                "serve.pipeline.admit_chunk"} <= types
        # the overlap window's stages are spans under the step
        stages = {e["name"] for e in rec.events("obs.span")
                  if e["parent"] == "serve.step"}
        assert {"serve.decision_wait", "serve.admit_chunks",
                "pool.ensure_resident", "serve.tables"} <= stages
    finally:
        _obs.install(_obs.Recorder())
