"""Traffic benchmark: the scheduler-fed online tuner vs a brute-force
fixed-period sweep, on a Poisson arrival stream whose mix shifts mid-run.

Phase A serves zipf random-retrieval requests (long-period friendly),
phase B drifting attention-sink requests (short-period friendly); all
requests share one HBM slot pool through ``serve.sched``.  Reports:

  * end-state (final-window) modeled cost of the online run vs every
    fixed period -- the acceptance bar is online <= 1.05x the best fixed;
  * peak cache memory of the bucket-rounded paged rows vs the dense
    packed-cache provisioning (``max_active`` rows of the longest
    request's footprint, held for the whole run) -- the fully-paged
    acceptance bar is >= 25% reduction on this mixed-length stream;
  * the token-parity check: a multi-request ``ContinuousBatcher`` running
    the FULLY-PAGED decode (every attention layer gathered from
    ``SharedPagedPools`` by ``kernels.paged_attention``) must emit
    token-identical output to per-request ``generate`` for the same
    prompts/keys, and the paged kernel's gather from the shared HBM pool
    must match the host-leaf reference;
  * wall-clock serving throughput (``serving_perf``): the macro-step
    decode loop (one device launch per movement period) vs the per-token
    paged loop -- tokens/sec (== decode token-steps/sec) and per-
    scheduler-step p50/p95 latency -- with the four-way bit-parity bar
    (dense == per-token paged == macro-step == per-request generate).
    Written to ``BENCH_serving.json`` so the serving perf trajectory is
    tracked across PRs.
  * the hostile-traffic replay (``hostile``): the online tuner rides a
    four-phase adversarial stream (plain Poisson, then flash crowds,
    correlated bursts and a diurnal swing -- ``repro.core.traffic``) and
    its per-phase regret vs the best fixed period must stay <= 1.15x in
    EVERY phase, plus a deterministic poisoned-TRIAL demo asserting the
    cost-spike guardrail reverts to the last attested period.  Written to
    ``BENCH_hostile.json``; both bars are asserted under ``--smoke``.

    PYTHONPATH=src python -m benchmarks.traffic [--quick | --smoke]
"""
from __future__ import annotations

import gc
import os
import time
from typing import Dict, Optional

import numpy as np

from benchmarks.common import out_dir, save_json
from repro import obs
from repro.core import OnlineTuner, shifting_mix_stream
from repro.memtier import SharedPagedPools, TierConfig, TieringManager
from repro.serve.sched import TrafficMonitor, TrafficScheduler

N_LOGICAL, HBM_PAGES, PAGE = 256, 32, 16
MAX_ACTIVE = 8
FIXED = (1, 2, 4, 8, 16, 32, 64, 200)
STEADY_WINDOW = 150

# Heavy-tailed mixed-length traffic (the serving shape bucketing is for):
# most requests are short (2..6 pages), an occasional long one spans up
# to the 16-page row cap.  A dense packed cache must provision EVERY row
# for the worst case; bucket-rounded paged rows pay their own
# power-of-two class.
SHORT = dict(rate=0.09, prompt_len=(8, 40), new_tokens=(24, 56))
LONG = dict(rate=0.015, prompt_len=(48, 104), new_tokens=(112, 152))


def _stream(phase_steps: int, seed: int = 0):
    import dataclasses

    def phases(rate, prompt_len, new_tokens, s):
        return shifting_mix_stream(
            [(phase_steps, rate, {"random": 1.0}),
             (phase_steps, rate, {"sink": 1.0})],
            prompt_len=prompt_len, new_tokens=new_tokens, seed=s)

    merged = sorted(phases(s=seed, **SHORT) + phases(s=seed + 1, **LONG),
                    key=lambda r: (r.arrival, r.rid))
    return [dataclasses.replace(r, rid=i) for i, r in enumerate(merged)]


def _run(specs, steps: int, *, period: int = 8,
         tuner: Optional[OnlineTuner] = None, probe_at: Optional[int] = None):
    """Replay one stream; returns (scheduler, manager, tuner,
    modeled_time at ``probe_at``) -- the probe turns one run into an exact
    final-window cost, the replays being deterministic."""
    pools = SharedPagedPools.create(N_LOGICAL, HBM_PAGES)
    mgr = TieringManager(N_LOGICAL, TierConfig(
        page_size=PAGE, hbm_pages=HBM_PAGES, period_steps=period))
    sched = TrafficScheduler(specs, TrafficMonitor(pools, mgr, tuner),
                             page_size=PAGE, max_active=MAX_ACTIVE)
    probe = 0.0
    for t in range(steps):
        if t == probe_at:
            probe = mgr.modeled_time
        sched.step()
    return sched, mgr, tuner, probe


def run(quick: bool = False) -> Dict:
    phase = 400 if quick else 700
    steps = 2 * phase
    lo = steps - STEADY_WINDOW
    specs = _stream(phase)

    # heavy-tailed traffic makes short cost windows noisy (a trial's cost
    # depends on which requests happen to be in flight): 96-step trials
    # average over several request lifetimes so the ladder ranks stably
    tuner = OnlineTuner(N_LOGICAL, default_period=8,
                        drift_ratio=1.5, drift_patience=3, trial_steps=96)
    sched, mgr, tuner, probe = _run(specs, steps, tuner=tuner, probe_at=lo)
    online_steady = (mgr.modeled_time - probe) / STEADY_WINDOW

    fixed = {}
    for p in FIXED:
        _, m, _, pr = _run(specs, steps, period=p, probe_at=lo)
        fixed[str(p)] = {"total": m.modeled_time,
                         "steady": (m.modeled_time - pr) / STEADY_WINDOW}
    best_steady = min(v["steady"] for v in fixed.values())
    best_total = min(v["total"] for v in fixed.values())

    out = {
        "steps": steps,
        "requests": {"submitted": len(specs), "admitted": sched.admitted,
                     "completed": sched.completed},
        "cache_memory": {
            "peak_paged_pages": sched.peak_cache_pages,
            "dense_pages": sched.dense_cache_pages,
            "row_pages": sched.row_pages,
            "reduction": 1.0 - sched.peak_cache_pages
            / max(1, sched.dense_cache_pages),
        },
        "online": {
            "total": mgr.modeled_time,
            "steady": online_steady,
            "final_period": tuner.period,
            "state": tuner.state,
            "tune_cycles": tuner.retunes,
            "period_history": tuner.history,
        },
        "fixed": fixed,
        "online_vs_best_fixed_steady": online_steady / best_steady,
        "online_vs_best_fixed_total": mgr.modeled_time / best_total,
        "token_parity": _token_parity(quick),
    }
    save_json("traffic", out)
    return out


HOSTILE_MIX = {"random": 0.7, "sink": 0.3}
HOSTILE_FIXED = (1, 2, 4, 8, 16, 64)


def _hostile_stream(phase_steps: int, seed: int = 0):
    """Four phases of identical mix and mean rate, escalating hostility:
    plain Poisson, flash crowds, correlated bursts, a diurnal swing.  The
    optimum barely moves across phases, so any per-phase regret the online
    run shows is the hostile *shape* shaking the tuner -- exactly what the
    guardrail/variance/warm-retune defenses exist to prevent."""
    rate = 0.09
    return shifting_mix_stream(
        [(phase_steps, rate, HOSTILE_MIX),
         (phase_steps, rate, HOSTILE_MIX,
          {"gen": "flash_crowd", "spike_factor": 6.0, "spike_every": 120,
           "spike_len": 10}),
         (phase_steps, rate, HOSTILE_MIX, {"gen": "burst", "burst_size": 5}),
         (phase_steps, rate, HOSTILE_MIX,
          # swing period deliberately NOT scaled with phase length: a
          # 300-step cycle is what a drift detector with ~35-step windows
          # and patience 3 must ride out -- much slower swings are
          # indistinguishable from genuine regime changes and SHOULD
          # re-tune
          {"gen": "diurnal", "swing_period": 300, "amplitude": 0.6})],
        prompt_len=(16, 48), new_tokens=(40, 100), seed=seed)


def _trajectory(specs, steps: int, *, period: int = 8,
                tuner: Optional[OnlineTuner] = None):
    """Replay one stream recording the full modeled-time trajectory, so one
    deterministic run yields the exact cost of every phase window."""
    pools = SharedPagedPools.create(N_LOGICAL, HBM_PAGES)
    mgr = TieringManager(N_LOGICAL, TierConfig(
        page_size=PAGE, hbm_pages=HBM_PAGES, period_steps=period))
    sched = TrafficScheduler(specs, TrafficMonitor(pools, mgr, tuner),
                             page_size=PAGE, max_active=MAX_ACTIVE)
    traj = np.zeros(steps + 1)
    for t in range(steps):
        sched.step()
        traj[t + 1] = mgr.modeled_time
    return sched, tuner, traj


def _poisoned_trial_revert() -> Dict:
    """Deterministic guardrail demo: converge a tuner on a clean synthetic
    workload (attesting period 8 at cost ~1), force a re-tune sweep, then
    poison the TRIAL windows with a spiky cost (whole period-buckets
    alternating 300x/clean).  The cost-spike guardrail must abort the
    sweep and revert to the attested period instead of crowning whichever
    candidate the spikes happened to spare."""
    tuner = OnlineTuner(64, default_period=2, profile_steps=32,
                        trial_steps=32, horizon_steps=64, bin_width=1,
                        patience=3)
    ids = lambda t: np.array([t % 4])        # every reuse gap is exactly 4
    for t in range(600):
        tuner.on_step(accessed_ids=ids(t), cost=abs(tuner.period - 8) + 1.0)
    attested = tuner.last_good_period
    tuner._reprofile()                       # force the re-tune sweep
    poisoned_steps = 0
    while tuner.state == OnlineTuner.TRIAL and poisoned_steps < 200:
        c = 300.0 if (poisoned_steps // 8) % 2 == 0 else 1.0
        tuner.on_step(accessed_ids=ids(poisoned_steps), cost=c)
        poisoned_steps += 1
    return {
        "attested_period": attested,
        "final_period": tuner.period,
        "state": tuner.state,
        "guard_trips": tuner.guard_trips,
        "steps_to_abort": poisoned_steps,
        "reverted": (tuner.state == OnlineTuner.HOLD
                     and tuner.period == attested
                     and tuner.guard_trips >= 1),
    }


def hostile(quick: bool = False) -> Dict:
    phase = 350 if quick else 600
    window = 120 if quick else 150
    steps = 4 * phase
    specs = _hostile_stream(phase)

    # a fresh flight recorder isolates the online run's event stream: the
    # JSONL written below is the full tuner decision timeline of exactly
    # this trajectory (fixed-period replays never pollute it)
    rec = obs.install(obs.Recorder())
    # shorter profile/trial windows than run(): the tuner must be settled
    # well before the first phase window closes, and the variance-scaled
    # extension recovers the averaging when a phase is genuinely noisy
    tuner = OnlineTuner(N_LOGICAL, default_period=8, profile_steps=48,
                        trial_steps=24, drift_ratio=1.5, drift_patience=3)
    sched, tuner, online_traj = _trajectory(specs, steps, tuner=tuner)
    events_jsonl = obs.write_jsonl(out_dir() / "hostile_events.jsonl", rec)
    metrics = {"schema": obs.SCHEMA, **rec.summary()}
    fixed_traj = {p: _trajectory(specs, steps, period=p)[2]
                  for p in HOSTILE_FIXED}

    names = ("poisson", "flash_crowd", "burst", "diurnal")
    phases = []
    for i, name in enumerate(names):
        e = (i + 1) * phase
        s = e - window
        online_cost = (online_traj[e] - online_traj[s]) / window
        fixed = {str(p): (tr[e] - tr[s]) / window
                 for p, tr in fixed_traj.items()}
        best = min(fixed.values())
        phases.append({"phase": name, "online_steady": online_cost,
                       "fixed_steady": fixed, "best_fixed": best,
                       "regret": online_cost / best})

    out = {
        "steps": steps,
        "requests": {"submitted": len(specs), "admitted": sched.admitted,
                     "completed": sched.completed},
        "phases": phases,
        "max_regret": max(p["regret"] for p in phases),
        "tuner": {"final_period": tuner.period, "state": tuner.state,
                  "tune_cycles": tuner.retunes,
                  "guard_trips": tuner.guard_trips,
                  "window_extensions": tuner.window_extensions,
                  "period_history": tuner.history},
        "poisoned_trial": _poisoned_trial_revert(),
        # the flight-recorder view of the same online run (see
        # docs/observability.md for the schema): replay the JSONL with
        # ``python -m repro.obs.report`` for the decision trace
        "metrics": metrics,
        "events_jsonl": str(events_jsonl),
    }
    save_json("BENCH_hostile", out)
    return out


def _print_hostile(ho: Dict) -> None:
    for p in ho["phases"]:
        print(f"hostile[{p['phase']:>11s}]: online {p['online_steady']:8.2f}"
              f"/step vs best fixed {p['best_fixed']:8.2f} "
              f"(regret {p['regret']:.3f}x)")
    t = ho["tuner"]
    print(f"hostile tuner: period={t['final_period']} ({t['state']}), "
          f"{t['tune_cycles']} tune cycles, {t['guard_trips']} guard trips, "
          f"{t['window_extensions']} window extensions")
    pt = ho["poisoned_trial"]
    print(f"poisoned trial: reverted={pt['reverted']} "
          f"(period {pt['final_period']} == attested "
          f"{pt['attested_period']}, {pt['guard_trips']} guard trips, "
          f"abort after {pt['steps_to_abort']} poisoned steps)")


def _token_parity(quick: bool) -> Dict:
    """Fully-paged multi-request decode over SharedPagedPools (every
    attention layer through ``kernels.paged_attention``) == per-request
    generate, and the paged kernel's shared-HBM gather == the host-leaf
    reference."""
    import jax
    import jax.numpy as jnp

    import repro.configs as C
    from repro.kernels import ops
    from repro.models import model as mdl
    from repro.serve.engine import generate
    from repro.serve.sched import ContinuousBatcher, Request

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    n_req = 3 if quick else 4
    prompts = [rng.integers(0, cfg.vocab_size, size=int(rng.integers(4, 10)))
               .astype(np.int32) for _ in range(n_req)]
    new_tokens = [int(rng.integers(4, 8)) for _ in range(n_req)]
    keys = [jax.random.PRNGKey(100 + i) for i in range(n_req)]

    page = 4
    pools = SharedPagedPools.create(48, 16)
    mgr = TieringManager(48, TierConfig(page_size=page, hbm_pages=16,
                                        period_steps=2))
    mon = TrafficMonitor(pools, mgr,
                         OnlineTuner(48, default_period=2, profile_steps=8,
                                     trial_steps=4))
    batcher = ContinuousBatcher(params, cfg, max_active=2, max_len=32,
                                page_size=page, monitor=mon)
    assert batcher.paged, "gemma3 must take the fully-paged decode path"
    for i in range(n_req):
        batcher.submit(Request(rid=i, prompt=prompts[i],
                               max_new_tokens=new_tokens[i], key=keys[i],
                               temperature=0.7 if i % 2 else 0.0))
    # after a few steps, validate the shared-pool paged gather path
    for _ in range(3):
        batcher.step()
    kernel_diff = 0.0
    if batcher.active:
        req = next(iter(batcher.active.values()))
        q = jax.random.normal(jax.random.PRNGKey(7),
                              (1, cfg.num_heads, cfg.head_dim))
        out, _ = batcher.paged_context(req.rid, q)
        length = int(np.asarray(batcher.pos)[req.row])
        n = -(-length // page)
        tbl = jnp.asarray(req.gids[:n], jnp.int32)[None]
        li = mdl.attn_slot_index(cfg, batcher._si, batcher._sj)
        ref = ops.paged_attention(q, pools.kv_layers["k_host"][li][-1],
                                  pools.kv_layers["v_host"][li][-1], tbl,
                                  jnp.asarray([length], jnp.int32),
                                  impl="reference")
        kernel_diff = float(jnp.abs(out - ref).max())
    got = batcher.run()

    matches = []
    for i in range(n_req):
        ref = np.asarray(generate(
            params, cfg, jnp.asarray(prompts[i])[None],
            steps=new_tokens[i], temperature=0.7 if i % 2 else 0.0,
            key=keys[i]))[0].tolist()
        matches.append(ref == got[i])
    return {"requests": n_req, "decode_mode": "fully-paged",
            "token_identical": all(matches),
            "paged_kernel_max_diff": kernel_diff,
            "pages_all_released": pools.free_pages == pools.n_logical}


def mla(quick: bool = False) -> Dict:
    """Paged MLA admission on the shared slot pool (deepseek-v3): requests
    hold bucket-rounded compressed ``ckv``/``krope`` pages instead of a
    dense ``max_active x max_len`` row cache, so peak provisioning drops
    by the mixed-length slack — the tentpole bar is >= 1.5x fewer pages
    than dense provisioning, token streams bit-identical to per-request
    ``generate``.  Written to ``traffic_mla.json``."""
    import jax
    import jax.numpy as jnp

    import repro.configs as C
    from repro.models import model as mdl
    from repro.serve.engine import generate
    from repro.serve.sched import ContinuousBatcher, Request

    cfg = C.reduced("deepseek-v3-671b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    n_req = 4 if quick else 8
    page, max_len, max_active = 4, 64, 4
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(6, 13))).astype(np.int32)
               for _ in range(n_req)]
    budgets = [int(rng.integers(8, 17)) for _ in range(n_req)]
    temps = [0.0 if i % 2 == 0 else 0.7 for i in range(n_req)]
    keys = [jax.random.PRNGKey(200 + i) for i in range(n_req)]

    n_logical, hbm = 96, 48
    pools = SharedPagedPools.create(n_logical, hbm)
    mgr = TieringManager(n_logical, TierConfig(page_size=page,
                                               hbm_pages=hbm,
                                               period_steps=2))
    mon = TrafficMonitor(pools, mgr,
                         OnlineTuner(n_logical, default_period=2,
                                     profile_steps=8, trial_steps=4))
    b = ContinuousBatcher(params, cfg, max_active=max_active,
                          max_len=max_len, page_size=page, monitor=mon,
                          macro=True, macro_steps=4)
    assert b.paged and b.macro, \
        "deepseek-v3 (MLA) must take the paged macro path"
    for i in range(n_req):
        b.submit(Request(rid=i, prompt=prompts[i], max_new_tokens=budgets[i],
                         key=keys[i], temperature=temps[i]))
    got = b.run()

    matches = []
    for i in range(n_req):
        ref = np.asarray(generate(params, cfg, jnp.asarray(prompts[i])[None],
                                  steps=budgets[i], temperature=temps[i],
                                  key=keys[i]))[0].tolist()
        matches.append(ref == got[i])

    # dense provisioning: every row carries max_len tokens of cache for
    # the whole run; paged provisioning peaks at the worst co-resident
    # sum of bucket-rounded compressed rows
    dense_pages = max_active * (max_len // page)
    peak_paged = int(pools.peak_allocated)
    out = {
        "arch": "deepseek-v3-671b",
        "decode_mode": "paged-macro",
        "requests": n_req,
        "token_identical": all(matches),
        "dense_pages": dense_pages,
        "peak_paged_pages": peak_paged,
        "page_reduction_x": dense_pages / max(1, peak_paged),
        "pages_all_released": pools.free_pages == pools.n_logical,
    }
    save_json("traffic_mla", out)
    return out


def _print_mla(m: Dict) -> None:
    print(f"mla[deepseek-v3]: peak paged {m['peak_paged_pages']} pages vs "
          f"dense {m['dense_pages']} ({m['page_reduction_x']:.2f}x "
          f"reduction); token-identical: {m['token_identical']}; "
          f"pages released: {m['pages_all_released']}")


def serving_perf(quick: bool = False) -> Dict:
    """Wall-clock serving throughput: macro-step vs per-token paged decode.

    Each mode serves two identical request waves over one batcher: wave 1
    warms the jit caches, wave 2 is timed.  ``tokens_per_sec`` counts
    decode token-steps served per wall second (the throughput the macro
    loop exists to raise); latency percentiles are per ``step()`` call
    (one token for the per-token path, one movement period for macro).
    The parity field pins the tentpole bar: every mode's wave-2 streams
    bit-identical to per-request ``generate``.

    Also measures the flight recorder's cost on the macro hot loop:
    alternating telemetry-enabled/disabled waves over one warmed batcher;
    the ``telemetry_overhead.ratio`` is the median of pairwise per-rep
    ratios (adjacent measurements cancel machine drift).  The CI bar is
    enabled throughput within 3% of disabled on hosts with >= 2 cores;
    single-core hosts cannot resolve 3% and the smoke floor widens to
    0.90 (see ``overlap_parallel_substrate``)."""
    import jax
    import jax.numpy as jnp

    import repro.configs as C
    from repro.models import model as mdl
    from repro.serve.engine import generate
    from repro.serve.sched import ContinuousBatcher, Request

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rec = obs.install(obs.Recorder())
    rng = np.random.default_rng(0)
    n_req = 4 if quick else 8
    page, max_len, max_active = 4, 64, 4
    macro_len = 8
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(5, 12))).astype(np.int32)
               for _ in range(n_req)]
    budgets = [int(rng.integers(10, 16)) for _ in range(n_req)]
    temps = [0.0 if i % 2 == 0 else 0.7 for i in range(n_req)]
    keys = [jax.random.PRNGKey(50 + i) for i in range(n_req)]

    def build(mode):
        pools = SharedPagedPools.create(192, 64)
        mgr = TieringManager(192, TierConfig(page_size=page, hbm_pages=64,
                                             period_steps=macro_len))
        mon = TrafficMonitor(pools, mgr,
                             OnlineTuner(192, default_period=macro_len,
                                         profile_steps=16, trial_steps=8))
        macro = mode in ("macro", "pipelined")
        return ContinuousBatcher(params, cfg, max_active=max_active,
                                 max_len=max_len, page_size=page,
                                 monitor=mon, paged=(mode != "dense"),
                                 macro=macro,
                                 macro_steps=(macro_len if macro else None),
                                 pipeline=(mode == "pipelined"))

    def submit_wave(b, wave):
        for i in range(n_req):
            b.submit(Request(rid=wave * n_req + i, prompt=prompts[i],
                             max_new_tokens=budgets[i], key=keys[i],
                             temperature=temps[i]))

    def drive(b):
        tokens, lats = 0, []
        while not b.idle:       # pipelined tail: in-flight macro, pendings
            t0 = time.perf_counter()
            out = b.step()
            lats.append(time.perf_counter() - t0)
            tokens += len(out)
        return tokens, lats

    refs = [np.asarray(generate(params, cfg, jnp.asarray(prompts[i])[None],
                                steps=budgets[i], temperature=temps[i],
                                key=keys[i]))[0].tolist()
            for i in range(n_req)]

    modes = ("paged", "macro", "pipelined", "dense")
    results: Dict[str, Dict] = {}
    parity: Dict[str, bool] = {}
    for mode in modes:
        b = build(mode)
        submit_wave(b, 0)                    # warm the jit caches
        drive(b)
        n_admits = len(rec.events("serve.admit"))
        n_spans = len(rec.events("obs.span"))
        submit_wave(b, 1)                    # timed wave
        t0 = time.perf_counter()
        tokens, lats = drive(b)
        wall = time.perf_counter() - t0
        b.close()
        lat_ms = np.asarray(lats) * 1e3
        results[mode] = {
            "tokens": tokens,
            "wall_s": wall,
            # decode token-steps/sec == tokens/sec: every emitted token
            # is one request-token-step (the satellite's "steps/sec")
            "tokens_per_sec": tokens / wall,
            "sched_steps": len(lats),
            "latency_ms_p50": float(np.percentile(lat_ms, 50)),
            "latency_ms_p95": float(np.percentile(lat_ms, 95)),
        }
        # p95 admission stall over the timed wave, from the flight
        # recorder: reservation-to-activation for the pipelined loop
        # (serve.admit's stall_ms), the serve.prefill span for the
        # synchronous paths (admission is inline there)
        admits = rec.events("serve.admit")[n_admits:]
        stalls = [e["stall_ms"] for e in admits if "stall_ms" in e] or [
            e["ms"] for e in rec.events("obs.span")[n_spans:]
            if e["name"] == "serve.prefill"]
        if stalls:
            results[mode]["admission_stall_ms_p95"] = float(
                np.percentile(np.asarray(stalls), 95))
        got = {r.rid: list(r.tokens) for r in b.completed}
        parity[mode] = all(got.get(n_req + i) == refs[i]
                           for i in range(n_req))

    # the overlap A-B: one warmed batcher per mode serves an identical
    # DOUBLE wave (2 x n_req over max_active rows, so joiners keep
    # prefilling while earlier rows decode -- the admission pressure the
    # overlap window exists to hide), interleaved best-of-3 so machine
    # drift hits both modes alike.  This is the assertable bar; the
    # single-wave rows above are per-mode latency reporting.
    ab = {m: build(m) for m in ("macro", "pipelined")}
    for b in ab.values():
        submit_wave(b, 0)                    # warm the jit caches
        drive(b)
    # machine noise here is low-frequency drift (whole phases speed up
    # and slow down), so the assertable ratio is the MEDIAN of pairwise
    # per-rep ratios -- adjacent measurements see the same machine state
    # and the drift cancels -- not a ratio of two independent bests
    ab_best = {m: 0.0 for m in ab}
    ab_ratios = []
    ab_wave = 1
    for rep in range(7):
        order = list(ab.items())
        if rep % 2:                      # alternate so order bias cancels
            order.reverse()
        per = {}
        for m, b in order:
            submit_wave(b, ab_wave)
            submit_wave(b, ab_wave + 1)
            ab_wave += 2
            gc.collect()                 # no GC pause inside the window
            t0 = time.perf_counter()
            tokens, _ = drive(b)
            per[m] = tokens / (time.perf_counter() - t0)
            ab_best[m] = max(ab_best[m], per[m])
        ab_ratios.append(per["pipelined"] / per["macro"])
    for b in ab.values():
        b.close()

    # telemetry overhead on the macro hot loop: one warmed batcher serves
    # alternating enabled/disabled DOUBLE waves (interleaved so machine
    # drift hits both modes alike; doubled so each timed window is long
    # enough that a GC pause or scheduler blip cannot masquerade as
    # recorder overhead), best-of-3 per mode
    b = build("macro")
    submit_wave(b, 0)
    drive(b)
    best = {True: 0.0, False: 0.0}
    oh_ratios = []
    wave = 1
    for rep in range(9):
        order = (True, False) if rep % 2 == 0 else (False, True)
        per = {}
        for enabled in order:
            rec.enabled = enabled
            submit_wave(b, wave)
            submit_wave(b, wave + 1)
            wave += 2
            gc.collect()                 # no GC pause inside the window
            t0 = time.perf_counter()
            tokens, _ = drive(b)
            per[enabled] = tokens / (time.perf_counter() - t0)
            best[enabled] = max(best[enabled], per[enabled])
        oh_ratios.append(per[True] / per[False])
    rec.enabled = True
    # same drift-robust estimator as the overlap A-B: median of pairwise
    # per-rep ratios, not a ratio of independent bests
    overhead = {"enabled_tok_s": best[True], "disabled_tok_s": best[False],
                "ratio": float(np.median(oh_ratios))}

    out = {
        "n_requests": n_req,
        "max_active": max_active,
        "macro_len": macro_len,
        "modes": results,
        "speedup_macro_vs_per_token": (results["macro"]["tokens_per_sec"]
                                       / results["paged"]["tokens_per_sec"]),
        # the overlap A-B: the pipelined loop vs the synchronous macro
        # loop under sustained admission -- overlap may only move work,
        # so any throughput delta is boundary host time (decision,
        # prefill, prefetch, tables) hidden behind the in-flight scan
        "overlap_ab": {"sync_tok_s": ab_best["macro"],
                       "pipelined_tok_s": ab_best["pipelined"],
                       "per_rep_ratios": ab_ratios},
        "speedup_overlap_vs_sync": float(np.median(ab_ratios)),
        # overlap needs somewhere to overlap INTO: on a single-core host
        # the in-flight scan and the boundary work time-slice the same
        # core, so wall time is conserved and the honest ceiling for the
        # A-B ratio is 1.0 (the smoke bar degrades to no-regression)
        "overlap_parallel_substrate": (os.cpu_count() or 1) >= 2,
        "parity_vs_generate": parity,
        "token_identical_all_modes": all(parity.values()),
        "telemetry_overhead": overhead,
        # the flight-recorder metrics of this whole benchmark run (see
        # docs/observability.md for the schema)
        "metrics": {"schema": obs.SCHEMA, **rec.summary()},
    }
    save_json("BENCH_serving", out)
    return out


def _print_serving(sp: Dict) -> None:
    for mode, r in sp["modes"].items():
        stall = r.get("admission_stall_ms_p95")
        print(f"serving[{mode:>9s}]: {r['tokens_per_sec']:8.1f} tok/s  "
              f"step p50 {r['latency_ms_p50']:7.2f} ms  "
              f"p95 {r['latency_ms_p95']:7.2f} ms  "
              f"({r['tokens']} tokens / {r['sched_steps']} sched steps"
              + (f"; admit stall p95 {stall:.1f} ms" if stall is not None
                 else "") + ")")
    print(f"macro-step speedup vs per-token paged: "
          f"{sp['speedup_macro_vs_per_token']:.2f}x; "
          f"overlap (pipelined vs sync macro): "
          f"{sp['speedup_overlap_vs_sync']:.2f}x; "
          f"token-identical (all modes vs generate): "
          f"{sp['token_identical_all_modes']}")
    ov = sp["telemetry_overhead"]
    print(f"telemetry overhead: enabled {ov['enabled_tok_s']:.0f} tok/s vs "
          f"disabled {ov['disabled_tok_s']:.0f} "
          f"(ratio {ov['ratio']:.3f})")


def overload(quick: bool = False) -> Dict:
    """Overload serving A-B: FIFO-forever vs graceful degradation.

    One heavy-tailed request stream arrives ~4x faster than the pool
    drains it.  The *baseline* batcher serves strict FIFO forever --
    every request is eventually served, including ones whose deadline
    passed long ago.  The *degraded* batcher turns on the overload
    ladder (docs/robustness.md): per-request admission TTLs (queued
    requests past their deadline shed with a typed status), a bounded
    submit queue (floods shed at submit instead of queueing without
    bound), and a deterministic mid-run HBM capacity squeeze exercising
    pressure preemption.  Both runs are scored by the SAME external
    rule -- tokens of requests that completed within ``ttl`` steps of
    arrival, per wall second (goodput) -- so shedding is only rewarded
    when the work it abandons was already worthless.  The degradation
    never trades fidelity: every stream the degraded run completes must
    be bit-identical to per-request ``generate``.  Written to
    ``BENCH_overload.json``."""
    import jax
    import jax.numpy as jnp

    import repro.configs as C
    from repro.ft.inject import FaultPlan, FaultPoint
    from repro.models import model as mdl
    from repro.serve.engine import generate
    from repro.serve.sched import ContinuousBatcher, Request

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    n_req = 32 if quick else 48
    ttl = 8
    page, max_len, max_active = 4, 64, 4
    n_logical, hbm = 96, 24
    # heavy-tailed: 3 in 4 short, 1 in 4 long; 8 arrivals per scheduler
    # step -- far past what max_active rows can drain inside a TTL, so
    # roughly half the offered work is doomed at arrival and a FIFO
    # server burns its wall clock on it anyway
    specs = []
    for i in range(n_req):
        long_req = i % 4 == 3
        specs.append(dict(
            arrival=i // 8,
            prompt=rng.integers(0, cfg.vocab_size,
                                size=9 if long_req else 5).astype(np.int32),
            budget=24 if long_req else 8,
            temp=0.7 if i % 2 else 0.0))

    def build(degrade: bool):
        pools = SharedPagedPools.create(n_logical, hbm)
        mgr = TieringManager(n_logical, TierConfig(page_size=page,
                                                   hbm_pages=hbm,
                                                   period_steps=4))
        mon = TrafficMonitor(pools, mgr,
                             OnlineTuner(n_logical, default_period=4,
                                         profile_steps=16, trial_steps=8))
        # BOTH runs face the identical deterministic mid-stream capacity
        # squeeze (the preemption ladder fires inside the measured
        # window; parity still holds -- preemption is a freeze, never a
        # token change).  Only the overload *policy* differs between the
        # modes: TTL shedding + the bounded queue.
        plan = FaultPlan([FaultPoint("pool.squeeze", start=6, stop=10,
                                     value=hbm // 2)], seed=0)
        return ContinuousBatcher(params, cfg, max_active=max_active,
                                 max_len=max_len, page_size=page,
                                 monitor=mon, macro=True, macro_steps=4,
                                 fault_plan=plan,
                                 max_queue=4 if degrade else None)

    def drive(b, *, base: int, degrade: bool):
        done_step: Dict[int, int] = {}
        lats = []
        t = 0
        pending = list(enumerate(specs))
        seen = len(b.completed)
        t0 = time.perf_counter()
        while pending or not b.idle:
            while pending and pending[0][1]["arrival"] <= t:
                i, s = pending.pop(0)
                b.submit(Request(rid=base + i, prompt=s["prompt"],
                                 max_new_tokens=s["budget"],
                                 temperature=s["temp"],
                                 key=jax.random.PRNGKey(300 + i),
                                 ttl_steps=ttl if degrade else None))
            s0 = time.perf_counter()
            b.step()
            lats.append(time.perf_counter() - s0)
            for r in b.completed[seen:]:
                done_step[r.rid - base] = t
            seen = len(b.completed)
            t += 1
            assert t < 3000, "overload drive must drain"
        return done_step, lats, time.perf_counter() - t0

    results: Dict[str, Dict] = {}
    parity = True
    for mode in ("baseline", "degraded"):
        degrade = mode == "degraded"
        b = build(degrade)
        # warm wave: the identical stream once over, so both prefill
        # shape buckets and the macro bodies are jitted before timing
        drive(b, base=10_000, degrade=degrade)
        # the warm wave consumed the squeeze window's clock span; rewind
        # the plan clock so the squeeze hits the timed wave
        b.fault_plan.clock = 0
        n0 = len(b.completed)
        pre_preempt = b.preemptions
        done_step, lats, wall = drive(b, base=0, degrade=degrade)
        timed = b.completed[n0:]
        status = {"completed": 0, "shed": 0, "expired": 0}
        good = total = 0
        for r in timed:
            status[r.status or "completed"] += 1
            total += len(r.tokens)
            if (r.status == "completed"
                    and done_step[r.rid] <= specs[r.rid]["arrival"] + ttl):
                good += len(r.tokens)
        lat_ms = np.asarray(lats) * 1e3
        results[mode] = {
            "wall_s": wall,
            "goodput_tok_s": good / wall,
            "in_deadline_tokens": good,
            "total_tokens": total,
            "statuses": status,
            "shed_rate": (status["shed"] + status["expired"]) / n_req,
            "p95_step_ms": float(np.percentile(lat_ms, 95)),
            "preemptions": b.preemptions - pre_preempt,
        }
        if degrade:
            for r in timed:
                if r.status != "completed":
                    continue
                s = specs[r.rid]
                ref = np.asarray(generate(
                    params, cfg, jnp.asarray(s["prompt"])[None],
                    steps=s["budget"], temperature=s["temp"],
                    key=jax.random.PRNGKey(300 + r.rid)))[0].tolist()
                parity = parity and list(r.tokens) == ref
        b.close()

    ratio = (results["degraded"]["goodput_tok_s"]
             / max(1e-9, results["baseline"]["goodput_tok_s"]))
    out = {
        "n_requests": n_req,
        "ttl_steps": ttl,
        "arrivals_per_step": 8,
        "modes": results,
        "goodput_ratio_degraded_vs_baseline": ratio,
        "degraded_completed_token_parity": parity,
    }
    save_json("BENCH_overload", out)
    return out


def _print_overload(ov: Dict) -> None:
    for mode, r in ov["modes"].items():
        st = r["statuses"]
        print(f"overload[{mode:>8s}]: goodput {r['goodput_tok_s']:8.1f} "
              f"tok/s  shed rate {r['shed_rate']:.2f}  "
              f"step p95 {r['p95_step_ms']:7.2f} ms  "
              f"preemptions {r['preemptions']}  "
              f"({st['completed']} completed / {st['shed']} shed / "
              f"{st['expired']} expired; wall {r['wall_s']:.2f}s)")
    print(f"goodput with degradation vs FIFO baseline: "
          f"{ov['goodput_ratio_degraded_vs_baseline']:.2f}x; "
          f"completed-stream parity vs generate: "
          f"{ov['degraded_completed_token_parity']}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="serving-throughput comparison only (the "
                         "macro-step acceptance bar)")
    args = ap.parse_args()
    if args.smoke:
        sp = serving_perf(quick=True)
        _print_serving(sp)
        assert sp["token_identical_all_modes"], \
            "macro/paged/dense decode diverged from per-request generate"
        assert sp["speedup_macro_vs_per_token"] >= 1.3, \
            "macro-step decode must beat the per-token paged path by " \
            f">= 1.3x (got {sp['speedup_macro_vs_per_token']:.2f}x)"
        # the overlap bar binds wherever overlap is physically possible
        # (>= 2 cores: the boundary host work runs while the scan holds
        # other cores).  A single-core host time-slices the two, so wall
        # time is conserved by construction and the bar degrades to
        # no-material-regression: the pipeline machinery (worker thread,
        # lazy admission, window bookkeeping) must stay within 10%.
        ov_floor = 1.0 if sp["overlap_parallel_substrate"] else 0.90
        assert sp["speedup_overlap_vs_sync"] >= ov_floor, \
            "the pipelined loop must not serve slower than the " \
            "synchronous macro loop " \
            f"(got {sp['speedup_overlap_vs_sync']:.2f}x, " \
            f"floor {ov_floor:.2f}x)"
        assert sp["parity_vs_generate"]["pipelined"], \
            "the pipelined loop diverged from per-request generate"
        # same substrate gate as the overlap bar: on a single-core host
        # the GIL, the recorder lock and XLA compute time-slice one core,
        # so paired wall measurements cannot resolve 3% (observed pair
        # spread ~0.6-1.3x with a median at 1.0) and the floor widens
        oh_floor = 0.97 if sp["overlap_parallel_substrate"] else 0.90
        assert sp["telemetry_overhead"]["ratio"] >= oh_floor, \
            "telemetry-enabled macro throughput regressed vs disabled " \
            f"(got {sp['telemetry_overhead']['ratio']:.3f}, " \
            f"floor {oh_floor:.2f})"
        ho = hostile(quick=True)
        _print_hostile(ho)
        assert ho["max_regret"] <= 1.15, \
            "hostile traffic shook the tuner: per-phase regret must stay " \
            f"<= 1.15x best fixed (got {ho['max_regret']:.3f}x)"
        assert ho["poisoned_trial"]["reverted"], \
            "poisoned TRIAL sweep must abort and revert to the last " \
            f"attested period (got {ho['poisoned_trial']})"
        m = mla(quick=True)
        _print_mla(m)
        assert m["token_identical"], \
            "paged MLA decode diverged from per-request generate"
        assert m["page_reduction_x"] >= 1.5, \
            "paged MLA admission must provision >= 1.5x fewer pages than " \
            f"dense rows (got {m['page_reduction_x']:.2f}x)"
        ovl = overload(quick=True)
        _print_overload(ovl)
        assert ovl["degraded_completed_token_parity"], \
            "graceful degradation must never trade token fidelity"
        assert ovl["goodput_ratio_degraded_vs_baseline"] >= 1.2, \
            "degradation must raise in-deadline goodput >= 1.2x over the " \
            "FIFO-forever baseline under overload " \
            f"(got {ovl['goodput_ratio_degraded_vs_baseline']:.2f}x)"
        raise SystemExit(0)
    r = run(args.quick)
    o = r["online"]
    print(f"traffic: {r['requests']['completed']}/{r['requests']['submitted']}"
          f" requests completed over {r['steps']} steps")
    cm = r["cache_memory"]
    print(f"cache memory: peak paged {cm['peak_paged_pages']} pages vs dense "
          f"{cm['dense_pages']} ({cm['reduction']:.1%} reduction)")
    print(f"online: period={o['final_period']} ({o['state']}) after "
          f"{o['tune_cycles']} tune cycles; steady {o['steady']:.2f}/step")
    for p, v in r["fixed"].items():
        print(f"    fixed {p:>3s}: steady {v['steady']:8.2f} total "
              f"{v['total']:10.0f}")
    print(f"online vs best fixed (steady): "
          f"{r['online_vs_best_fixed_steady']:.3f}x "
          f"(total {r['online_vs_best_fixed_total']:.3f}x)")
    tp = r["token_parity"]
    print(f"token parity: {tp['token_identical']} over {tp['requests']} "
          f"requests; paged kernel max diff {tp['paged_kernel_max_diff']:.1e};"
          f" pages released: {tp['pages_all_released']}")
    _print_hostile(hostile(args.quick))
    _print_serving(serving_perf(args.quick))
    _print_mla(mla(args.quick))
    _print_overload(overload(args.quick))
