"""Continuous-batching serving driver: online Cori tuned by real traffic.

Two stages:

  1. A model-backed ``ContinuousBatcher`` serves a handful of requests
     FULLY PAGED through one shared HBM page pool (admission mid-flight
     with batched prefills, retire on length, every attention layer
     decoding off the pool's slot tables, all-layer masses merged into
     the global page table) and cross-checks every request's tokens
     against per-request ``generate`` -- the scheduler must be invisible
     to the output.
  2. A model-free ``TrafficScheduler`` replays a long Poisson stream
     whose mix shifts mid-run, with the ``OnlineTuner`` re-tuning the
     shared pool's migration period from the merged traffic reuse.

    PYTHONPATH=src python examples/serve_traffic.py [--steps 1000]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro import compile_cache
from repro.core import OnlineTuner, shifting_mix_stream
from repro.memtier import SharedPagedPools, TierConfig, TieringManager
from repro.models import model as mdl
from repro.serve.engine import generate
from repro.serve.sched import (ContinuousBatcher, Request, TrafficMonitor,
                               TrafficScheduler)


def serve_batched(args):
    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    page = 4
    pools = SharedPagedPools.create(64, 24)
    mgr = TieringManager(64, TierConfig(page_size=page, hbm_pages=24,
                                        period_steps=2))
    tuner = OnlineTuner(64, default_period=2, profile_steps=8, trial_steps=4)
    batcher = ContinuousBatcher(params, cfg, max_active=args.batch,
                                max_len=48, page_size=page,
                                monitor=TrafficMonitor(pools, mgr, tuner))
    reqs = []
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=int(rng.integers(6, 14))).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt,
                            max_new_tokens=int(rng.integers(6, 12)),
                            key=jax.random.PRNGKey(100 + i)))
        batcher.submit(reqs[-1])
    got = batcher.run()
    ok = all(
        np.asarray(generate(params, cfg, jnp.asarray(r.prompt)[None],
                            steps=r.max_new_tokens,
                            key=jax.random.PRNGKey(100 + r.rid))
                   )[0].tolist() == got[r.rid]
        for r in reqs)
    mode = "fully-paged" if batcher.paged else "dense"
    print(f"batched serve ({mode}): {len(got)} requests over "
          f"{batcher.step_idx} scheduler steps on {args.batch} rows; "
          f"token-identical to per-request generate: {ok}")
    print(f"  shared pool: {mgr.migrations} migrations, {mgr.hits} hits / "
          f"{mgr.misses} misses, peak {pools.peak_allocated} pages, "
          f"tuner={tuner.state} period={tuner.period}")


def serve_traffic(args):
    n_logical, hbm, page = 256, 32, 16
    phase = args.steps // 2
    specs = shifting_mix_stream(
        [(phase, 0.1, {"random": 1.0}), (phase, 0.1, {"sink": 1.0})],
        prompt_len=(16, 48), new_tokens=(40, 100), seed=0)
    pools = SharedPagedPools.create(n_logical, hbm)
    mgr = TieringManager(n_logical, TierConfig(page_size=page,
                                               hbm_pages=hbm,
                                               period_steps=8))
    tuner = OnlineTuner(n_logical, default_period=8,
                        drift_ratio=1.5, drift_patience=3)
    sched = TrafficScheduler(specs, TrafficMonitor(pools, mgr, tuner),
                             page_size=page, max_active=8)
    sched.run(args.steps)
    print(f"\ntraffic: {sched.completed}/{len(specs)} requests completed "
          f"over {args.steps} steps (mix shift at step {phase})")
    print(f"  online Cori: state={tuner.state} period={tuner.period}, "
          f"{tuner.retunes} tune cycles, DR={tuner.dominant_reuse}")
    print(f"  period history (step, period): {tuner.history}")
    print(f"  shared pool: {mgr.migrations} migrations, modeled time "
          f"{mgr.modeled_time:.0f}, hit rate "
          f"{mgr.hits / max(1, mgr.hits + mgr.misses):.2f}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=1000,
                    help="traffic-replay decode steps")
    ap.add_argument("--batch", type=int, default=3,
                    help="continuous-batch rows (max in-flight requests)")
    ap.add_argument("--requests", type=int, default=6)
    args = ap.parse_args(argv)
    compile_cache.enable()
    serve_batched(args)
    serve_traffic(args)


if __name__ == "__main__":
    main()
