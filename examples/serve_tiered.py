"""End-to-end serving driver (the paper's kind: memory-system serving).

Serves a small gemma3-family model with batched requests while the
Cori-tuned tiering runtime manages the KV-page working set:

  1. prefill + batched decode with the attention monitor on,
  2. profile window -> reuse histogram -> DR -> candidate periods,
  3. Cori tunes the tiering period; the tiered pool is then replayed with
     physical page migrations (gather/scatter) and validated against the
     paged_attention kernel.

With ``--online`` the offline profile/replay split disappears: an
``OnlineTuner`` rides the decode loop itself (through
``monitored_generate``'s ``on_mass`` hook), re-deriving dominant reuse from
a sliding window and re-trialing candidate periods against the live
TieringManager, so the migration period adapts while tokens are still being
generated.

    PYTHONPATH=src python examples/serve_tiered.py [--steps 48] [--online]
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro import compile_cache
from repro.core import OnlineTuner
from repro.memtier import (PagedPools, TierConfig, TieringManager,
                           cori_tune_period, replay)
from repro.models import model as mdl
from repro.serve.engine import monitored_generate


def serve_online(params, cfg, prompts, args):
    """Closed-loop path: tiering + tuning run inside the decode loop."""
    prefix = cfg.prefix_len or 0
    max_len = prompts.shape[1] + prefix + args.steps
    n_pages = -(-max_len // args.page_size)
    tc = TierConfig(page_size=args.page_size,
                    hbm_pages=max(2, n_pages // 4), period_steps=4)
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    key = jax.random.PRNGKey(2)
    k_pages = jax.random.normal(key, (n_pages, args.page_size, kv, hd))
    v_pages = jax.random.normal(jax.random.fold_in(key, 1), k_pages.shape)
    pools = PagedPools.create(k_pages, v_pages, tc.hbm_pages)
    mgr = TieringManager(n_pages, tc)
    tuner = OnlineTuner(n_pages, default_period=tc.period_steps,
                        profile_steps=max(8, args.steps // 4),
                        trial_steps=max(4, args.steps // 8),
                        access_threshold=tc.access_threshold)

    def on_mass(i, m):
        nonlocal pools
        before = mgr.modeled_time
        mgr.on_step(m, pools.slot_of >= 0)
        pools = mgr.maybe_tier(pools)
        mgr.set_period(tuner.on_step(m, cost=mgr.modeled_time - before))

    tokens, mass = monitored_generate(params, cfg, prompts, steps=args.steps,
                                      page_size=args.page_size,
                                      on_mass=on_mass)
    print(f"generated {tokens.shape[1]} tokens/request with the online "
          f"tuner in the loop")
    print(f"online Cori: state={tuner.state} period={tuner.period} "
          f"(DR={tuner.dominant_reuse}, {len(tuner.tried)} live trials, "
          f"{tuner.retunes} tune cycles)")
    print(f"period history (step, period): {tuner.history}")
    print(f"tiering: {mgr.migrations} page swaps, "
          f"{mgr.data_moved_pages} pages moved, modeled time "
          f"{mgr.modeled_time:.0f}, "
          f"{int((pools.slot_of >= 0).sum())}/{n_pages} pages resident")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=4)
    ap.add_argument("--online", action="store_true",
                    help="closed-loop tuning inside the decode loop")
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = C.reduced("gemma3-12b")
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, 16), 0, cfg.vocab_size)

    print(f"serving {cfg.name} (reduced): batch={args.batch}, "
          f"decode steps={args.steps}")
    if args.online:
        serve_online(params, cfg, prompts, args)
        return
    tokens, mass = monitored_generate(params, cfg, prompts,
                                      steps=args.steps,
                                      page_size=args.page_size)
    n_pages = mass.shape[1]
    print(f"generated {tokens.shape[1]} tokens/request; monitored "
          f"{mass.shape[0]} steps x {n_pages} KV pages")

    tc = TierConfig(hbm_pages=max(2, n_pages // 4), period_steps=4)
    res, dr = cori_tune_period(mass, tc)
    print(f"\nCori: dominant reuse = {dr:.1f} decode steps; "
          f"chose tiering period {res.chosen_period:.0f} in {res.trials} "
          f"trials")
    for p in (1, 4, 16):
        t = replay(mass, dataclasses.replace(tc, period_steps=p)).modeled_time
        print(f"    fixed period {p:3d}: modeled time {t:10.0f}")
    print(f"    cori period {res.chosen_period:3.0f}: modeled time "
          f"{res.chosen_runtime:10.0f}")

    # physical migration pass over real KV pages of the monitor layer
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    key = jax.random.PRNGKey(2)
    k_pages = jax.random.normal(key, (n_pages, args.page_size, kv, hd))
    v_pages = jax.random.normal(jax.random.fold_in(key, 1), k_pages.shape)
    pools = PagedPools.create(k_pages, v_pages, tc.hbm_pages)
    mgr = TieringManager(n_pages, dataclasses.replace(
        tc, period_steps=max(1, int(res.chosen_period))))
    for t in range(mass.shape[0]):
        mgr.on_step(mass[t], pools.slot_of >= 0)
        pools = mgr.maybe_tier(pools)
    print(f"\nphysical pass: {mgr.migrations} page swaps, "
          f"{mgr.data_moved_pages} pages moved, "
          f"{int((pools.slot_of >= 0).sum())}/{n_pages} pages resident")


if __name__ == "__main__":
    main()
