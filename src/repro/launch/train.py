"""Training driver: data pipeline + train step + checkpoint/restart + FT.

Runs any ``--arch`` (reduced or full config) on the local device mesh.
This is the process the ``repro.ft.supervisor`` relaunches on failure:
at startup it restores the newest checkpoint and resumes the *exact*
deterministic data stream from the restored step.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --reduced \
      --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/run1
  REPRO_FAIL_AT_STEP=20 PYTHONPATH=src python -m repro.launch.train ...
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro import compile_cache
from repro.ckpt import checkpoint as ckpt
from repro.data.pipeline import DataConfig, batch_at
from repro.distributed import sharding as SH
from repro.ft.monitor import FailureInjector, Heartbeat, StepTimer
from repro.launch.mesh import make_host_mesh
from repro.models import model as mdl
from repro.train import optim, step as tstep


def build(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default="")
    return ap.parse_args(argv)


def main(argv=None):
    args = build(argv)
    compile_cache.enable()
    cfg = C.reduced(args.arch) if args.reduced else C.get(args.arch)
    ocfg = optim.OptConfig(lr=args.lr, warmup_steps=max(2, args.steps // 20),
                           decay_steps=args.steps)
    dcfg = DataConfig(seed=args.seed, global_batch=args.batch,
                      seq_len=args.seq)

    mesh = None
    shard = lambda x, n: x
    if args.data_mesh * args.model_mesh > 1:
        mesh = make_host_mesh(data=args.data_mesh, model=args.model_mesh)
        shard = SH.make_shard_fn(mesh)

    state, specs = tstep.init_state(jax.random.PRNGKey(args.seed), cfg, ocfg)
    step_fn = jax.jit(tstep.make_train_step(cfg, ocfg, mesh=mesh, shard=shard,
                                            accum_steps=args.accum))

    start = 0
    workdir = pathlib.Path(args.ckpt_dir) if args.ckpt_dir else None
    if workdir:
        last = ckpt.latest_step(workdir)
        if last is not None:
            state = ckpt.restore(workdir, last, state)
            start = last
            print(f"[train] restored step {start} from {workdir}")
    saver = ckpt.AsyncCheckpointer(workdir) if workdir else None
    injector = FailureInjector(workdir or ".")
    timer = StepTimer()
    hb = Heartbeat((workdir or pathlib.Path(".")) / "heartbeat")

    losses = []
    with hb:
        for i in range(start, args.steps):
            injector.check(i)
            batch = {k: jnp.asarray(v)
                     for k, v in batch_at(dcfg, cfg, i).items()}
            timer.start()
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            timer.stop(i)
            losses.append(loss)
            if i % args.log_every == 0 or i == args.steps - 1:
                print(f"[train] step {i} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.3f}")
            if saver and (i + 1) % args.ckpt_every == 0:
                saver.save(i + 1, state)
    if saver:
        saver.save(args.steps, state)
        saver.wait()
    report = {"final_loss": losses[-1], "first_loss": losses[0],
              "steps_run": len(losses), "start": start,
              "stragglers": timer.stragglers}
    print("[train] done:", json.dumps(report))
    if args.metrics_out:
        pathlib.Path(args.metrics_out).write_text(json.dumps(
            {**report, "losses": losses}))
    return report


if __name__ == "__main__":
    main()
