"""Bring-up smoke of the served path on one TPU chip.

Serves qwen3-14b at its published widths (d_model 5120, 40/8 heads of
128, d_ff 17408, vocabulary 151936, untied embeddings) through the normal
serving stack -- ``SharedPagedPools``, ``TieringManager``, ``OnlineTuner``,
``TrafficMonitor`` and ``ContinuousBatcher`` with its default macro loop --
and the compiled Pallas paged-attention kernel.  Depth is cut from 40
layers to 2: the weights are float32, and with 4 layers the macro decode
program does not fit the chip's 16 GB (11.5 GB of weights plus 6.2 GB of
compiler temporaries, most of them the weights' bf16 copies hoisted out of
the decode loop).  Weights are random, made from ``--seed``.

Phases, all in this one process (a second process could not reach the
chip this one holds):

  1. init     -- random weights on the device;
  2. kernel   -- the served macro program's HLO holds ``tpu_custom_call``,
                 and one paged decode step at the served shapes gives the
                 same logits through the kernel as through the jnp
                 reference;
  3. sync     -- 8 seeded requests (prompts of 512-2048 tokens, 64 new
                 tokens each) arrive two per scheduler step on 4 rows, so
                 later ones are admitted while earlier ones decode;
  4. pipeline -- the same requests through the pipelined macro loop,
                 which must emit token-identical streams.

Any failed check raises, and the exit code is then not 0.  Compile
seconds and per-phase wall time are printed as set-up information; the
last line of standard output is one JSON object naming the device.

    python chip_smoke.py                # on a TPU host
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse

``--rehearse`` is the only mode that runs without a TPU: the reduced
qwen3-14b config on the CPU at small prompt lengths, the kernel check in
Pallas interpret mode, and the served loop on the CPU's reference path.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.configs as C  # noqa: E402
from repro import compile_cache  # noqa: E402
from repro.core import OnlineTuner  # noqa: E402
from repro.memtier import (SharedPagedPools, TierConfig,  # noqa: E402
                           TieringManager)
from repro.models import model as mdl  # noqa: E402
from repro.serve import sched as S  # noqa: E402

PAGE = 16
ROWS = 4
N_REQUESTS = 8
LAYERS = 2
# Logits of one paged decode step through the Pallas kernel against the
# jnp reference, as max |difference| over max |reference logit|.  The two
# attention paths round their f32 operands differently on the chip (XLA's
# default-precision f32 dots take bf16 operands: 2^-8 relative rounding),
# and two layers and the unembedding carry that into the logits; a wrong
# page, mask or softmax gives errors of the order of the logits themselves.
LOGIT_RTOL = 3e-2
# per-page attention mass (each row sums to 1): same rounding, no scale
MASS_ATOL = 2e-2


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="reduced config on the CPU with interpret kernels")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (a persistent
    cache hit counts its load time under compiling)."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def __init__(self):
        self.secs = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.secs[self.EVENTS[event]] += duration


def setup_line(**kw):
    print("setup " + " ".join(f"{k}={v}" for k, v in kw.items()), flush=True)


def make_requests(cfg, rng, lo: int, hi: int, new: int):
    reqs = []
    for i in range(N_REQUESTS):
        plen = int(rng.integers(lo, hi + 1))
        prompt = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
        reqs.append((i, prompt, new))
    return reqs


def serve(params, cfg, reqs, *, max_len: int, pipeline: bool):
    """Serve ``reqs`` through the full stack; returns {rid: tokens}."""
    n_row = max_len // PAGE
    hbm = ROWS * n_row               # every in-flight page fits the HBM tier
    n_logical = 2 * hbm              # the host tier holds twice that
    pools = SharedPagedPools.create(n_logical, hbm)
    mgr = TieringManager(n_logical, TierConfig(page_size=PAGE,
                                               hbm_pages=hbm,
                                               period_steps=16))
    tuner = OnlineTuner(n_logical, default_period=16, profile_steps=32,
                        trial_steps=16)
    batcher = S.ContinuousBatcher(
        params, cfg, max_active=ROWS, max_len=max_len, page_size=PAGE,
        monitor=S.TrafficMonitor(pools, mgr, tuner), pipeline=pipeline)
    try:
        # two arrivals per scheduler step: the third pair and later wait
        # for rows, and join while earlier requests are still decoding
        for i in range(0, len(reqs), 2):
            for rid, prompt, new in reqs[i: i + 2]:
                batcher.submit(S.Request(
                    rid=rid, prompt=prompt, max_new_tokens=new,
                    key=jax.random.PRNGKey(1000 + rid)))
            batcher.step()
        got = batcher.run()
    finally:
        batcher.close()
    done = {r.rid: r for r in batcher.completed}
    for rid, _, new in reqs:
        r = done.get(rid)
        if r is None or r.status != "completed" or len(r.tokens) != new:
            raise AssertionError(
                f"request {rid} did not complete with {new} tokens: "
                f"{None if r is None else (r.status, len(r.tokens))}")
    setup_line(phase="pipeline" if pipeline else "sync",
               scheduler_steps=batcher.step_idx,
               migrations=mgr.migrations, tuner_state=tuner.state,
               period=tuner.period)
    return {rid: list(got[rid]) for rid, _, _ in reqs}


def check_kernel(params, cfg, *, max_len: int, on_tpu: bool, seed: int):
    """The served macro program holds the compiled kernel on a TPU (and no
    TPU kernel in the CPU rehearsal); one paged decode step at the served
    shapes gives the same logits through the kernel as through the
    reference, on seeded random pages."""
    n_row = max_len // PAGE
    hbm, n_logical = ROWS * n_row, 2 * ROWS * n_row
    rng = np.random.default_rng(seed + 1)
    key = jax.random.PRNGKey(seed + 1)
    kv = {}
    for i, (name, pages) in enumerate([("k_hbm", hbm), ("v_hbm", hbm),
                                       ("k_host", n_logical),
                                       ("v_host", n_logical)]):
        shape = (cfg.num_layers, pages, PAGE, cfg.num_kv_heads, cfg.head_dim)
        kv[name] = [jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)]
    tables = jnp.asarray(rng.permutation(hbm)[: ROWS * n_row]
                         .reshape(ROWS, n_row), jnp.int32)
    gids = jnp.asarray(rng.permutation(n_logical)[: ROWS * n_row]
                       .reshape(ROWS, n_row), jnp.int32)
    pos = jnp.asarray([max_len - 1, 3 * max_len // 4, max_len // 3, 0],
                      jnp.int32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(ROWS, 1)),
                       jnp.int32)

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    text = S.decode_macro.lower(
        params, cfg, kv, tables, gids, toks, pos,
        jax.ShapeDtypeStruct((ROWS, 2), jnp.uint32), i32(ROWS), i32(ROWS),
        i32(ROWS), i32(ROWS), jax.ShapeDtypeStruct((ROWS,), jnp.float32),
        n_steps=16, page_size=PAGE).as_text()
    if ("tpu_custom_call" in text) != on_tpu:
        raise AssertionError("the served macro program "
                             + ("lacks" if on_tpu else "holds")
                             + " the Pallas TPU kernel (tpu_custom_call)")

    kernel_impl = "pallas" if on_tpu else "interpret"
    out = {}
    for impl in (kernel_impl, "reference"):
        step = jax.jit(functools.partial(mdl.decode_step_paged,
                                         page_size=PAGE, impl=impl),
                       static_argnums=(1,))
        logits, _, mass = step(params, cfg, kv, tables, gids, toks, pos)
        out[impl] = (np.asarray(logits, np.float32),
                     np.asarray(mass, np.float32))
    (lk, mk), (lr, mr) = out[kernel_impl], out["reference"]
    if lk.shape != (ROWS, 1, cfg.vocab_size):
        raise AssertionError(f"logits shape {lk.shape}")
    if not (np.isfinite(lk).all() and np.isfinite(lr).all()):
        raise AssertionError("non-finite logits")
    rel = float(np.abs(lk - lr).max() / np.abs(lr).max())
    mass_err = float(np.abs(mk - mr).max())
    setup_line(phase="kernel", impl=kernel_impl,
               logit_rel_err=rel, logit_rtol=LOGIT_RTOL,
               mass_abs_err=mass_err, mass_atol=MASS_ATOL)
    if rel > LOGIT_RTOL or mass_err > MASS_ATOL:
        raise AssertionError(f"{kernel_impl} vs reference: logit rel err "
                             f"{rel:.3e} (tol {LOGIT_RTOL}), mass err "
                             f"{mass_err:.3e} (tol {MASS_ATOL})")


def main(argv=None) -> None:
    args = parse_args(argv)
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    compile_cache.enable()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
                 "--rehearse is the only CPU mode")
    clock = CompileClock()
    if args.rehearse:
        cfg = C.reduced("qwen3-14b")
        lo, hi, new = 32, 128, 8
    else:
        cfg = dataclasses.replace(C.get("qwen3-14b"),
                                  segments=((("attn",), LAYERS),))
        lo, hi, new = 512, 2048, 64
    max_len = -(-(hi + new) // PAGE) * PAGE
    wall = {}

    t0 = time.monotonic()
    params, _ = mdl.init(jax.random.PRNGKey(args.seed), cfg)
    jax.block_until_ready(params)
    wall["init"] = time.monotonic() - t0

    t0 = time.monotonic()
    check_kernel(params, cfg, max_len=max_len, on_tpu=on_tpu,
                 seed=args.seed)
    wall["kernel"] = time.monotonic() - t0

    reqs = make_requests(cfg, np.random.default_rng(args.seed), lo, hi, new)
    t0 = time.monotonic()
    sync = serve(params, cfg, reqs, max_len=max_len, pipeline=False)
    wall["sync"] = time.monotonic() - t0

    t0 = time.monotonic()
    piped = serve(params, cfg, reqs, max_len=max_len, pipeline=True)
    wall["pipeline"] = time.monotonic() - t0
    if piped != sync:
        bad = [rid for rid in sync if piped[rid] != sync[rid]]
        raise AssertionError(f"pipelined streams differ from the sync "
                             f"loop's for requests {bad}")

    setup_line(config=cfg.name, layers=cfg.num_layers,
               d_model=cfg.d_model, prompts=f"{lo}-{hi}", new_tokens=new,
               requests=N_REQUESTS, rows=ROWS)
    setup_line(compile_s=clock.secs["compile"], trace_s=clock.secs["trace"],
               lower_s=clock.secs["lower"], cache_dir=compile_cache.enable())
    setup_line(**{f"wall_{k}_s": v for k, v in wall.items()})
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
