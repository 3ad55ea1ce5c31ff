"""Training step: loss, gradient accumulation, compressed cross-pod DP.

``make_train_step`` builds a jittable ``(state, batch) -> (state, metrics)``
closure for a ModelConfig:

  * microbatching -- ``accum_steps`` splits the per-step batch and
    accumulates grads with ``lax.scan`` (bounds activation memory; the
    340B-class configs need it to fit v5e HBM -- see EXPERIMENTS.md).
  * remat         -- per-layer ``jax.checkpoint`` inside the model.
  * compressed cross-pod DP -- when the mesh has a "pod" axis and
    ``grad_compression=True``, the step runs under ``shard_map`` with the
    pod axis manual and all other axes auto: each pod computes grads for
    its pod-local batch (data/model parallelism inside stays automatic),
    and the cross-pod gradient reduction -- the only DCN-crossing
    collective -- goes through the int8 error-feedback ``compressed_psum``.

Loss: softmax cross-entropy, targets == IGNORE (-1) masked out (used for
VLM image-prefix positions and padding).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.collectives import compressed_psum
from repro.models import model as mdl
from repro.models.config import ModelConfig
from repro.train import optim

IGNORE = -1


def cross_entropy(logits, targets):
    """Mean CE over non-ignored targets.  logits: [B,S,V] (any float dtype),
    targets: [B,S] int32 with IGNORE for masked positions."""
    logits = logits.astype(jnp.float32)
    mask = (targets != IGNORE)
    tgt = jnp.where(mask, targets, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    ce = (logz - gold) * mask
    return jnp.sum(ce) / jnp.maximum(jnp.sum(mask), 1)


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, Any], mesh=None,
            shard=lambda x, n: x, param_specs=None, pshard=None):
    logits, aux = mdl.forward(
        params, cfg, batch["tokens"],
        extra_embeds=batch.get("extra_embeds"), cond=batch.get("cond"),
        mesh=mesh, shard=shard, param_specs=param_specs, pshard=pshard)
    ce = cross_entropy(logits, batch["targets"])
    aux_w = cfg.moe.aux_loss_weight if cfg.moe is not None else 0.0
    return ce + aux_w * aux, {"ce": ce, "aux": aux}


def _split_microbatches(batch, accum: int):
    return jax.tree.map(
        lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]), batch)


def cast_params_tree(params, dtype=jnp.bfloat16):
    """Cast f32 weight leaves to `dtype` (cast-before-gather: the FSDP
    all-gather then moves 2-byte words -- half the collective volume of
    gathering f32 masters).  Grads still accumulate into f32 masters via
    the cast's transpose."""
    return jax.tree.map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a, params)


def make_train_step(cfg: ModelConfig, ocfg: optim.OptConfig, mesh=None,
                    shard=lambda x, n: x, accum_steps: int = 1,
                    grad_compression: bool = False, param_specs=None,
                    cast_params: bool = False):
    """Returns train_step(state, batch) -> (state, metrics).
    state = {"params", "opt", "step"}."""
    from repro.distributed import sharding as _SHX
    pshard = _SHX.make_param_shard_fn(mesh) if param_specs is not None else None

    def grads_of(params, batch):
        if cast_params:
            params = cast_params_tree(params)
        if accum_steps == 1:
            (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, cfg, batch, mesh, shard, param_specs, pshard)
            return g, l, m

        micro = _split_microbatches(batch, accum_steps)

        def body(carry, mb):
            gsum, lsum = carry
            (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, cfg, mb, mesh, shard, param_specs, pshard)
            gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                gsum, g)
            return (gsum, lsum + l), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, lsum), _ = jax.lax.scan(body, (g0, jnp.float32(0.0)), micro)
        g = jax.tree.map(lambda a: a / accum_steps, gsum)
        return g, lsum / accum_steps, {}

    use_pod = (grad_compression and mesh is not None
               and "pod" in mesh.axis_names and mesh.shape["pod"] > 1)

    def plain_step(state, batch):
        g, loss, _ = grads_of(state["params"], batch)
        new_p, new_opt, om = optim.update(g, state["opt"], state["params"],
                                          ocfg)
        metrics = {"loss": loss, **om}
        return {"params": new_p, "opt": new_opt,
                "step": state["step"] + 1}, metrics

    if not use_pod:
        return plain_step

    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as _SH

    inner_shard = _SH.make_shard_fn(mesh, exclude=("pod",))

    def grads_of_pod(params, batch):
        if accum_steps == 1:
            (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, cfg, batch, mesh, inner_shard)
            return g, l, m
        micro = _split_microbatches(batch, accum_steps)

        def body(carry, mb):
            gsum, lsum = carry
            (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, cfg, mb, mesh, inner_shard)
            gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                gsum, g)
            return (gsum, lsum + l), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, lsum), _ = jax.lax.scan(body, (g0, jnp.float32(0.0)), micro)
        return (jax.tree.map(lambda a: a / accum_steps, gsum),
                lsum / accum_steps, {})

    def pod_local(params, opt, step, batch):
        g, loss, _ = grads_of_pod(params, batch)
        # int8 error-feedback all-reduce across pods (the only DCN hop)
        g = jax.tree.map(lambda x: compressed_psum(x, "pod"), g)
        loss = jax.lax.pmean(loss, "pod")
        new_p, new_opt, om = optim.update(g, opt, params, ocfg)
        return new_p, new_opt, step + 1, {"loss": loss, **om}

    def pod_step(state, batch):
        fn = jax.shard_map(
            pod_local, mesh=mesh,
            in_specs=(P(), P(), P(), P("pod")),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
            axis_names={"pod"},
        )
        new_p, new_opt, step, metrics = fn(state["params"], state["opt"],
                                           state["step"], batch)
        return {"params": new_p, "opt": new_opt, "step": step}, metrics

    return pod_step


def init_state(key, cfg: ModelConfig, ocfg: optim.OptConfig):
    params, specs = mdl.init(key, cfg)
    opt = optim.init(params, ocfg)
    return ({"params": params, "opt": opt, "step": jnp.zeros((), jnp.int32)},
            specs)


def state_specs(param_specs, ocfg: optim.OptConfig):
    return {"params": param_specs,
            "opt": optim.state_specs(param_specs, ocfg),
            "step": None}
