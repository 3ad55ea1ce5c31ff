"""Mixture-of-Experts layers.

Two implementations sharing identical routing math (softmax over top-k
logits, renormalised):

  * ``dense``     -- computes every expert for every token; the numerics
                     oracle used by smoke/property tests (tiny configs only).
  * ``shard_map`` -- production expert-parallel path: activations are
                     replicated across the ``model`` mesh axis (TP), experts
                     are sharded over it; each shard sort-dispatches tokens
                     to its local experts under a capacity bound and the
                     partial outputs are ``psum``-combined.  Communication
                     profile == one TP all-reduce per MoE layer, no
                     all-to-all -- the right trade on ICI-rich TPU meshes.

Both are fully differentiable (capacity drops use stop-gradient-free
masking; indices are non-differentiable by construction).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import _dense_init, split_tree

Params = Dict[str, Any]


def moe_init(key, cfg: ModelConfig):
    mo = cfg.moe
    d, e, f = cfg.d_model, mo.num_experts, mo.d_expert
    ks = jax.random.split(key, 5)
    tree = {
        "router": _dense_init(ks[0], (d, e), ("embed", None)),
        "wi_gate": _dense_init(ks[1], (e, d, f), ("expert", "embed", "mlp")),
        "wi_up": _dense_init(ks[2], (e, d, f), ("expert", "embed", "mlp")),
        "wo": _dense_init(ks[3], (e, f, d), ("expert", "mlp", "embed")),
    }
    if mo.num_shared:
        fs = (mo.d_shared or mo.d_expert) * mo.num_shared
        k5, k6, k7 = jax.random.split(ks[4], 3)
        tree["shared"] = {
            "wi_gate": _dense_init(k5, (d, fs), ("embed", "mlp")),
            "wi_up": _dense_init(k6, (d, fs), ("embed", "mlp")),
            "wo": _dense_init(k7, (fs, d), ("mlp", "embed")),
        }
    return split_tree(tree)


def _route(x, router_w, top_k: int):
    """Common routing: returns (weights [T,k], idx [T,k], probs [T,E])."""
    logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p.astype(x.dtype), top_i, probs


def _aux_loss(probs, top_i, num_experts: int):
    """Switch-style load-balance loss."""
    me = jnp.mean(probs, axis=0)                        # mean router prob
    ce = jnp.mean(jax.nn.one_hot(top_i[:, 0], num_experts), axis=0)
    return num_experts * jnp.sum(me * ce)


def _shared_out(p, x):
    h = jax.nn.silu(x @ p["wi_gate"].astype(x.dtype)) * (
        x @ p["wi_up"].astype(x.dtype))
    return h @ p["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# dense oracle
# ---------------------------------------------------------------------------


def moe_apply_dense(p: Params, cfg: ModelConfig, x) -> Tuple[Any, Any]:
    """x: [B,S,d] -> (y, aux_loss).  Computes all experts (oracle)."""
    mo = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    w, idx, probs = _route(xt, p["router"], mo.top_k)
    h = jnp.einsum("td,edf->tef", xt, p["wi_gate"].astype(x.dtype))
    u = jnp.einsum("td,edf->tef", xt, p["wi_up"].astype(x.dtype))
    y_all = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * u,
                       p["wo"].astype(x.dtype))        # [T,E,d]
    sel = jnp.take_along_axis(y_all, idx[:, :, None], axis=1)  # [T,k,d]
    y = jnp.sum(sel * w[:, :, None], axis=1)
    if mo.num_shared:
        y = y + _shared_out(p["shared"], xt)
    return y.reshape(b, s, d), _aux_loss(probs, idx, mo.num_experts)


# ---------------------------------------------------------------------------
# shard_map expert-parallel path
# ---------------------------------------------------------------------------


def _local_dispatch(xt, w, idx, e0, e_local: int, capacity: int):
    """Build the [E_local, C, d] buffer for this shard's experts.

    xt: [T,d]; w/idx: [T,k].  Token-expert pairs whose expert lives on this
    shard are ranked FCFS; pairs beyond `capacity` are dropped (standard
    capacity-factor semantics)."""
    t, k = idx.shape
    pairs_e = idx.reshape(-1)                      # [T*k] global expert id
    pairs_w = w.reshape(-1)
    pairs_t = jnp.repeat(jnp.arange(t), k)
    local = (pairs_e >= e0) & (pairs_e < e0 + e_local)
    le = jnp.where(local, pairs_e - e0, e_local)   # e_local == trash bin
    onehot = jax.nn.one_hot(le, e_local + 1, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1           # position within expert
    pos = jnp.take_along_axis(pos, le[:, None], axis=1)[:, 0]
    keep = local & (pos < capacity)
    le_c = jnp.where(keep, le, e_local)            # clamp for scatter
    pos_c = jnp.where(keep, pos, 0)
    buf = jnp.zeros((e_local + 1, capacity, xt.shape[1]), xt.dtype)
    buf = buf.at[le_c, pos_c].add(jnp.where(keep[:, None], xt[pairs_t], 0))
    return buf[:e_local], (pairs_t, le_c, pos_c, pairs_w, keep)


def _local_combine(y_buf, meta, t: int, d: int):
    pairs_t, le_c, pos_c, pairs_w, keep = meta
    gathered = y_buf[jnp.minimum(le_c, y_buf.shape[0] - 1), pos_c]
    contrib = jnp.where(keep[:, None], gathered * pairs_w[:, None], 0)
    return jnp.zeros((t, d), y_buf.dtype).at[pairs_t].add(contrib)


def moe_apply_shard_map(p: Params, cfg: ModelConfig, x, mesh,
                        model_axis: str = "model") -> Tuple[Any, Any]:
    """Expert-parallel MoE.  x: [B,S,d] sharded on batch only (replicated
    over `model_axis`); experts sharded over `model_axis`."""
    mo = cfg.moe
    b, s, d = x.shape
    n_model = mesh.shape[model_axis]
    assert mo.num_experts % n_model == 0, (mo.num_experts, n_model)
    e_local = mo.num_experts // n_model

    batch_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    P = jax.sharding.PartitionSpec

    def shard_fn(xt, router_w, wi_gate, wi_up, wo):
        # xt: [T_local, d] (batch-sharded, model-replicated)
        t = xt.shape[0]
        wgt, idx, probs = _route(xt, router_w, mo.top_k)
        e0 = jax.lax.axis_index(model_axis) * e_local
        capacity = max(1, int(np.ceil(t * mo.top_k / mo.num_experts
                                      * mo.capacity_factor)))
        buf, meta = _local_dispatch(xt, wgt, idx, e0, e_local, capacity)
        h = jnp.einsum("ecd,edf->ecf", buf, wi_gate.astype(xt.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, wi_up.astype(xt.dtype))
        y_buf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u,
                           wo.astype(xt.dtype))
        y = _local_combine(y_buf, meta, t, d)
        y = jax.lax.psum(y, model_axis)
        # global load-balance loss: pmean the *means*, then the product
        me = jax.lax.pmean(jnp.mean(probs, axis=0), batch_axes)
        ce = jax.lax.pmean(
            jnp.mean(jax.nn.one_hot(idx[:, 0], mo.num_experts), axis=0),
            batch_axes)
        aux = mo.num_experts * jnp.sum(me * ce)
        return y, aux

    # Shared experts are computed OUTSIDE the shard_map as a plain TP MLP
    # (their mlp dim is sharded over `model_axis` by the param specs);
    # computing them replicated inside and psum'ing would overcount.
    shared_y = None
    if mo.num_shared:
        shared_y = _shared_out(p["shared"], x.reshape(b * s, d))

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(batch_axes, None), P(None, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=(P(batch_axes, None), P()),
    )
    y, aux = fn(x.reshape(b * s, d), p["router"], p["wi_gate"], p["wi_up"],
                p["wo"])
    if shared_y is not None:
        y = y + shared_y
    return y.reshape(b, s, d), aux


def moe_apply(p: Params, cfg: ModelConfig, x, mesh=None):
    if cfg.moe_impl == "shard_map" and mesh is not None:
        return moe_apply_shard_map(p, cfg, x, mesh)
    return moe_apply_dense(p, cfg, x)
