"""Mixture-of-Experts layers.

Two implementations sharing the router (``route``: softmax over top-k
logits, renormalised; or DeepSeek-V3's sigmoid group-limited rule, chosen
by ``MoEConfig.scoring_func``):

  * ``grouped``   -- the served layer (and the default everywhere): told
                     which contiguous block of experts it holds, it routes
                     over all of them, sorts the token-expert pairs whose
                     expert it holds by expert and runs one grouped matmul
                     per projection over the held experts
                     (``kernels.ops.expert_gmm``).  Dropless: the buffer
                     holds the worst case, T x min(k, held) rows, chunked
                     over tokens so it stays bounded.  Pairs whose expert
                     is not held add nothing (another chip's share); on
                     one chip there is no exchange.
  * ``shard_map`` -- expert-parallel training path over a mesh:
                     activations are replicated across the ``model`` mesh
                     axis (TP), experts are sharded over it; each shard
                     sort-dispatches tokens to its local experts under a
                     capacity bound (it drops past it) and the partial
                     outputs are ``psum``-combined.

Both are fully differentiable (indices are non-differentiable by
construction).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models.config import ModelConfig, MoEConfig
from repro.models.layers import PIECES, _dense_init, mm, pieces, split_tree

Params = Dict[str, Any]

#: rows of the grouped layer's dispatch buffer per token chunk: a long
#: prefill runs in chunks of DISPATCH_ROWS // (min(k, held) x PIECES)
#: tokens (PIECES rows a pair under bfloat16 experts, else 1)
DISPATCH_ROWS = 16384


def moe_init(key, cfg: ModelConfig):
    mo = cfg.moe
    d, e, f = cfg.d_model, mo.num_experts, mo.d_expert
    h = mo.n_held
    ks = jax.random.split(key, 5)
    tree = {
        "router": _dense_init(ks[0], (d, e), ("embed", None)),
        "wi_gate": _dense_init(ks[1], (h, d, f), ("expert", "embed", "mlp")),
        "wi_up": _dense_init(ks[2], (h, d, f), ("expert", "embed", "mlp")),
        "wo": _dense_init(ks[3], (h, f, d), ("expert", "mlp", "embed")),
    }
    if mo.scoring_func == "sigmoid":
        tree["router_bias"] = (jnp.zeros((e,), jnp.float32), (None,))
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
    """Softmax routing: returns (weights [T,k], idx [T,k], probs [T,E])."""
    logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p.astype(x.dtype), top_i, probs


def _route_sigmoid(x, router_w, bias, mo: MoEConfig):
    """DeepSeek-V3's router (``scoring_func`` sigmoid, ``topk_method``
    noaux_tc), in float32 as its gate computes it: hidden states and the
    router weight are cast to float32 and multiplied at full precision --
    bfloat16 logits would flip near-tie selections against the float32
    published rule.  Selection ranks ``sigmoid(logits) + bias``: a group's
    score is the sum of its 2 best, the best ``topk_group`` of ``n_group``
    groups are kept and the rest excluded, and the top ``k`` of the kept
    experts are chosen.  The weights are the chosen experts' *unbiased*
    scores over their sum, times ``routed_scaling_factor``.  Returns
    (weights f32 [T,k], idx [T,k], scores [T,E])."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    biased = scores + bias.astype(jnp.float32)
    t, e = scores.shape
    g = mo.n_group
    group_score = jax.lax.top_k(biased.reshape(t, g, e // g), 2)[0].sum(-1)
    _, keep = jax.lax.top_k(group_score, mo.topk_group)        # [T, kg]
    kept = jnp.any(keep[:, :, None] == jnp.arange(g)[None, None], axis=1)
    kept = jnp.repeat(kept, e // g, axis=1)                     # [T, E]
    _, idx = jax.lax.top_k(jnp.where(kept, biased, -jnp.inf), mo.top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * mo.routed_scaling_factor, idx, scores


def route(p: Params, mo: MoEConfig, x):
    """The configured router over ``x`` [T, d]: (weights [T,k], expert ids
    [T,k] over all ``num_experts``, probs/scores [T,E])."""
    if mo.scoring_func == "sigmoid":
        return _route_sigmoid(x, p["router"], p["router_bias"], mo)
    if mo.scoring_func != "softmax":
        raise ValueError(f"unknown scoring_func {mo.scoring_func!r}")
    return _route(x, p["router"], mo.top_k)


def _aux_loss(probs, top_i, num_experts: int):
    """Switch-style load-balance loss."""
    me = jnp.mean(probs, axis=0)                        # mean router prob
    ce = jnp.mean(jax.nn.one_hot(top_i[:, 0], num_experts), axis=0)
    return num_experts * jnp.sum(me * ce)


def _shared_out(p, x):
    h = jax.nn.silu(mm(x, p["wi_gate"])) * mm(x, p["wi_up"])
    return mm(h, p["wo"])


# ---------------------------------------------------------------------------
# grouped dropless layer over the held experts
# ---------------------------------------------------------------------------


def _pieces_of(w, x) -> int:
    """Rows a pair takes in the grouped matmul: ``PIECES`` where bfloat16
    experts meet float32 activations (``layers.mm``'s exact products),
    else 1."""
    return PIECES if w.dtype.itemsize < x.dtype.itemsize else 1


def _gmm(x, w, sizes):
    """The grouped matmul of rows ``x`` [R, d] sorted by group, float32
    out.  Under bfloat16 experts each row runs as its bfloat16 pieces on
    adjacent rows of its group (the weights read once), summed after."""
    n = _pieces_of(w, x)
    if n == 1:
        return ops.expert_gmm(x.astype(w.dtype), w, sizes)
    r = x.shape[0]
    xs = jnp.stack(pieces(x, w.dtype, n), axis=1).reshape(r * n, -1)
    return ops.expert_gmm(xs, w, sizes * n).reshape(r, n, -1).sum(axis=1)


def _dispatch_chunk(p: Params, mo: MoEConfig, xc, wc, ic, vc):
    """One token chunk through the held experts.  xc [C,d]; wc/ic [C,k]
    routing weights and global expert ids; vc [C] tokens that count.
    Returns (y [C,d] f32, int32[2]: held pairs, held experts touched)."""
    c, k = ic.shape
    held = mo.n_held
    rows = c * min(k, held)            # the worst case: nothing is dropped
    le = ic - mo.held_start
    mine = (le >= 0) & (le < held) & vc[:, None]                # [C, k]
    key = jnp.where(mine, le, held).reshape(-1)
    order = jnp.argsort(key, stable=True)          # held pairs first
    sizes = jnp.zeros((held,), jnp.int32).at[key].add(1, mode="drop")
    xs = xc[order[:rows] // k]
    g = _gmm(xs, p["wi_gate"], sizes)
    u = _gmm(xs, p["wi_up"], sizes)
    out = _gmm(jax.nn.silu(g) * u, p["wo"], sizes)
    # each held pair reads its row back (a gather, not a scatter); rows
    # past the held pairs hold no pair (the kernel leaves them unwritten):
    # select, never multiply, them away
    slot = jnp.zeros((c * k,), jnp.int32).at[order].set(
        jnp.arange(c * k, dtype=jnp.int32))
    rows_of = out[jnp.minimum(slot, rows - 1)].reshape(c, k, -1)
    y = jnp.sum(jnp.where(mine[..., None],
                          rows_of * wc[..., None].astype(out.dtype), 0.0),
                axis=1)
    counts = jnp.stack([mine.sum(dtype=jnp.int32),
                        (sizes > 0).sum(dtype=jnp.int32)])
    return y, counts


def moe_apply_grouped(p: Params, cfg: ModelConfig, x, valid=None
                      ) -> Tuple[Any, Any, Any]:
    """x: [B,S,d] -> (y, aux_loss, counts int32[2]).  ``valid`` [B,S]
    marks the tokens that count (padding and idle rows route nowhere; they
    get the shared experts only).  ``counts``: token-expert pairs routed to
    held experts, and held experts with at least one pair, summed over
    token chunks."""
    mo = cfg.moe
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    w, idx, probs = route(p, mo, xt)
    vt = (jnp.ones((t,), bool) if valid is None
          else jnp.asarray(valid, bool).reshape(t))
    chunk = max(1, DISPATCH_ROWS // (min(mo.top_k, mo.n_held)
                                     * _pieces_of(p["wi_gate"], xt)))
    if t <= chunk:
        y, counts = _dispatch_chunk(p, mo, xt, w, idx, vt)
    else:
        n = -(-t // chunk)
        pad = n * chunk - t

        def split(a):
            a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
            return a.reshape((n, chunk) + a.shape[1:])

        y, counts = jax.lax.map(
            lambda a: _dispatch_chunk(p, mo, *a),
            (split(xt), split(w), split(idx), split(vt)))
        y = y.reshape(n * chunk, d)[:t]
        counts = counts.sum(axis=0)
    y = y.astype(x.dtype)
    if mo.num_shared:
        y = y + _shared_out(p["shared"], xt).astype(x.dtype)
    aux = (jnp.float32(0.0) if mo.scoring_func == "sigmoid"
           else _aux_loss(probs, idx, mo.num_experts))
    return y.reshape(b, s, d), aux, counts


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


def moe_apply(p: Params, cfg: ModelConfig, x, mesh=None, valid=None):
    """(y, aux_loss, counts int32[2]): the expert-parallel training path
    over a mesh, else the grouped layer (counts of the shard_map path are
    not kept: zeros)."""
    if cfg.moe_impl == "shard_map" and mesh is not None:
        y, aux = moe_apply_shard_map(p, cfg, x, mesh)
        return y, aux, jnp.zeros((2,), jnp.int32)
    return moe_apply_grouped(p, cfg, x, valid)
