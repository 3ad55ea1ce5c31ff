"""Plain float32 reference of a Qwen3 dense decoder (Qwen3ForCausalLM).

Follows the published forward: token embedding (no scaling), per layer
RMSNorm -> q/k/v projections -> per-head RMSNorm of q and k -> rotary
embedding (theta from the configuration, halves rotated) -> causal
grouped-query attention (query head h reads KV head h // (H / KV)) ->
output projection -> residual; RMSNorm -> SwiGLU MLP -> residual; final
RMSNorm and the untied unembedding.  Straight ``jax.numpy``, no cache, no
kernels, no batching, every product at ``Precision.HIGHEST``.  It
imports nothing of the program: its weights come from ``bench.weights``,
drawn by the leaf table of ``bench/arch/qwen3.py``.

``precision="fp8"`` is the control: every matrix product takes float8
(e4m3) operands, each tensor under one scale, as a float8 serving path
would, accumulated in float32.  ``precision="kv_bf16"`` keeps every
product at float32 and rounds the keys and values to bfloat16, as a
bfloat16 page pool would store them.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.arch import qwen3 as Q

__all__ = ["served_logits"]

#: sequences are padded to a multiple of this (bounds the compiles)
PAD = 2048
#: queries per attention block
Q_BLOCK = 512
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _q8(x):
    """Round to float8 under one scale for the whole tensor."""
    s = jnp.max(jnp.abs(x)) / _F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(_F8).astype(jnp.float32) * s


def _mm(spec, a, b, precision):
    """One matrix product, float32 at the highest precision, or with both
    operands first rounded to float8 (the control)."""
    if precision == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("conf_t", "precision"))
def _layer(x, lw, *, conf_t, precision):
    """One decoder layer over one right-padded sequence x [T, d]
    (causality keeps the real rows exact)."""
    conf = dict(conf_t)
    eps, theta = conf["rms_norm_eps"], float(conf["rope_theta"])
    t = x.shape[0]
    h_, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    rep = h_ // kv
    pos = jnp.arange(t)
    h = _rms(x, lw["norm1"], eps)
    q = _mm("td,dhk->thk", h, lw["wq"], precision)
    k = _mm("td,dhk->thk", h, lw["wk"], precision)
    v = _mm("td,dhk->thk", h, lw["wv"], precision)
    q = _rope(_rms(q, lw["q_norm"], eps), pos, theta)
    k = _rope(_rms(k, lw["k_norm"], eps), pos, theta)
    if precision == "kv_bf16":
        k = k.astype(jnp.bfloat16).astype(jnp.float32)
        v = v.astype(jnp.bfloat16).astype(jnp.float32)
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scale = q.shape[-1] ** -0.5

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = _mm("qhk,thk->hqt", qb, k, precision) * scale
        mask = pos[None, None, :] <= qpos[None, :, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return _mm("hqt,thk->qhk", p, v, precision)

    ctx = jax.lax.map(block, jnp.arange(t // Q_BLOCK))
    ctx = ctx.reshape(t, h_, -1)
    x = x + _mm("thk,hkd->td", ctx, lw["wo"], precision)
    h2 = _rms(x, lw["norm2"], eps)
    g = _mm("td,df->tf", h2, lw["w_gate"], precision)
    u = _mm("td,df->tf", h2, lw["w_up"], precision)
    x = x + _mm("tf,fd->td", jax.nn.silu(g) * u, lw["w_down"], precision)
    return x


@functools.partial(jax.jit, static_argnames=("conf_t", "precision"))
def _head(x, gw, *, conf_t, precision):
    conf = dict(conf_t)
    h = _rms(x, gw["final_norm"], conf["rms_norm_eps"])
    return _mm("td,dv->tv", h, gw["unembed"], precision)


@functools.partial(jax.jit, static_argnames=("conf_t",))
def _layer_weights(key, l, *, conf_t):
    return W.layer(key, Q, dict(conf_t), l)


@functools.partial(jax.jit, static_argnames=("conf_t",))
def _global_weights(key, *, conf_t):
    return W.globals_(key, Q, dict(conf_t))


def _conf_t(conf: Dict):
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size", "rms_norm_eps",
            "rope_theta", "num_hidden_layers")
    return tuple((k, conf[k]) for k in keep)


def served_logits(conf: Dict, seed: int, seqs: Sequence[np.ndarray],
                  starts: Sequence[int], precision: str = "f32"
                  ) -> List[np.ndarray]:
    """Reference logits [len(seq) - start, V] at positions start .. end-1
    of each token sequence: the logits that chose the tokens at positions
    start+1 .. end.  Computed layer by layer over every sequence, so one
    layer's weights and the sequences' hidden states are all it holds."""
    ct = _conf_t(conf)
    key = W.key_of(seed)
    gw = _global_weights(key, conf_t=ct)
    xs = []
    for s in seqs:
        t = -(-len(s) // PAD) * PAD
        ids = np.zeros(t, np.int32)
        ids[: len(s)] = s
        xs.append(gw["embed"][jnp.asarray(ids)])
    for l in range(conf["num_hidden_layers"]):
        lw = _layer_weights(key, l, conf_t=ct)
        xs = [_layer(x, lw, conf_t=ct, precision=precision) for x in xs]
        del lw
    return [np.asarray(_head(x, gw, conf_t=ct, precision=precision)
                       [st: len(s)]) for x, s, st in zip(xs, seqs, starts)]
