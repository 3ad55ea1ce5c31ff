"""Plain float32 reference of DeepSeek-V3 (DeepseekV3ForCausalLM) on one
chip's share of each layer.

Follows the published forward: token embedding (no scaling); per layer
RMSNorm -> Multi-head Latent Attention in its published, non-absorbed
form (q through the q_lora bottleneck and its RMSNorm; the compressed
kv latent and its RMSNorm, from which per-head K (nope part) and V are
materialised; one rope key shared by every head; YaRN rotary embedding
on the interleaved pairs, de-interleaved first as ``apply_rotary_pos_emb``
does; causal softmax at 192^-0.5 x mscale(factor, mscale_all_dim)^2) ->
output projection -> residual; RMSNorm -> the dense SwiGLU FFN (the
leading layers) or the expert layer -> residual; final RMSNorm and the
untied unembedding.  The expert layer is the published ``MoEGate``
(sigmoid scores of float32 logits; selection on scores + the correction
bias; groups scored by their 2 best, the best ``topk_group`` kept; top-k
of the kept; weights the unbiased scores over their sum, times
``routed_scaling_factor``) over the router's whole width, then each held
expert's SwiGLU on every token weighted by the token's weight for it (0
where it was not chosen), plus the shared expert.  Straight
``jax.numpy``, no cache, no kernels, no batching, every product at
``Precision.HIGHEST``.  It imports nothing of the program: its weights
come from ``bench.weights``, drawn by the leaf table of
``bench/arch/deepseek_v3.py`` and rounded to bfloat16 as stored there
(``stored``; the token table's float32 values are ``as_float32``'s).

Departures from the published forward, each the configuration's cut:
- the expert share: only the held experts (the file's ``n_routed_experts``
  from ``reduced``'s ``first``) add their part; the others' part would be
  added on the chips that hold them;
- the vocabulary is the file's slice (``vocab_size``), heads the file's
  share, depth the file's layers;
- no multi-token-prediction module (the published model drops it at
  inference unless it drafts for speculation).

``precision="fp8"`` is the control: every matrix product (the router's
too) takes float8 (e4m3) operands, each tensor under one scale, as a
float8 serving path would, accumulated in float32.  ``precision="kv_bf16"``
keeps every product at float32 and rounds the cached latents (the kv
latent and the rope key) to bfloat16, as a bfloat16 page pool would store
them.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.arch import deepseek_v3 as A

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


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_cos_sin(conf, t: int):
    """``DeepseekV3YarnRotaryEmbedding``'s cos/sin tables [t, rope]."""
    rs, dim = conf["rope_scaling"], conf["qk_rope_head_dim"]
    base, factor = float(conf["rope_theta"]), float(rs["factor"])
    orig = rs["original_max_position_embeddings"]

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ar = np.arange(0, dim, 2, dtype=np.float32)
    freq_extra = 1.0 / (base ** (ar / dim))
    freq_inter = 1.0 / (factor * base ** (ar / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv_freq = (freq_inter * (1 - mask) + freq_extra * mask).astype(
        np.float32)
    freqs = np.outer(np.arange(t, dtype=np.float32), inv_freq)
    emb = jnp.asarray(np.concatenate([freqs, freqs], axis=-1))
    m = _mscale(factor, rs["mscale"]) / _mscale(factor, rs["mscale_all_dim"])
    # cos and sin of the float32 angles on the device, as the published
    # module takes them on its own; a host library's would differ in the
    # last bits at angles of thousands of radians
    return jnp.cos(emb) * m, jnp.sin(emb) * m


def _rope(x, cos, sin):
    """The published ``apply_rotary_pos_emb`` on x [t, ..., rope] with
    interleaved pairs: de-interleave, then rotate halves."""
    t, d = x.shape[0], x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (d,))
    c = cos.reshape((t,) + (1,) * (x.ndim - 2) + (d,))
    s = sin.reshape((t,) + (1,) * (x.ndim - 2) + (d,))
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * c + rot * s


def _gate(h, lw, conf, precision):
    """``MoEGate``: (weights [t, k], expert ids [t, k])."""
    logits = _mm("td,de->te", h, lw["router"], precision)
    scores = jax.nn.sigmoid(logits)
    biased = scores + lw["router_bias"]
    t, e = scores.shape
    g, kg, k = conf["n_group"], conf["topk_group"], \
        conf["num_experts_per_tok"]
    group_scores = jax.lax.top_k(biased.reshape(t, g, e // g), 2)[0].sum(-1)
    gidx = jax.lax.top_k(group_scores, kg)[1]
    gmask = jnp.zeros((t, g)).at[jnp.arange(t)[:, None], gidx].set(1.0)
    smask = jnp.repeat(gmask, e // g, axis=1)
    idx = jax.lax.top_k(jnp.where(smask > 0, biased, -jnp.inf), k)[1]
    w = jnp.take_along_axis(scores, idx, axis=1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * conf["routed_scaling_factor"], idx


def _swiglu(h, wg, wu, wd, precision):
    return _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", h, wg, precision))
               * _mm("td,df->tf", h, wu, precision), wd, precision)


@functools.partial(jax.jit, static_argnames=("conf_t", "moe", "precision"))
def _layer(x, lw, cos, sin, *, conf_t, moe, precision):
    """One decoder layer over one right-padded sequence x [T, d]
    (causality keeps the real rows exact)."""
    conf = dict(conf_t)
    eps = conf["rms_norm_eps"]
    t = x.shape[0]
    nope = conf["qk_nope_head_dim"]
    pos = jnp.arange(t)
    h = _rms(x, lw["norm1"], eps)
    cq = _rms(_mm("td,dr->tr", h, lw["w_dq"], precision), lw["q_norm"], eps)
    q = _mm("tr,rhk->thk", cq, lw["w_uq"], precision)
    c = _rms(_mm("td,dr->tr", h, lw["w_dkv"], precision), lw["kv_norm"],
             eps)
    k_pe = _rope(_mm("td,dk->tk", h, lw["w_kr"], precision), cos, sin)
    if precision == "kv_bf16":
        c = c.astype(jnp.bfloat16).astype(jnp.float32)
        k_pe = k_pe.astype(jnp.bfloat16).astype(jnp.float32)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], -1)
    k_nope = _mm("tr,rhk->thk", c, lw["w_uk"], precision)
    v = _mm("tr,rhk->thk", c, lw["w_uv"], precision)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None],
                                  k_nope.shape[:2] + k_pe.shape[-1:])], -1)
    rs = conf["rope_scaling"]
    scale = q.shape[-1] ** -0.5 * _mscale(rs["factor"],
                                          rs["mscale_all_dim"]) ** 2

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = _mm("qhk,thk->hqt", qb, k, precision) * scale
        mask = pos[None, None, :] <= qpos[None, :, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return _mm("hqt,thk->qhk", p, v, precision)

    ctx = jax.lax.map(block, jnp.arange(t // Q_BLOCK)).reshape(
        t, q.shape[1], -1)
    x = x + _mm("thk,hkd->td", ctx, lw["wo"], precision)
    h2 = _rms(x, lw["norm2"], eps)
    if not moe:
        return x + _swiglu(h2, lw["w_gate"], lw["w_up"], lw["w_down"],
                           precision)
    w, idx = _gate(h2, lw, conf, precision)
    first = conf["first_expert"]
    y = _swiglu(h2, lw["s_gate"], lw["s_up"], lw["s_down"], precision)
    for j in range(lw["e_gate"].shape[0]):
        wj = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1)
        y = y + wj[:, None] * _swiglu(h2, lw["e_gate"][j], lw["e_up"][j],
                                      lw["e_down"][j], precision)
    return x + y


@functools.partial(jax.jit, static_argnames=("conf_t", "precision"))
def _head(x, gw, *, conf_t, precision):
    conf = dict(conf_t)
    h = _rms(x, gw["final_norm"], conf["rms_norm_eps"])
    return _mm("td,dv->tv", h, gw["unembed"], precision)


def _as_stored(leaves):
    """The values the bfloat16 checkpoint holds, in float32."""
    return {n: A.as_float32(n, A.stored(n, x)) for n, x in leaves.items()}


@functools.partial(jax.jit, static_argnames=("conf_t", "names"))
def _layer_weights(key, l, *, conf_t, names):
    return _as_stored(A.layer_leaves(key, dict(conf_t), l, names))


@functools.partial(jax.jit, static_argnames=("conf_t",))
def _global_weights(key, *, conf_t):
    return _as_stored(W.globals_(key, A, dict(conf_t)))


def _conf_t(conf: Dict):
    keep = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "n_shared_experts", "n_routed_experts", "num_experts_per_tok",
            "n_group", "topk_group", "routed_scaling_factor", "vocab_size",
            "rms_norm_eps", "rope_theta", "num_hidden_layers",
            "first_k_dense_replace")
    _, first, _ = A.experts(conf)
    return tuple([(k, conf[k]) for k in keep]
                 + [("reduced", _Frozen(conf["reduced"])),
                    ("rope_scaling", _Frozen(conf["rope_scaling"])),
                    ("first_expert", first)])


class _Frozen(dict):
    """A hashable nested group of the configuration (a static argument)."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


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
    xs, tabs = [], {}
    for s in seqs:
        t = -(-len(s) // PAD) * PAD
        ids = np.zeros(t, np.int32)
        ids[: len(s)] = s
        xs.append(gw["embed"][jnp.asarray(ids)])
        if t not in tabs:
            tabs[t] = _yarn_cos_sin(conf, t)
    dense = conf["first_k_dense_replace"]
    for l in range(conf["num_hidden_layers"]):
        moe = l >= dense
        lw = _layer_weights(key, l, conf_t=ct,
                            names=A.ATTN + (A.MOE if moe else A.DENSE))
        xs = [_layer(x, lw, *tabs[x.shape[0]], conf_t=ct, moe=moe,
                     precision=precision) for x in xs]
        del lw
    return [np.asarray(_head(x, gw, conf_t=ct, precision=precision)
                       [st: len(s)]) for x, s, st in zip(xs, seqs, starts)]
