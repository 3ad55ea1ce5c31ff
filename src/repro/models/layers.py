"""Core layers: norms, rotary, MLPs, GQA / local / cross attention, MLA.

Every ``*_init`` returns ``(params, specs)`` where ``specs`` mirrors the
param tree with tuples of *logical* axis names (resolved to mesh axes by
``repro.distributed.sharding``).  Every ``*_apply`` is a pure function.

The attention reference implementation chunks over queries (``lax.map``)
so the score matrix never materialises at [S, S] -- the memory profile the
dry-run reports is the deployable one.  The Pallas flash kernel
(``repro.kernels.flash_attention``) is the TPU fast path; numerics match.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import (MLAConfig, ModelConfig, RopeScaling,
                                 yarn_mscale)

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _dense_init(key, shape, axes, scale=None, dtype=jnp.float32):
    """He-style init; returns (param, spec)."""
    fan_in = shape[0] if len(shape) > 1 else 1
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, dtype) * scale, axes)


def split_tree(tree):
    """Split a tree of (param, spec) leaves into (params, specs)."""
    is_leaf = lambda x: isinstance(x, tuple) and len(x) == 2 and hasattr(
        x[0], "shape")
    params = jax.tree.map(lambda x: x[0], tree, is_leaf=is_leaf)
    specs = jax.tree.map(lambda x: x[1], tree, is_leaf=is_leaf)
    return params, specs


#: bfloat16 pieces an activation is split into for a product with
#: bfloat16 weights: 3 x 8 significand bits hold a float32's 24
PIECES = 3
#: activation elements up to which those pieces run as one stacked
#: product (the weights read once, as decode needs); larger inputs (a
#: long prefill) run one product per piece, with no stacked copy
STACK_ELEMS = 1 << 22


def pieces(x, dt, n: int = PIECES):
    """``n`` arrays of dtype ``dt`` whose float32 sum is ``x`` to n x 8
    significand bits: each is what the ones before it left, rounded."""
    out, r = [], x.astype(jnp.float32)
    for _ in range(n):
        p = r.astype(dt)
        out.append(p)
        r = r - p.astype(jnp.float32)
    return out


def mm(x, w, spec: Optional[str] = None):
    """``x @ w`` (or the einsum ``spec``) with the weights as stored.
    Weights at least as wide as the activations are cast to them, as
    every layer always did.  Narrower ones (bfloat16 storage under a
    float32 residual stream) are never widened (no float32 copy of the
    weights): the activations are split into bfloat16 ``pieces``, each
    product is exact and accumulates in float32, so the result is the
    float32 product -- a single bfloat16 pass (a TPU's default precision)
    would round every activation to 8 bits, enough to flip a router's
    near-tie choice of expert."""
    if w.dtype.itemsize >= x.dtype.itemsize:
        w = w.astype(x.dtype)
        return x @ w if spec is None else jnp.einsum(spec, x, w)
    if spec is None:
        spec = "...k,kn->...n"
    parts = pieces(x, w.dtype)
    if x.size <= STACK_ELEMS:
        lhs, rest = spec.split(",", 1)
        z = next(c for c in "zyxwvu" if c not in spec)
        out = jnp.einsum(f"{z}{lhs},{rest.replace('->', f'->{z}')}",
                         jnp.stack(parts), w,
                         preferred_element_type=jnp.float32)
        return out.sum(axis=0)
    # smallest first
    return sum(jnp.einsum(spec, p, w, preferred_element_type=jnp.float32)
               for p in parts[::-1])


# ---------------------------------------------------------------------------
# norms / rotary
# ---------------------------------------------------------------------------


def rms_norm_init(d):
    return (jnp.ones((d,), jnp.float32), ("embed",))


def rms_norm(x, w, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w).astype(dt)


def rope_inv_freq(d: int, theta: float,
                  scaling: Optional[RopeScaling] = None):
    """Inverse frequencies of dimension pairs (i, i + d/2).  With a YaRN
    ``scaling`` they are ``DeepseekV3YarnRotaryEmbedding``'s, computed as
    it computes them (float32, on the host): a linear ramp over the
    correction range [low, high] that beta_fast and beta_slow rotations at
    the original context give, from extrapolated (theta^(-2i/d)) below it
    to interpolated (/ factor) above it."""
    half = d // 2
    if scaling is None:
        return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)

    def corr_dim(rot):
        return (d * math.log(scaling.original_max_position_embeddings
                             / (rot * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(corr_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(corr_dim(scaling.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    base = float(theta) ** (np.arange(0, d, 2, dtype=np.float32) / d)
    extra = 1.0 / base
    inter = 1.0 / (float(scaling.factor) * base)
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0, 1)
    keep = 1.0 - ramp               # 1: keep the extrapolated frequency
    return jnp.asarray((inter * (1 - keep) + extra * keep).astype(np.float32))


def rope(x, positions, theta: float, scaling: Optional[RopeScaling] = None):
    """Apply rotary embedding to pairs (i, i + D/2).  x: [..., S, H, D],
    positions: [..., S].  ``scaling``: a YaRN block (``rope_inv_freq``;
    cos and sin then carry mscale(factor, mscale) / mscale(factor,
    mscale_all_dim), which is 1 where the two are equal)."""
    d = x.shape[-1]
    half = d // 2
    freq = rope_inv_freq(d, theta, scaling)
    ang = positions[..., None].astype(jnp.float32) * freq  # [..., S, half]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            sin, cos = sin * m, cos * m
    sin = sin[..., None, :]  # broadcast over heads
    cos = cos[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_kind == "swiglu":
        tree = {
            "wi_gate": _dense_init(ks[0], (d, ff), ("embed", "mlp")),
            "wi_up": _dense_init(ks[1], (d, ff), ("embed", "mlp")),
            "wo": _dense_init(ks[2], (ff, d), ("mlp", "embed")),
        }
    else:  # squared_relu | gelu
        tree = {
            "wi": _dense_init(ks[0], (d, ff), ("embed", "mlp")),
            "wo": _dense_init(ks[1], (ff, d), ("mlp", "embed")),
        }
    return split_tree(tree)


def mlp_apply(p: Params, cfg: ModelConfig, x):
    if cfg.mlp_kind == "swiglu":
        h = jax.nn.silu(mm(x, p["wi_gate"])) * mm(x, p["wi_up"])
        return mm(h, p["wo"])
    # NB: weights must be cast to the activation dtype -- bf16 @ f32
    # silently promotes the whole residual stream to f32 (2x activation
    # memory + 2x collective volume; EXPERIMENTS.md SPerf iteration 5).
    h = x @ p["wi"].astype(x.dtype)
    if cfg.mlp_kind == "squared_relu":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    return h @ p["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# attention masks
# ---------------------------------------------------------------------------


def causal_mask(q_pos, k_pos, window: int = 0, prefix_len: int = 0):
    """Boolean [.., Q, K] mask.  window>0 -> sliding window; prefix_len>0 ->
    bidirectional prefix (PaliGemma image tokens)."""
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window > 0:
        m &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    if prefix_len > 0:
        m |= (k_pos[..., None, :] < prefix_len)
    return m


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def attention_init(key, cfg: ModelConfig, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kd = cfg.cond_dim or d if cross else d
    ks = jax.random.split(key, 6)
    tree = {
        "wq": _dense_init(ks[0], (d, h, hd), ("embed", "heads", "head_dim")),
        "wk": _dense_init(ks[1], (kd, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": _dense_init(ks[2], (kd, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": _dense_init(ks[3], (h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        tree["q_norm"] = (jnp.ones((hd,), jnp.float32), ("head_dim",))
        tree["k_norm"] = (jnp.ones((hd,), jnp.float32), ("head_dim",))
    return split_tree(tree)


def _sdpa_chunked(q, k, v, mask, softcap: float, q_chunk: int = 512,
                  scale=None, precision=None):
    """Softmax attention, chunked over queries.  q: [B,S,H,D], k/v:
    [B,T,KV,D], mask: [B,S,T] or [S,T] broadcastable bool; ``scale``
    defaults to 1/sqrt(D); ``precision`` of both products."""
    b, s, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    kr = jnp.repeat(k, rep, axis=2)  # [B,T,H,D]
    vr = jnp.repeat(v, rep, axis=2)

    n_chunks = max(1, s // q_chunk) if s % q_chunk == 0 else 1
    if s % q_chunk != 0 or s <= q_chunk:
        n_chunks, q_chunk_eff = 1, s
    else:
        q_chunk_eff = q_chunk

    def one_chunk(args):
        qc, mc = args  # [B,C,H,D], [B,C,T]
        logits = jnp.einsum("bchd,bthd->bhct", qc, kr,
                            preferred_element_type=jnp.float32,
                            precision=precision) * scale
        if softcap > 0:
            logits = jnp.tanh(logits / softcap) * softcap
        logits = jnp.where(mc[:, None], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1).astype(qc.dtype)
        return jnp.einsum("bhct,bthd->bchd", w, vr, precision=precision)

    if n_chunks == 1:
        m = jnp.broadcast_to(mask, (b, s, t))
        return one_chunk((q, m))
    qs = q.reshape(b, n_chunks, q_chunk_eff, h, d).transpose(1, 0, 2, 3, 4)
    ms = jnp.broadcast_to(mask, (b, s, t)).reshape(
        b, n_chunks, q_chunk_eff, t).transpose(1, 0, 2, 3)
    out = jax.lax.map(one_chunk, (qs, ms))
    # NB: output head dim is v's, not q's -- MLA has d_v != d_qk.
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, v.shape[-1])


def attention_apply(p: Params, cfg: ModelConfig, x, kv_x, positions, mask,
                    *, kv_positions=None, use_rope=True, past=None):
    """Full attention (training/prefill).  Returns (out, (k, v)).

    ``past`` -- optional ``(past_k, past_v)`` of already-processed prefix
    tokens (post-qk-norm, post-rope: exactly the cache entries a previous
    chunk returned), each [B, P, KV, D].  The chunk attends over
    ``past ++ own`` keys; ``mask`` must then cover [.., S, P+S] (build it
    from the concatenated key positions).  The returned cache entries are
    the OWN chunk's only -- the caller threads the accumulation."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", kv_x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", kv_x, p["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if use_rope:
        kv_pos = positions if kv_positions is None else kv_positions
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, kv_pos, cfg.rope_theta)
    k_all, v_all = k, v
    if past is not None:
        past_k, past_v = past
        k_all = jnp.concatenate([past_k.astype(k.dtype), k], axis=1)
        v_all = jnp.concatenate([past_v.astype(v.dtype), v], axis=1)
    out = _sdpa_chunked(q, k_all, v_all, mask, cfg.softcap)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, (k, v)


def attention_decode(p: Params, cfg: ModelConfig, x, cache_k, cache_v,
                     cache_pos, cur_pos, *, window: int = 0):
    """One-token decode.  x: [B,1,d]; cache_k/v: [B,T,KV,D]; cache_pos:
    [B,T] absolute positions (-1 == empty); cur_pos: [B] int32.
    Returns (out, new_k_entry, new_v_entry)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k_new = rms_norm(k_new, p["k_norm"])
    q = rope(q, cur_pos[:, None], cfg.rope_theta)
    k_new = rope(k_new, cur_pos[:, None], cfg.rope_theta)

    # attend over cache plus the new entry
    k_all = jnp.concatenate([cache_k, k_new], axis=1).astype(x.dtype)
    v_all = jnp.concatenate([cache_v, v_new], axis=1).astype(x.dtype)
    pos_all = jnp.concatenate([cache_pos, cur_pos[:, None]], axis=1)
    valid = pos_all >= 0
    m = (pos_all <= cur_pos[:, None]) & valid
    if window > 0:
        m &= pos_all > (cur_pos[:, None] - window)
    out = _sdpa_chunked(q, k_all, v_all, m[:, None, :], cfg.softcap)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, k_new, v_new


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 Multi-head Latent Attention)
# ---------------------------------------------------------------------------


def mla_init(key, cfg: ModelConfig):
    d, h = cfg.d_model, cfg.num_heads
    m: MLAConfig = cfg.mla
    ks = jax.random.split(key, 8)
    qk = m.qk_nope_dim
    tree = {
        "w_dq": _dense_init(ks[0], (d, m.q_lora_rank), ("embed", "q_lora")),
        "q_norm": (jnp.ones((m.q_lora_rank,), jnp.float32), ("q_lora",)),
        "w_uq": _dense_init(ks[1], (m.q_lora_rank, h, qk + m.qk_rope_dim),
                            ("q_lora", "heads", "head_dim")),
        "w_dkv": _dense_init(ks[2], (d, m.kv_lora_rank), ("embed", "kv_lora")),
        "kv_norm": (jnp.ones((m.kv_lora_rank,), jnp.float32), ("kv_lora",)),
        "w_kr": _dense_init(ks[3], (d, m.qk_rope_dim), ("embed", None)),
        "w_uk": _dense_init(ks[4], (m.kv_lora_rank, h, qk),
                            ("kv_lora", "heads", "head_dim")),
        "w_uv": _dense_init(ks[5], (m.kv_lora_rank, h, m.v_head_dim),
                            ("kv_lora", "heads", "head_dim")),
        "wo": _dense_init(ks[6], (h, m.v_head_dim, d),
                          ("heads", "head_dim", "embed")),
    }
    return split_tree(tree)


#: precision of MLA's attention products (float32 queries against float32
#: latents): a TPU's default single bfloat16 pass would round both to 8
#: bits, as ``mm`` avoids for the projections
MLA_PRECISION = jax.lax.Precision.HIGHEST


def mla_project(p: Params, cfg: ModelConfig, x, positions):
    """The projections every MLA path shares: (q_nope [B,S,H,nope], q_rope
    [B,S,H,rope] roped, c_kv [B,S,kv_lora] normed, k_rope [B,S,rope] roped,
    one key shared by every head).  Rope pairs dimensions (i, i + rope/2);
    a checkpoint that pairs (2i, 2i+1) permutes its rope columns."""
    m: MLAConfig = cfg.mla
    cq = rms_norm(mm(x, p["w_dq"]), p["q_norm"])
    q = mm(cq, p["w_uq"], "bsr,rhk->bshk")
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    c_kv = rms_norm(mm(x, p["w_dkv"]), p["kv_norm"])
    k_rope = rope(mm(x, p["w_kr"])[:, :, None, :], positions,
                  cfg.rope_theta, cfg.rope_scaling)[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def mla_apply(p: Params, cfg: ModelConfig, x, positions, mask, *, past=None):
    """Training/prefill MLA: materialise per-head K/V.  Returns
    (out, (c_kv, k_rope)) -- the *compressed* cache entries.

    ``past`` -- optional ``(past_ckv, past_krope)`` compressed cache rows
    of a previous chunk ([B, P, kv_lora], [B, P, rope]); the chunk attends
    over ``past ++ own`` (``mask``: [.., S, P+S]) and still returns only
    its OWN chunk's cache entries."""
    m: MLAConfig = cfg.mla
    q_nope, q_rope, c_kv, k_rope = mla_project(p, cfg, x, positions)
    c_all, kr_all = c_kv, k_rope
    if past is not None:
        past_ckv, past_krope = past
        c_all = jnp.concatenate([past_ckv.astype(c_kv.dtype), c_kv], axis=1)
        kr_all = jnp.concatenate([past_krope.astype(c_kv.dtype), kr_all],
                                 axis=1)
    k_nope = mm(c_all, p["w_uk"], "bsr,rhk->bshk")
    v = mm(c_all, p["w_uv"], "bsr,rhk->bshk")

    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_all[:, :, None, :].astype(k_nope.dtype),
                                  k_nope.shape[:3] + (m.qk_rope_dim,))],
        axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope.astype(q_nope.dtype)], axis=-1)
    out = _sdpa_chunked(q_full, k, v, mask, cfg.softcap, scale=cfg.mla_scale,
                        precision=MLA_PRECISION)
    out = mm(out, p["wo"], "bshk,hkd->bsd")
    return out, (c_kv, k_rope)


def mla_decode(p: Params, cfg: ModelConfig, x, cache_ckv, cache_krope,
               cache_pos, cur_pos):
    """Absorbed-matrix MLA decode over the compressed cache.
    cache_ckv: [B,T,kv_lora]; cache_krope: [B,T,rope]."""
    q_nope, q_rope, c_new, kr_new = mla_project(p, cfg, x, cur_pos[:, None])
    ckv = jnp.concatenate([cache_ckv, c_new], axis=1).astype(c_new.dtype)
    krope = jnp.concatenate([cache_krope, kr_new], axis=1).astype(c_new.dtype)
    pos_all = jnp.concatenate([cache_pos, cur_pos[:, None]], axis=1)

    # absorb W_uk into q:  q_abs[b,h,r] = sum_k q_nope[b,h,k] W_uk[r,h,k]
    q_abs = mm(q_nope, p["w_uk"], "bshk,rhk->bshr")
    logits = (jnp.einsum("bshr,btr->bhst", q_abs, ckv,
                         precision=MLA_PRECISION)
              + jnp.einsum("bshk,btk->bhst", q_rope.astype(krope.dtype),
                           krope, precision=MLA_PRECISION))
    # multiply by the precomputed scale (not divide by sqrt) so the paged
    # MLA kernel, which takes `scale` as a static operand, stays
    # bit-identical to this dense path
    logits = logits.astype(jnp.float32) * cfg.mla_scale
    mask = (pos_all <= cur_pos[:, None]) & (pos_all >= 0)
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(ckv.dtype)
    ctx = jnp.einsum("bhst,btr->bshr", w, ckv, precision=MLA_PRECISION)
    out = mm(ctx, p["w_uv"], "bshr,rhk->bshk")
    out = mm(out, p["wo"], "bshk,hkd->bsd")
    return out, c_new, kr_new


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------


def embedding_init(key, cfg: ModelConfig):
    # scale 1/sqrt(d): embed() re-scales by sqrt(d) (unit-std activations)
    # and the tied unembedding then produces unit-scale logits at init.
    tree = {"tok": _dense_init(key, (cfg.vocab_size, cfg.d_model),
                               ("vocab", "embed"),
                               scale=1.0 / np.sqrt(cfg.d_model))}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        tree["unembed"] = _dense_init(k2, (cfg.d_model, cfg.vocab_size),
                                      ("embed", "vocab"))
    return split_tree(tree)


def embed(p: Params, cfg: ModelConfig, tokens):
    e = p["tok"].astype(jnp.dtype(cfg.dtype))[tokens]
    # NB: np.sqrt returns a strong np.float64 scalar which promotes the
    # residual stream to f32.  A weak python float keeps it bf16 -- correct
    # for TPU -- but the CPU SPMD partitioner regresses badly on the bf16
    # graph (nemotron train temp 74->93 GB, bytes 31->127 TB), so the CPU
    # dry-run keeps the f32 stream and documents the ~2x activation-traffic
    # headroom (EXPERIMENTS.md SPerf iteration 5: confirmed root cause,
    # fix deferred to the TPU target via RESID_DTYPE).
    scale = (float(np.sqrt(cfg.d_model)) if RESID_WEAK_SCALE
             else np.sqrt(cfg.d_model))
    return e * scale


# Toggle for the TPU deployment: weak-typed scale => bf16 residual stream.
RESID_WEAK_SCALE = False


def unembed(p: Params, cfg: ModelConfig, x):
    return mm(x, p["tok"].T if cfg.tie_embeddings else p["unembed"])
