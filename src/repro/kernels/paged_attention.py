"""Pallas TPU kernel: decode attention over a paged KV working set.

The TPU-native consumer of the Cori-tuned tiering runtime: KV lives in
fixed-size pages; a per-sequence page table indirects into the physical
page pool (the HBM working set managed by ``repro.memtier``).  The page
table is a *scalar-prefetch* operand -- its values drive the BlockSpec
index_map, so each grid step DMAs exactly the physical page it needs
(hardware page-gather; no materialised gather HLO).

Grid: (batch, pages_per_seq); online softmax carries (m, l, acc) in VMEM
scratch across the page axis, exactly like flash attention but with the kv
tile = one page and block indices taken from the page table.

Since the fully-paged decode refactor, *every* attention layer of the
serving engine reads its KV through this kernel, so it supports the whole
layer mix, not just the monitor layer:

  * ``window > 0`` -- sliding-window (local) layers: only positions in
    ``[length - window, length)`` are attended.  Callers still pass the
    full page table; out-of-window pages are masked, not skipped, so one
    table layout serves every layer of a multi-layer pool.
  * ``softcap > 0`` -- tanh logit capping (Gemma-style), applied before
    masking exactly as in the dense layers.

Multi-request tables are ragged: rows shorter than ``pages_per_seq`` are
padded with ``-1`` (bucket-rounded allocations leave tail pages unused).
The jitted wrapper (``repro.kernels.ops.paged_attention``) clamps those to
0 -- they are masked by ``lengths`` -- so the index_map never DMAs out of
bounds.

Besides the context the kernel emits the **per-page attention mass** as a
second output: f32[B, pages_per_seq], head-normalised (each in-length row
sums to ~1).  This is the "accessed bits" signal the Cori-tuned tiering
runtime consumes -- emitting it from the online-softmax accumulators makes
telemetry free (one extra [H, pages] VMEM scratch, no second pass over the
KV pages).  Per page the kernel keeps the running exp-sum under the SAME
max/correction cascade as the context accumulator, so at the flush step
``mass[pi] = sum_h p_scr[h, pi] / l[h] / H`` equals the softmax
probability mass the reference oracle assigns to page ``pi``.

q: [B, H, D]; k_pages/v_pages: [P_phys, page, KV, D];
page_table: int32[B, pages_per_seq]; lengths: int32[B].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(page_table, lengths, q_ref, k_ref, v_ref, o_ref, mass_ref,
            m_scr, l_scr, acc_scr, p_scr, *, page: int, n_pages: int,
            scale: float, window: int, softcap: float):
    b = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        p_scr[...] = jnp.zeros_like(p_scr)

    q = q_ref[0]                                   # [H, D]
    k = k_ref[0]                                   # [page, KV, D]
    v = v_ref[0]
    h, d = q.shape
    kvh = k.shape[1]
    rep = h // kvh
    length = lengths[b]

    # token positions covered by this logical page
    pos = pi * page + jax.lax.iota(jnp.int32, page)
    valid = pos < length                           # [page]
    if window > 0:
        # sliding-window layer: the decoding token sits at length - 1, so
        # the attended span is [length - window, length)
        valid &= pos >= length - window

    qg = q.reshape(kvh, rep, d)
    logits = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32) * scale   # [kvh, rep, page]
    if softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap
    logits = jnp.where(valid[None, None, :], logits, NEG_INF)

    m_prev = m_scr[...]                            # [kvh, rep, 1]... flat [h,1]
    lg = logits.reshape(h, page)
    m_cur = jnp.max(lg, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(lg - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    pg = p.reshape(kvh, rep, page)
    ctx = jax.lax.dot_general(
        pg.astype(v.dtype), v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)        # [kvh, rep, d]
    acc_scr[...] = acc_scr[...] * corr + ctx.reshape(h, d)
    # per-page exp-sum under the same correction cascade as the context
    # accumulator: column pi gets this page's sum, prior columns re-scale
    page_col = (jax.lax.iota(jnp.int32, n_pages) == pi).astype(jnp.float32)
    p_scr[...] = p_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True) \
        * page_col[None, :]
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(pi == n_pages - 1)
    def _flush():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        mass_ref[0] = jnp.sum(p_scr[...] / l_safe, axis=0, keepdims=True) / h


def paged_attention(q, k_pages, v_pages, page_table, lengths, *,
                    window: int = 0, softcap: float = 0.0,
                    interpret: bool = False):
    """Decode attention over paged KV.

    Returns (out [B, H, D], mass f32[B, pages_per_seq]) -- the per-page
    head-normalised attention mass is emitted from the kernel's own
    softmax accumulators (no second pass over the pages)."""
    b, h, d = q.shape
    p_phys, page, kvh, _ = k_pages.shape
    n_pages = page_table.shape[1]
    assert h % kvh == 0
    scale = 1.0 / np.sqrt(d)

    kernel = functools.partial(_kernel, page=page, n_pages=n_pages,
                               scale=scale, window=window, softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pages),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bi, pi, pt, ln: (bi, 0, 0)),
            pl.BlockSpec((1, page, kvh, d),
                         lambda bi, pi, pt, ln: (pt[bi, pi], 0, 0, 0)),
            pl.BlockSpec((1, page, kvh, d),
                         lambda bi, pi, pt, ln: (pt[bi, pi], 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, d), lambda bi, pi, pt, ln: (bi, 0, 0)),
            # the mass is emitted as [B, 1, n_pages] so the block's last
            # two dims equal the array's: a (1, n_pages) block of a
            # [B, n_pages] array breaks the TPU's (8, 128) tiling rule
            pl.BlockSpec((1, 1, n_pages), lambda bi, pi, pt, ln: (bi, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, n_pages), jnp.float32),
        ],
    )
    out, mass = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, d), q.dtype),
                   jax.ShapeDtypeStruct((b, 1, n_pages), jnp.float32)],
        interpret=interpret,
    )(page_table, lengths, q, k_pages, v_pages)
    return out, mass[:, 0]


def _mla_kernel(page_table, lengths, qa_ref, qr_ref, ckv_ref, kr_ref,
                o_ref, mass_ref, m_scr, l_scr, acc_scr, p_scr, *,
                page: int, n_pages: int, scale: float):
    """Absorbed-matrix MLA decode over compressed pages.

    Same online-softmax + fused per-page mass cascade as ``_kernel``, but
    the page holds one *compressed* row per token -- ckv [page, R] shared
    across every head (not roped) plus krope [page, K] roped positional
    keys -- so the logits are the sum of two head x page dots and the
    "values" are the ckv rows themselves (the caller up-projects with
    W_uv outside the kernel).
    """
    b = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        p_scr[...] = jnp.zeros_like(p_scr)

    qa = qa_ref[0]                                 # [H, R]
    qr = qr_ref[0]                                 # [H, K]
    ckv = ckv_ref[0]                               # [page, R]
    kr = kr_ref[0]                                 # [page, K]
    h = qa.shape[0]
    length = lengths[b]

    pos = pi * page + jax.lax.iota(jnp.int32, page)
    valid = pos < length                           # [page]

    logits = (jax.lax.dot_general(
        qa, ckv, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
        + jax.lax.dot_general(
        qr, kr, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)) * scale   # [H, page]
    logits = jnp.where(valid[None, :], logits, NEG_INF)

    m_prev = m_scr[...]                            # [H, 1]
    m_cur = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    ctx = jax.lax.dot_general(
        p.astype(ckv.dtype), ckv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # [H, R]
    acc_scr[...] = acc_scr[...] * corr + ctx
    page_col = (jax.lax.iota(jnp.int32, n_pages) == pi).astype(jnp.float32)
    p_scr[...] = p_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True) \
        * page_col[None, :]
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(pi == n_pages - 1)
    def _flush():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        mass_ref[0] = jnp.sum(p_scr[...] / l_safe, axis=0, keepdims=True) / h


def paged_attention_mla(q_abs, q_rope, ckv_pages, krope_pages, page_table,
                        lengths, *, scale: float, interpret: bool = False):
    """MLA decode over compressed paged rows.

    q_abs: [B, H, R]; q_rope: [B, H, K]; ckv_pages: [P_phys, page, R];
    krope_pages: [P_phys, page, K].  ``scale`` is 1/sqrt(qk_nope + qk_rope)
    (the uncompressed head dim, not derivable from compressed shapes).
    Returns (ctx [B, H, R] in the compressed space, mass f32[B, n_pages])."""
    b, h, rdim = q_abs.shape
    kdim = q_rope.shape[2]
    _, page, _ = ckv_pages.shape
    n_pages = page_table.shape[1]

    kernel = functools.partial(_mla_kernel, page=page, n_pages=n_pages,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pages),
        in_specs=[
            pl.BlockSpec((1, h, rdim), lambda bi, pi, pt, ln: (bi, 0, 0)),
            pl.BlockSpec((1, h, kdim), lambda bi, pi, pt, ln: (bi, 0, 0)),
            pl.BlockSpec((1, page, rdim),
                         lambda bi, pi, pt, ln: (pt[bi, pi], 0, 0)),
            pl.BlockSpec((1, page, kdim),
                         lambda bi, pi, pt, ln: (pt[bi, pi], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, rdim), lambda bi, pi, pt, ln: (bi, 0, 0)),
            pl.BlockSpec((1, 1, n_pages), lambda bi, pi, pt, ln: (bi, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, rdim), jnp.float32),
            pltpu.VMEM((h, n_pages), jnp.float32),
        ],
    )
    out, mass = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, rdim), q_abs.dtype),
                   jax.ShapeDtypeStruct((b, 1, n_pages), jnp.float32)],
        interpret=interpret,
    )(page_table, lengths, q_abs, q_rope, ckv_pages, krope_pages)
    return out, mass[:, 0]
