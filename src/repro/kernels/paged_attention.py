"""Pallas TPU kernel: decode attention over a paged KV working set.

The TPU-native consumer of the Cori-tuned tiering runtime: KV lives in
fixed-size pages; a per-sequence page table indirects into the physical
page pool (the HBM working set managed by ``repro.memtier``).  The page
table and the lengths are *scalar-prefetch* operands; the pool stays in
HBM and the kernel copies the pages it needs itself (a page-gather by
DMA; no materialised gather HLO).

Grid: (batch, blocks of pages).  A block is as many pages of one row as
fit about 1 MB of K+V (8 pages of 64 f32 positions at 2 KV heads of 128).
Only *live* blocks do work: a block past the row's length, or before its
window, issues no copy and no compute, and within a live block only the
pages holding an attended position are copied.  The copies go into a
double-buffered VMEM block, and each live block starts the next live
block's copies (this row's, or the next row's that attends anything)
before it computes, so the walk follows ``lengths``, not the padded
table.  Online softmax carries (m, l, acc) in VMEM scratch across a row's
blocks, exactly like flash attention.

Since the fully-paged decode refactor, *every* attention layer of the
serving engine reads its KV through this kernel, so it supports the whole
layer mix, not just the monitor layer:

  * ``window > 0`` -- sliding-window (local) layers: only positions in
    ``[length - window, length)`` are attended.  Callers still pass the
    full page table, so one table layout serves every layer of a
    multi-layer pool; blocks before the window are skipped.
  * ``softcap > 0`` -- tanh logit capping (Gemma-style), applied before
    masking exactly as in the dense layers.

Multi-request tables are ragged: rows shorter than ``pages_per_seq`` are
padded with ``-1`` (bucket-rounded allocations leave tail pages unused).
The jitted wrapper (``repro.kernels.ops.paged_attention``) clamps those to
0 -- they lie past ``lengths`` -- so no copy reads out of bounds.  A row of
length 0 attends nothing: its context and mass are zeros.

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

The MLA kernel below still walks one page of the padded table per grid
step, through the BlockSpec index_map.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# K+V bytes one grid step gathers: a block that size amortises the cost of
# a grid step and of a copy's latency, and two of them (this block and the
# next one in flight) stay well inside the scoped VMEM.  On a TPU v5e, at
# 8 rows of 68 pages of 64 f32 positions, half the size took about 59 us
# a call against 48-51, and twice the size 50.
_BLOCK_BYTES = 1 << 20


def _kernel(page_table, lengths, q_ref, k_hbm, v_hbm, o_ref, mass_ref,
            k_buf, v_buf, sems, flow, m_scr, l_scr, acc_scr, p_scr, *,
            page: int, ppb: int, n_pages: int, scale: float, window: int,
            softcap: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_rows = pl.num_programs(0)
    n_blocks = pl.num_programs(1)
    p_phys, _, kvh, d = k_hbm.shape
    bk = ppb * page                    # positions per block
    width = page * kvh                 # buffer rows per page: (token, kv head)
    k_rows = k_hbm.reshape(p_phys, width, d)
    v_rows = v_hbm.reshape(p_phys, width, d)

    def block_live(row, blk):
        n = lengths[row]
        live = (blk < n_blocks) & (blk * bk < n)
        if window > 0:
            live &= (blk + 1) * bk > n - window
        return live

    def first_block(row):
        if window > 0:
            return jnp.maximum(lengths[row] - window, 0) // bk
        return 0

    def page_copies(row, blk, slot):
        """(live, k copy, v copy) per page of the block: a page is copied
        only if it holds an attended position."""
        n = lengths[row]
        out = []
        for i in range(ppb):
            col = blk * ppb + i
            live = (col < n_pages) & (col * page < n)
            if window > 0:
                live &= (col + 1) * page > n - window
            phys = page_table[row, jnp.minimum(col, n_pages - 1)]
            dst = pl.ds(i * width, width)
            out.append((live,
                        pltpu.make_async_copy(k_rows.at[phys],
                                              k_buf.at[slot, dst],
                                              sems.at[0, slot]),
                        pltpu.make_async_copy(v_rows.at[phys],
                                              v_buf.at[slot, dst],
                                              sems.at[1, slot])))
        return out

    def start(row, blk, slot):
        for live, kc, vc in page_copies(row, blk, slot):
            @pl.when(live)
            def _():
                kc.start()
                vc.start()

    def wait(copies, which):
        for live, *kv in copies:
            @pl.when(live)
            def _():
                kv[which].wait()

    @pl.when((b == 0) & (j == 0))
    def _init_call():
        # pages of a block that are not copied keep what the buffer held:
        # zeros or an earlier real page, never non-finite garbage that a
        # zero probability could not cancel in p @ v
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        flow[0] = 0          # buffer slot of the current block
        flow[1] = 0          # 1: the current block's copies are in flight

    @pl.when(j == 0)
    def _init_row():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        p_scr[...] = jnp.zeros_like(p_scr)

    @pl.when(block_live(b, j))
    def _block():
        slot = flow[0]

        @pl.when(flow[1] == 0)
        def _():
            start(b, j, slot)

        # start the next live block in grid order into the other slot:
        # this row's next block, else the first block of the next row
        # that attends anything
        flow[0] = 1 - slot
        same = block_live(b, j + 1)
        flow[1] = same.astype(jnp.int32)

        @pl.when(same)
        def _():
            start(b, j + 1, 1 - slot)

        @pl.when(jnp.logical_not(same))
        def _():
            def later_row(r, cand):
                row = n_rows - 1 - r
                ok = (row > b) & block_live(row, first_block(row))
                return jnp.where(ok, row, cand)

            row = jax.lax.fori_loop(0, n_rows, later_row, n_rows)

            @pl.when(row < n_rows)
            def _():
                start(row, first_block(row), 1 - slot)
                flow[1] = 1

        q = q_ref[0]                               # [H, D]
        h = q.shape[0]
        rep = h // kvh
        length = lengths[b]
        copies = page_copies(b, j, slot)

        # column c of the block holds position j * bk + c // kvh of kv
        # head c % kvh; a query head attends its own kv head's columns
        shape = (h, bk * kvh)
        c = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        valid = (c % kvh == jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                 // rep) & (c < (length - j * bk) * kvh)
        if window > 0:
            # sliding-window layer: the decoding token sits at length - 1,
            # so the attended span is [length - window, length)
            valid &= c >= (length - window - j * bk) * kvh

        wait(copies, 0)
        lg = jax.lax.dot_general(
            q, k_buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [H, bk * kvh]
        if softcap > 0:
            lg = jnp.tanh(lg / softcap) * softcap
        lg = jnp.where(valid, lg, NEG_INF)

        m_prev = m_scr[...]                        # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(lg, axis=1, keepdims=True))
        p = jnp.exp(lg - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new

        wait(copies, 1)
        v = v_buf[slot]
        ctx = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [H, D]
        acc_scr[...] = acc_scr[...] * corr + ctx

        # per-page exp-sums under the same correction cascade as the
        # context accumulator: column j * ppb + i gets page i's sum, the
        # columns before re-scale
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, n_pages), 1)
        sums = jnp.zeros((h, n_pages), jnp.float32)
        for i in range(ppb):
            s = jnp.sum(p[:, i * width:(i + 1) * width], axis=1,
                        keepdims=True)
            sums += jnp.where(cols == j * ppb + i, s, 0.0)
        p_scr[...] = p_scr[...] * corr + sums

    @pl.when(j == n_blocks - 1)
    def _flush():
        # a row that attends nothing (length 0) writes zeros
        h = l_scr.shape[0]
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
    # whole pages of K and V per block, at most the table's width
    ppb = max(1, min(n_pages, _BLOCK_BYTES
                     // (2 * page * kvh * d * k_pages.dtype.itemsize)))

    kernel = functools.partial(_kernel, page=page, ppb=ppb, n_pages=n_pages,
                               scale=scale, window=window, softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pl.cdiv(n_pages, ppb)),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bi, ji, pt, ln: (bi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, h, d), lambda bi, ji, pt, ln: (bi, 0, 0)),
            # the mass is emitted as [B, 1, n_pages] so the block's last
            # two dims equal the array's: a (1, n_pages) block of a
            # [B, n_pages] array breaks the TPU's (8, 128) tiling rule
            pl.BlockSpec((1, 1, n_pages), lambda bi, ji, pt, ln: (bi, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, ppb * page * kvh, d), k_pages.dtype),
            pltpu.VMEM((2, ppb * page * kvh, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
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
        # the grid runs in order on one core: a block's copies are started
        # by the grid step before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
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

    # float32 products at full precision (a single bfloat16 pass would
    # round queries and latents to 8 bits); bfloat16 ones are exact
    hi = (jax.lax.Precision.HIGHEST if ckv.dtype == jnp.float32
          and qa.dtype == jnp.float32 else None)
    logits = (jax.lax.dot_general(
        qa, ckv, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=hi)
        + jax.lax.dot_general(
        qr, kr, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=hi)) * scale
    logits = jnp.where(valid[None, :], logits, NEG_INF)

    m_prev = m_scr[...]                            # [H, 1]
    m_cur = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    ctx = jax.lax.dot_general(
        p.astype(ckv.dtype), ckv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=hi)   # [H, R]
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
