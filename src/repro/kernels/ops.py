"""Jitted public wrappers for the Pallas kernels.

``impl`` selects the execution path:
  * "pallas"    -- compiled TPU kernel (the deploy target)
  * "interpret" -- Pallas interpret mode (CPU-validatable, same kernel body)
  * "reference" -- pure-jnp oracle (autodiff-friendly)

``impl=None`` (the default) takes the path from the platform
(``default_impl``): the compiled kernel on a TPU; on the CPU, "interpret"
here and "reference" inside the served model code.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm as _megablox_gmm

from repro.kernels import flash_attention as _fa
from repro.kernels import page_hist as _ph
from repro.kernels import paged_attention as _pa
from repro.kernels import ref as _ref


def default_impl(cpu: str) -> str:
    """The kernel path for the backend this process computes on: the
    compiled Pallas kernel on a TPU, ``cpu`` on the CPU.  Any other
    backend raises -- no kernel path was built for it."""
    backend = jax.default_backend()
    if backend == "tpu":
        return "pallas"
    if backend == "cpu":
        return cpu
    raise ValueError(f"no kernel path for the {backend!r} backend")


@functools.partial(jax.jit, static_argnames=("alpha", "threshold", "impl"))
def page_hist(ids, hotness, *, alpha: float = 0.5, threshold: float = 1.0,
              impl: Optional[str] = None):
    impl = impl or default_impl("interpret")
    if impl == "reference":
        return _ref.page_hist_ref(ids, hotness, alpha=alpha,
                                  threshold=threshold)
    return _ph.page_hist(ids, hotness, alpha=alpha, threshold=threshold,
                         interpret=(impl == "interpret"))


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "bq", "bkv", "impl"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = _fa.DEFAULT_BQ, bkv: int = _fa.DEFAULT_BKV,
                    impl: Optional[str] = None):
    impl = impl or default_impl("interpret")
    if impl == "reference":
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _fa.flash_attention(q, k, v, causal=causal, window=window, bq=bq,
                               bkv=bkv, interpret=(impl == "interpret"))


@functools.partial(jax.jit,
                   static_argnames=("window", "softcap", "return_mass",
                                    "impl"))
def paged_attention(q, k_pages, v_pages, page_table, lengths, *,
                    window: int = 0, softcap: float = 0.0,
                    return_mass: bool = False, impl: Optional[str] = None):
    # Ragged multi-request tables pad short rows with -1; those entries are
    # already masked out by `lengths`, so clamp them to a valid physical
    # page: that keeps the kernel's page copies in bounds and stops the
    # reference gather from wrapping.  Precondition: a -1 *inside* the
    # `lengths` range means a non-resident page (slot_of == -1) leaked into
    # the table -- callers must ensure_resident first; the clamp cannot
    # distinguish that from padding on traced values.
    page_table = jnp.maximum(page_table, 0)
    impl = impl or default_impl("interpret")
    if impl == "reference":
        return _ref.paged_attention_ref(q, k_pages, v_pages, page_table,
                                        lengths, window=window,
                                        softcap=softcap,
                                        return_mass=return_mass)
    # The kernel carries a per-page exp-sum alongside its online-softmax
    # accumulators and emits the head-normalised page mass as a second
    # output -- telemetry is fused in-kernel; the reference oracle above is
    # retained only as the allclose target (tests/test_kernels.py).
    out, mass = _pa.paged_attention(q, k_pages, v_pages, page_table, lengths,
                                    window=window, softcap=softcap,
                                    interpret=(impl == "interpret"))
    if not return_mass:
        return out
    return out, mass


@functools.partial(jax.jit,
                   static_argnames=("scale", "return_mass", "impl"))
def paged_attention_mla(q_abs, q_rope, ckv_pages, krope_pages, page_table,
                        lengths, *, scale: float, return_mass: bool = False,
                        impl: Optional[str] = None):
    """MLA absorbed-matrix decode over compressed paged rows (ckv shared
    across heads + roped krope).  Same ragged-table clamp contract as
    ``paged_attention``; ``scale`` = 1/sqrt(qk_nope_dim + qk_rope_dim).
    Returns the compressed-space context [B, H, R] (callers up-project
    with W_uv) and, with ``return_mass``, the per-page mass f32[B, n]."""
    page_table = jnp.maximum(page_table, 0)
    impl = impl or default_impl("interpret")
    if impl == "reference":
        return _ref.paged_attention_mla_ref(q_abs, q_rope, ckv_pages,
                                            krope_pages, page_table, lengths,
                                            scale=scale,
                                            return_mass=return_mass)
    out, mass = _pa.paged_attention_mla(q_abs, q_rope, ckv_pages,
                                        krope_pages, page_table, lengths,
                                        scale=scale,
                                        interpret=(impl == "interpret"))
    if not return_mass:
        return out
    return out, mass


#: the grouped matmul's (m, k, n) tile on the TPU
GMM_TILE = (128, 512, 1024)


@functools.partial(jax.jit, static_argnames=("impl",))
def expert_gmm(x, w, group_sizes, *, impl: Optional[str] = None):
    """Grouped matmul: rows of ``x`` [m, k], sorted by group, times their
    group's ``w`` [G, k, n] -> float32 [m, n].  Group g owns the
    ``group_sizes[g]`` rows after groups 0..g-1; rows past the groups'
    sum are left unspecified (callers select them away).  On a TPU the
    kernel is the megablox ``gmm`` (its op in a trace is ``gmm``): it
    visits only the row tiles of groups that have rows, so an expert with
    no rows costs no weight read.  "reference" is ``lax.ragged_dot``."""
    impl = impl or default_impl("reference")
    if impl == "reference":
        return jax.lax.ragged_dot(x, w, group_sizes,
                                  preferred_element_type=jnp.float32)
    (m, k), n = x.shape, w.shape[2]
    tiling = (min(GMM_TILE[0], m), min(GMM_TILE[1], k), min(GMM_TILE[2], n))
    pad = -m % tiling[0]            # the kernel takes whole row tiles
    out = _megablox_gmm(jnp.pad(x, ((0, pad), (0, 0))), w, group_sizes,
                        preferred_element_type=jnp.float32, tiling=tiling,
                        interpret=(impl == "interpret"))
    return out[:m]
