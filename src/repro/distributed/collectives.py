"""Distributed-optimization collectives.

``compressed_psum``: int8-quantised all-reduce for the cross-pod (DCN)
gradient reduction.  The wire format is int8 (all_gather of int8 shards +
local fp32 accumulate), cutting DCN bytes 4x vs fp32 / 2x vs bf16; the
quantisation scale is agreed with one scalar pmax.  ``*_ef`` keeps an
error-feedback residual so the quantisation error is re-injected next step
(1-bit-Adam-style convergence behaviour).

These run inside ``shard_map`` bodies (manual axes).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["compressed_psum", "compressed_psum_ef"]

def _int_sum(q, axis_name: str):
    """Sum the int8 shards over ``axis_name`` in int32, exactly."""
    allq = jax.lax.all_gather(q, axis_name)      # int8 on the wire
    return jnp.sum(allq.astype(jnp.int32), axis=0)


def _quantize_global(x, axis_name: str):
    """int8-quantise with a scale agreed across `axis_name`."""
    amax = jnp.max(jnp.abs(x))
    gmax = jax.lax.pmax(amax, axis_name)
    scale = jnp.maximum(gmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Mean over `axis_name` with int8 wire format.

    all_gather moves int8 (the compressed payload); the accumulation runs
    locally in int32 -> fp32.  Returns the *mean* (DP semantics)."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    q, scale = _quantize_global(x.astype(jnp.float32), axis_name)
    total = _int_sum(q, axis_name).astype(jnp.float32)
    return (total * scale / n).astype(x.dtype)


def compressed_psum_ef(x: jnp.ndarray, ef: jnp.ndarray, axis_name: str
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Error-feedback variant: compresses (x + ef), returns (mean, new_ef)
    where new_ef is this step's local quantisation residual."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x, ef
    xf = x.astype(jnp.float32) + ef.astype(jnp.float32)
    q, scale = _quantize_global(xf, axis_name)
    sent = q.astype(jnp.float32) * scale
    new_ef = (xf - sent).astype(ef.dtype)
    total = _int_sum(q, axis_name).astype(jnp.float32)
    return (total * scale / n).astype(x.dtype), new_ef
