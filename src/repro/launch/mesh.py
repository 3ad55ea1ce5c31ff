"""Production mesh construction.

Single pod: 256 chips as (16, 16) ("data", "model").
Multi-pod:  2 pods x 256 chips as (2, 16, 16) ("pod", "data", "model");
the "pod" axis carries cross-DCN data parallelism (optionally with int8
gradient compression -- see repro.distributed.collectives).

Defined as functions (not module constants) so importing this module never
touches jax device state.  Every axis is ``Auto``: the sharding code places
arrays with ``with_sharding_constraint`` and named shardings, which only
refer to Auto axes (``jax.make_mesh`` defaults to Explicit ones).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh"]


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 1):
    """Small mesh over however many (possibly fake) host devices exist --
    used by tests and the local examples."""
    if pod > 1:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))
