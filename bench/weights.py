"""Seeded random weights, by leaf name, for any architecture.

Both the system under test (through its ``bench/arch/<name>.py``) and the
plain reference draw their weights here, from the run's seed, so neither
takes anything the other made.  The architecture module passed in gives
the leaf table: ``GLOBAL`` (leaves outside the layer stack) and ``LAYER``
(leaves of every layer), names in a fixed order, and ``shapes(conf)``,
``{leaf: (shape, std)}``.  Leaf ``name`` of layer ``l`` is drawn from
``fold_in(fold_in(key(seed), index(name)), l)``, its index in
``GLOBAL + LAYER``: one layer can be drawn alone (the reference, layer by
layer) or all layers at once under ``vmap`` (the served program's stacked
leaves), with the same values.  A std of 0 marks a norm scale, drawn as
1 + N(0, 0.1^2).
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp

__all__ = ["key_of", "draw", "layer", "globals_"]


def key_of(seed: int) -> jax.Array:
    """The run's root key; any whole seed up to 2**63."""
    return jax.random.PRNGKey(int(seed))


def draw(key: jax.Array, leaves: Sequence[str], name: str, l, shape,
         std: float) -> jax.Array:
    """Leaf ``name`` of layer ``l`` (traced or concrete), float32;
    ``leaves`` is the architecture's ``GLOBAL + LAYER``."""
    idx = tuple(leaves).index(name)
    k = jax.random.fold_in(jax.random.fold_in(key, idx), l)
    x = jax.random.normal(k, shape, jnp.float32)
    return 1.0 + 0.1 * x if std == 0.0 else x * std


def layer(key: jax.Array, arch, conf: Dict, l) -> Dict[str, jax.Array]:
    """Every leaf of layer ``l`` of the architecture ``arch``."""
    sh = arch.shapes(conf)
    order = arch.GLOBAL + arch.LAYER
    return {n: draw(key, order, n, l, *sh[n]) for n in arch.LAYER}


def globals_(key: jax.Array, arch, conf: Dict) -> Dict[str, jax.Array]:
    """Every leaf outside the layer stack of the architecture ``arch``."""
    sh = arch.shapes(conf)
    order = arch.GLOBAL + arch.LAYER
    return {n: draw(key, order, n, 0, *sh[n]) for n in arch.GLOBAL}
