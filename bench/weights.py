"""Seeded random weights of a Qwen3-style dense decoder, by leaf name.

Both the system under test (through ``bench/model_adapter.py``) and the
plain reference draw their weights here, from the run's seed, so neither
takes anything the other made.  Leaf ``name`` of layer ``l`` is drawn from
``fold_in(fold_in(key(seed), index(name)), l)``: one layer can be drawn
alone (the reference, layer by layer) or all layers at once under
``vmap`` (the served program's stacked leaves), with the same values.

Semantics are the published model's: ``embed`` is the token table as the
published forward uses it (no scaling), ``norm*`` are RMSNorm scales,
matrices map input features to output features.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

__all__ = ["GLOBAL", "LAYER", "shapes", "draw", "key_of", "layer",
           "globals_"]

#: leaves outside the layer stack
GLOBAL = ("embed", "unembed", "final_norm")
#: leaves of every layer
LAYER = ("norm1", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "norm2",
         "w_gate", "w_up", "w_down")


def shapes(conf: Dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """{leaf: (shape, std)}; std 0 marks a norm scale (1 + N(0, 0.1^2))."""
    d, h, kv = conf["hidden_size"], conf["num_attention_heads"], \
        conf["num_key_value_heads"]
    hd, ff, v = conf["head_dim"], conf["intermediate_size"], \
        conf["vocab_size"]
    return {
        "embed": ((v, d), 1.0),
        "unembed": ((d, v), d ** -0.5),
        "final_norm": ((d,), 0.0),
        "norm1": ((d,), 0.0),
        "wq": ((d, h, hd), d ** -0.5),
        "wk": ((d, kv, hd), d ** -0.5),
        "wv": ((d, kv, hd), d ** -0.5),
        "q_norm": ((hd,), 0.0),
        "k_norm": ((hd,), 0.0),
        "wo": ((h, hd, d), (h * hd) ** -0.5),
        "norm2": ((d,), 0.0),
        "w_gate": ((d, ff), d ** -0.5),
        "w_up": ((d, ff), d ** -0.5),
        "w_down": ((ff, d), ff ** -0.5),
    }


def key_of(seed: int) -> jax.Array:
    """The run's root key; any whole seed up to 2**63."""
    return jax.random.PRNGKey(int(seed))


def draw(key: jax.Array, name: str, l, shape, std: float) -> jax.Array:
    """Leaf ``name`` of layer ``l`` (traced or concrete), float32."""
    idx = (GLOBAL + LAYER).index(name)
    k = jax.random.fold_in(jax.random.fold_in(key, idx), l)
    x = jax.random.normal(k, shape, jnp.float32)
    return 1.0 + 0.1 * x if std == 0.0 else x * std


def layer(key: jax.Array, conf: Dict, l) -> Dict[str, jax.Array]:
    """Every leaf of layer ``l``."""
    sh = shapes(conf)
    return {n: draw(key, n, l, *sh[n]) for n in LAYER}


def globals_(key: jax.Array, conf: Dict) -> Dict[str, jax.Array]:
    sh = shapes(conf)
    return {n: draw(key, n, 0, *sh[n]) for n in GLOBAL}
