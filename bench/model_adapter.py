"""The system under test, as the benchmark sees it: a configuration file
turned into the program's ``ModelConfig`` and weights in the program's
parameter layout.

The program's ``embed`` multiplies the token table by sqrt(hidden) (and
rounds the table to its activation dtype first); the published Qwen3
forward does not scale.  The adapter hands the program the published
table divided by sqrt(hidden), so both compute the same function.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp

from bench import weights as W

__all__ = ["model_config", "make_params"]

#: configuration keys whose values the program fixes itself: the
#: adapter refuses a file that states anything else
_FIXED = {"hidden_act": "silu", "attention_bias": False,
          "rms_norm_eps": 1e-06, "rope_scaling": None,
          "use_sliding_window": False, "tie_word_embeddings": False}


def model_config(conf: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    import repro.configs as C

    for k, want in _FIXED.items():
        if conf.get(k, want) != want:
            raise ValueError(f"{conf['name']}: {k}={conf[k]!r}, the program "
                             f"serves only {want!r}")
    base = C.get(conf["registry_base"])
    if not (base.qk_norm and base.mlp_kind == "swiglu"):
        raise ValueError(f"{conf['registry_base']} is not a Qwen3 block")
    return dataclasses.replace(
        base, name=conf["name"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"], d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        segments=((("attn",), conf["num_hidden_layers"]),),
        rope_theta=float(conf["rope_theta"]), tie_embeddings=False,
        max_seq_len=conf["max_position_embeddings"])


def make_params(conf: Dict, seed: int):
    """The program's parameter tree, drawn on the device in one call."""
    return _make(seed, _Conf(conf))


class _Conf(dict):
    """A hashable configuration, so the jitted maker can take it static."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


@functools.partial(jax.jit, static_argnums=(1,))
def _make_jit(key, conf):
    sh = W.shapes(conf)
    n_layers = conf["num_hidden_layers"]
    g = {n: W.draw(key, n, 0, *sh[n]) for n in W.GLOBAL}
    lay = jax.vmap(lambda l: W.layer(key, conf, l))(jnp.arange(n_layers))
    slot = {
        "norm1": lay["norm1"],
        "attn": {"wq": lay["wq"], "wk": lay["wk"], "wv": lay["wv"],
                 "wo": lay["wo"], "q_norm": lay["q_norm"],
                 "k_norm": lay["k_norm"]},
        "norm2": lay["norm2"],
        "mlp": {"wi_gate": lay["w_gate"], "wi_up": lay["w_up"],
                "wo": lay["w_down"]},
    }
    return {"embed": {"tok": g["embed"] * conf["hidden_size"] ** -0.5,
                      "unembed": g["unembed"]},
            "final_norm": g["final_norm"],
            "segments": [[slot]]}


def _make(seed, conf):
    return _make_jit(W.key_of(seed), conf)
