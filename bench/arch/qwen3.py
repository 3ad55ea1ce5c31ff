"""Qwen3 dense decoder (Qwen3ForCausalLM): the configuration file turned
into the program's ``ModelConfig`` and weights in the program's parameter
layout, the leaf table its weights and its reference draw from, and its
model FLOPs.

The program's ``embed`` multiplies the token table by sqrt(hidden) (and
rounds the table to its activation dtype first); the published Qwen3
forward does not scale.  ``make_params`` hands the program the published
table divided by sqrt(hidden), so both compute the same function.

The program is imported only inside ``model_config``: the reference
(``bench/reference/qwen3.py``) reads the leaf table here and imports
nothing of the program.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

__all__ = ["GLOBAL", "LAYER", "shapes", "model_config", "make_params",
           "matmul_params", "model_flops"]

#: configuration keys whose values the program fixes itself:
#: ``model_config`` refuses a file that states anything else
_FIXED = {"hidden_act": "silu", "attention_bias": False,
          "rms_norm_eps": 1e-06, "rope_scaling": None,
          "use_sliding_window": False, "tie_word_embeddings": False}

#: leaves outside the layer stack
GLOBAL = ("embed", "unembed", "final_norm")
#: leaves of every layer
LAYER = ("norm1", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "norm2",
         "w_gate", "w_up", "w_down")


def shapes(conf: Dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """{leaf: (shape, std)}; std 0 marks a norm scale (1 + N(0, 0.1^2)).
    Semantics are the published model's: ``embed`` is the token table as
    the published forward uses it (no scaling), ``norm*`` are RMSNorm
    scales, matrices map input features to output features."""
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
    return _make_jit(W.key_of(seed), _Conf(conf))


class _Conf(dict):
    """A hashable configuration, so the jitted maker can take it static."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


@functools.partial(jax.jit, static_argnums=(1,))
def _make_jit(key, conf):
    me = sys.modules[__name__]          # this module's leaf table
    g = W.globals_(key, me, conf)
    lay = jax.vmap(lambda l: W.layer(key, me, conf, l))(
        jnp.arange(conf["num_hidden_layers"]))
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


def matmul_params(conf: Dict) -> Tuple[int, int]:
    """(matrix parameters of one layer, of the unembedding)."""
    d, h, kv = conf["hidden_size"], conf["num_attention_heads"], \
        conf["num_key_value_heads"]
    hd, ff = conf["head_dim"], conf["intermediate_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return per_layer, d * conf["vocab_size"]


def model_flops(conf: Dict, prompts: Iterable[int],
                decode_contexts: Iterable[int]) -> float:
    """FLOPs of prefilling ``prompts`` (lengths; logits at the last
    position only) and of decoding one token at each of
    ``decode_contexts`` (keys attended, the new token's included): 2 per
    multiply-add of every matrix product a token needs, plus attention at
    the token's actual context."""
    per_layer, unembed = matmul_params(conf)
    n_layers = conf["num_hidden_layers"]
    attn = 4.0 * conf["num_attention_heads"] * conf["head_dim"] * n_layers
    p = np.asarray(list(prompts), np.float64)
    c = np.asarray(list(decode_contexts), np.float64)
    flops = 2.0 * per_layer * n_layers * (p.sum() + c.size)
    flops += 2.0 * unembed * (p.size + c.size)
    flops += attn * (float((p * (p + 1) / 2).sum()) + float(c.sum()))
    return float(flops)
