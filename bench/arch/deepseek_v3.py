"""DeepSeek-V3 (DeepseekV3ForCausalLM): the configuration file turned into
the program's ``ModelConfig`` and weights in the program's parameter
layout, the leaf table its weights and its reference draw from, its model
FLOPs and the cost of the two kernels only it runs.

One chip's share of a layer: the file's ``n_routed_experts`` is the block
of experts held here (``reduced`` gives the published count, the router's
width, and the first expert held); heads and vocabulary are the file's.

Weights are stored in bfloat16, as a bfloat16 conversion of the published
checkpoint serves them: each float32 draw is rounded once, and the
reference computes in float32 on the same rounded values.  The router's
selection bias stays float32, as published.

Two departures of layout, not of function:

- the published rope pairs dimensions (2i, 2i+1) (its
  ``apply_rotary_pos_emb`` de-interleaves them first); the program's
  ``rope`` pairs (i, i + d/2).  ``make_params`` permutes the rope columns
  of ``w_uq`` and ``w_kr`` the same way, so both compute the same function;
- the program's ``embed`` multiplies the token table by sqrt(hidden); the
  published forward does not scale.  So the table is stored as the
  program reads it, each draw divided by sqrt(hidden) and rounded once
  (``stored``), and the float32 values it stands for are those multiplied
  back as the program does (``as_float32``): what the reference embeds.
  Both then compute on the same values (a near-tie of the router flips on
  a difference of bfloat16's 2^-9).

The program is imported only inside ``model_config``: the reference
(``bench/reference/deepseek_v3.py``) reads the leaf table here and imports
nothing of the program.
"""
from __future__ import annotations

import functools
import sys
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

__all__ = ["GLOBAL", "LAYER", "ATTN", "DENSE", "MOE", "shapes", "experts",
           "stored", "as_float32", "rope_perm", "model_config",
           "make_params", "model_flops", "paged_attention_mla_cost",
           "expert_gmm_cost"]

#: configuration keys whose values the program fixes itself:
#: ``model_config`` refuses a file that states anything else
_FIXED = {"model_type": "deepseek_v3", "hidden_act": "silu",
          "attention_bias": False, "rms_norm_eps": 1e-06,
          "scoring_func": "sigmoid", "topk_method": "noaux_tc",
          "norm_topk_prob": True, "moe_layer_freq": 1, "ep_size": 1,
          "n_shared_experts": 1, "tie_word_embeddings": False}

#: leaves outside the layer stack
GLOBAL = ("embed", "unembed", "final_norm")
#: the attention leaves of every layer
ATTN = ("norm1", "w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm", "w_kr",
        "w_uk", "w_uv", "wo", "norm2")
#: the dense FFN of a leading layer
DENSE = ("w_gate", "w_up", "w_down")
#: the expert layer of every other layer: router, its selection bias, the
#: held experts and the shared expert
MOE = ("router", "router_bias", "e_gate", "e_up", "e_down", "s_gate",
       "s_up", "s_down")
#: every leaf of a layer, in fold-in order (a layer draws its kind's)
LAYER = ATTN + DENSE + MOE
#: std of the router's selection bias (the checkpoint's is learned)
BIAS_STD = 0.02


def experts(conf: Dict) -> Tuple[int, int, int]:
    """(router width, first expert held, experts held)."""
    red = conf["reduced"]["n_routed_experts"]
    return int(red["published"]), int(red["first"]), \
        int(conf["n_routed_experts"])


def shapes(conf: Dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """{leaf: (shape, std)}; std 0 marks a norm scale (1 + N(0, 0.1^2)).
    Semantics are the published model's: ``embed`` unscaled, matrices map
    input features to output features, per-head leaves keep the heads
    axis, the rope columns are in the published (interleaved) order."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    ql, kl = conf["q_lora_rank"], conf["kv_lora_rank"]
    nope, rope, vd = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                      conf["v_head_dim"])
    ff, f = conf["intermediate_size"], conf["moe_intermediate_size"]
    fs = f * conf["n_shared_experts"]
    width, _, held = experts(conf)
    v = conf["vocab_size"]
    return {
        "embed": ((v, d), 1.0), "unembed": ((d, v), d ** -0.5),
        "final_norm": ((d,), 0.0),
        "norm1": ((d,), 0.0), "w_dq": ((d, ql), d ** -0.5),
        "q_norm": ((ql,), 0.0), "w_uq": ((ql, h, nope + rope), ql ** -0.5),
        "w_dkv": ((d, kl), d ** -0.5), "kv_norm": ((kl,), 0.0),
        "w_kr": ((d, rope), d ** -0.5),
        "w_uk": ((kl, h, nope), kl ** -0.5),
        "w_uv": ((kl, h, vd), kl ** -0.5),
        "wo": ((h, vd, d), (h * vd) ** -0.5), "norm2": ((d,), 0.0),
        "w_gate": ((d, ff), d ** -0.5), "w_up": ((d, ff), d ** -0.5),
        "w_down": ((ff, d), ff ** -0.5),
        "router": ((d, width), d ** -0.5),
        "router_bias": ((width,), BIAS_STD),
        "e_gate": ((held, d, f), d ** -0.5),
        "e_up": ((held, d, f), d ** -0.5),
        "e_down": ((held, f, d), f ** -0.5),
        "s_gate": ((d, fs), d ** -0.5), "s_up": ((d, fs), d ** -0.5),
        "s_down": ((fs, d), fs ** -0.5),
    }


def layer_leaves(key, conf: Dict, l, names) -> Dict[str, jax.Array]:
    """Leaves ``names`` of layer ``l``, float32, as published."""
    sh = shapes(conf)
    return {n: W.draw(key, GLOBAL + LAYER, n, l, *sh[n]) for n in names}


def stored(name: str, x: jax.Array) -> jax.Array:
    """A leaf as the bfloat16 checkpoint stores it (the selection bias
    stays float32; the token table is the draw over sqrt(hidden), as the
    program's ``embed`` reads it)."""
    if name == "router_bias":
        return x
    if name == "embed":
        x = x * x.shape[-1] ** -0.5
    return x.astype(jnp.bfloat16)


def as_float32(name: str, x: jax.Array) -> jax.Array:
    """The float32 values a ``stored`` leaf stands for: the token table
    multiplied back by sqrt(hidden) in float32, as the program's ``embed``
    does."""
    y = x.astype(jnp.float32)
    if name == "embed":
        y = y * np.float32(np.sqrt(x.shape[-1]))
    return y


def rope_perm(rope: int) -> np.ndarray:
    """Column order that turns the published interleaved rope pairs (2i,
    2i+1) into the program's halves (i, i + rope/2)."""
    return np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])


def model_config(conf: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    import dataclasses

    import repro.configs as C
    from repro.models.config import MLAConfig, MoEConfig, RopeScaling

    for k, want in _FIXED.items():
        if conf.get(k, want) != want:
            raise ValueError(f"{conf['name']}: {k}={conf[k]!r}, the program "
                             f"serves only {want!r}")
    rs = dict(conf["rope_scaling"])
    if rs.pop("type") != "yarn":
        raise ValueError(f"{conf['name']}: only yarn rope scaling is served")
    width, first, held = experts(conf)
    f, dense = conf["moe_intermediate_size"], conf["first_k_dense_replace"]
    base = C.get(conf["registry_base"])
    return dataclasses.replace(
        base, name=conf["name"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        segments=((("attn.mla",), dense),
                  (("attn.mla.moe",), conf["num_hidden_layers"] - dense)),
        moe=MoEConfig(num_experts=width,
                      top_k=conf["num_experts_per_tok"], d_expert=f,
                      num_shared=conf["n_shared_experts"], d_shared=f,
                      scoring_func=conf["scoring_func"],
                      n_group=conf["n_group"], topk_group=conf["topk_group"],
                      routed_scaling_factor=float(
                          conf["routed_scaling_factor"]),
                      held_start=first, held=held),
        mla=MLAConfig(q_lora_rank=conf["q_lora_rank"],
                      kv_lora_rank=conf["kv_lora_rank"],
                      qk_nope_dim=conf["qk_nope_head_dim"],
                      qk_rope_dim=conf["qk_rope_head_dim"],
                      v_head_dim=conf["v_head_dim"],
                      rope_scaling=RopeScaling(**rs)),
        rope_theta=float(conf["rope_theta"]), tie_embeddings=False,
        max_seq_len=conf["max_position_embeddings"],
        param_dtype="bfloat16")


def make_params(conf: Dict, seed: int):
    """The program's parameter tree, drawn on the device in one call."""
    return _make_jit(W.key_of(seed), _Conf(conf))


class _Conf(dict):
    """A hashable configuration, so the jitted maker can take it static."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


def _attn(lay, perm, nope):
    rope_cols = nope + perm
    w_uq = jnp.concatenate([lay["w_uq"][..., :nope],
                            lay["w_uq"][..., rope_cols]], axis=-1)
    return {"w_dq": lay["w_dq"], "q_norm": lay["q_norm"], "w_uq": w_uq,
            "w_dkv": lay["w_dkv"], "kv_norm": lay["kv_norm"],
            "w_kr": lay["w_kr"][..., perm], "w_uk": lay["w_uk"],
            "w_uv": lay["w_uv"], "wo": lay["wo"]}


@functools.partial(jax.jit, static_argnums=(1,))
def _make_jit(key, conf):
    me = sys.modules[__name__]
    perm = rope_perm(conf["qk_rope_head_dim"])
    nope = conf["qk_nope_head_dim"]
    dense = conf["first_k_dense_replace"]

    def draw(names, l):
        return {n: stored(n, x) for n, x in
                layer_leaves(key, conf, l, names).items()}

    g = {n: stored(n, x) for n, x in W.globals_(key, me, conf).items()}
    dl = jax.vmap(lambda l: draw(ATTN + DENSE, l))(jnp.arange(dense))
    ml = jax.vmap(lambda l: draw(ATTN + MOE, l))(
        jnp.arange(dense, conf["num_hidden_layers"]))
    dense_slot = {"norm1": dl["norm1"], "attn": _attn(dl, perm, nope),
                  "norm2": dl["norm2"],
                  "mlp": {"wi_gate": dl["w_gate"], "wi_up": dl["w_up"],
                          "wo": dl["w_down"]}}
    moe_slot = {"norm1": ml["norm1"], "attn": _attn(ml, perm, nope),
                "norm2": ml["norm2"],
                "moe": {"router": ml["router"],
                        "router_bias": ml["router_bias"],
                        "wi_gate": ml["e_gate"], "wi_up": ml["e_up"],
                        "wo": ml["e_down"],
                        "shared": {"wi_gate": ml["s_gate"],
                                   "wi_up": ml["s_up"],
                                   "wo": ml["s_down"]}}}
    return {"embed": {"tok": g["embed"], "unembed": g["unembed"]},
            "final_norm": g["final_norm"],
            "segments": [[dense_slot], [moe_slot]]}


def model_flops(conf: Dict, prompts: Iterable[int],
                decode_contexts: Iterable[int]) -> float:
    """FLOPs of prefilling ``prompts`` (lengths; logits at the last
    position only) and of decoding one token at each of
    ``decode_contexts`` (keys attended, the new token's included), as the
    published (non-absorbed) forward counts them on this chip's share: 2
    per multiply-add of every matrix product a token needs -- routed
    experts at their expected k x held / width per token -- plus
    attention at the token's context (q.k over nope + rope, p.v over v,
    every head)."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    ql, kl = conf["q_lora_rank"], conf["kv_lora_rank"]
    nope, rope, vd = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                      conf["v_head_dim"])
    f, ff = conf["moe_intermediate_size"], conf["intermediate_size"]
    width, _, held = experts(conf)
    n_layers, dense = conf["num_hidden_layers"], conf["first_k_dense_replace"]
    mla = (d * ql + ql * h * (nope + rope) + d * (kl + rope)
           + kl * h * (nope + vd) + h * vd * d)
    routed = conf["num_experts_per_tok"] * held / width
    moe = (d * width + 3 * d * f * routed
           + 3 * d * f * conf["n_shared_experts"])
    per_token = (n_layers * mla + dense * 3 * d * ff
                 + (n_layers - dense) * moe)
    attn = 2.0 * h * (nope + rope + vd) * n_layers
    p = np.asarray(list(prompts), np.float64)
    c = np.asarray(list(decode_contexts), np.float64)
    flops = 2.0 * per_token * (p.sum() + c.size)
    flops += 2.0 * d * conf["vocab_size"] * (p.size + c.size)
    flops += attn * (float((p * (p + 1) / 2).sum()) + float(c.sum()))
    return float(flops)


def paged_attention_mla_cost(lengths: Iterable[int], *, heads: int,
                             kv_lora: int, rope: int, kv_itemsize: int,
                             q_itemsize: int) -> Tuple[float, float]:
    """(flops, bytes) of one absorbed-MLA decode query per row over
    ``lengths`` keys: q.ckv and q.krope are 2 * heads * (kv_lora + rope)
    flops a key, p.ckv 2 * heads * kv_lora; the row reads each key's
    kv_lora + rope latent once, its query (heads x (kv_lora + rope)) once
    and writes its context (heads x kv_lora) once (rows of length 0 do
    nothing)."""
    n = np.asarray(list(lengths), np.float64)
    live = float((n > 0).sum())
    total = float(n.sum())
    flops = 2.0 * heads * (2 * kv_lora + rope) * total
    bytes_ = ((kv_lora + rope) * kv_itemsize * total
              + heads * (2 * kv_lora + rope) * q_itemsize * live)
    return flops, bytes_


def expert_gmm_cost(pairs: float, experts_touched: float, *, hidden: int,
                    expert_width: int, w_itemsize: int = 2,
                    x_itemsize: int = 4) -> Tuple[float, float]:
    """(flops, bytes) of the held experts' grouped matmuls for ``pairs``
    token-expert pairs over ``experts_touched`` experts with at least one
    pair: gate, up and down are 6 * pairs * hidden * expert_width flops;
    each touched expert's three matrices are read once, and each pair
    reads its row for gate and up and the down input, and writes gate,
    up (float32) and its output row (float32).  Weights are bfloat16, as
    stored; the rows the products read are the float32 activations (the
    kernel reads each as three bfloat16 pieces, the same bytes and a
    half)."""
    d, f = hidden, expert_width
    flops = 6.0 * pairs * d * f
    bytes_ = (experts_touched * 3.0 * d * f * w_itemsize
              + pairs * ((2 * d + f) * x_itemsize + (2 * f + d) * 4))
    return flops, bytes_
