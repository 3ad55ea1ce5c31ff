"""Model configuration schema covering all assigned architecture families.

A model is a sequence of *segments*; each segment is a layer pattern repeated
R times (``(pattern, R)``).  Patterns are tuples of layer-kind strings:

    "attn"    full causal self-attention + MLP
    "local"   sliding-window causal self-attention + MLP
    "mlstm"   xLSTM matrix-LSTM block
    "slstm"   xLSTM scalar-LSTM block
    "rglru"   RG-LRU recurrent block (+ MLP)

Kind strings may carry dot-flags: ``.moe`` (MLP is a routed MoE),
``.xattn`` (adds cross-attention to conditioning), ``.mla`` (attention is
Multi-head Latent Attention).  Example: ``"attn.mla.moe"`` (DeepSeek-V3).

Scanning: parameters of each segment are stacked ``[R, ...]`` and the
segment is executed with ``lax.scan`` over repeats (pattern slots unrolled
inside the scan body), keeping HLO size proportional to the pattern length
rather than the layer count.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import numpy as np

__all__ = [
    "MoEConfig", "MLAConfig", "RopeScaling", "LayerKind", "ModelConfig",
    "parse_kind",
]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int            # the router's width: every expert it scores
    top_k: int
    d_expert: int               # per-expert FFN hidden size
    num_shared: int = 0         # always-on shared experts (DeepSeek)
    d_shared: int = 0           # shared expert hidden size (0 -> d_expert)
    capacity_factor: float = 1.25   # shard_map path only (it drops tokens)
    router_noise: float = 0.0   # jitter during training
    aux_loss_weight: float = 0.01
    # router: "softmax" (softmax over the logits, top-k renormalised) or
    # "sigmoid" (DeepSeek-V3's noaux_tc: sigmoid scores, a correction
    # bias that only selects, group-limited top-k, weights renormalised
    # and scaled by routed_scaling_factor)
    scoring_func: str = "softmax"
    n_group: int = 1            # sigmoid: expert groups ...
    topk_group: int = 1         # ... of which the best this many are kept
    routed_scaling_factor: float = 1.0
    # the contiguous block of experts this layer holds (its expert-parallel
    # share): experts [held_start, held_start + held); 0 holds all
    held_start: int = 0
    held: int = 0

    @property
    def n_held(self) -> int:
        return self.held or self.num_experts


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """A checkpoint's ``rope_scaling`` block (only ``type`` "yarn"):
    frequencies blended between interpolated (/ factor) and extrapolated
    over the correction range that ``beta_fast`` / ``beta_slow`` rotations
    at ``original_max_position_embeddings`` give, and attention logits
    scaled by mscale(factor, mscale_all_dim)^2."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    type: str = "yarn"


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor (``yarn_get_mscale``)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    # the checkpoint's rope_scaling block (its only rope is MLA's)
    rope_scaling: Optional[RopeScaling] = None


@dataclasses.dataclass(frozen=True)
class LayerKind:
    base: str                   # attn | local | mlstm | slstm | rglru
    moe: bool = False
    xattn: bool = False
    mla: bool = False

    @property
    def is_attention(self) -> bool:
        return self.base in ("attn", "local")

    @property
    def is_recurrent(self) -> bool:
        return self.base in ("mlstm", "slstm", "rglru")


def parse_kind(s: str) -> LayerKind:
    parts = s.split(".")
    base, flags = parts[0], set(parts[1:])
    assert base in ("attn", "local", "mlstm", "slstm", "rglru"), s
    assert flags <= {"moe", "xattn", "mla"}, s
    return LayerKind(base, "moe" in flags, "xattn" in flags, "mla" in flags)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                              # dense|vlm|ssm|audio|moe|hybrid
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    segments: Tuple[Tuple[Tuple[str, ...], int], ...]  # ((pattern, repeats),...)
    # attention details
    window_size: int = 0                     # sliding window for "local"
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    softcap: float = 0.0                     # logit soft-capping (gemma-style)
    # MLP
    mlp_kind: str = "swiglu"                 # swiglu | squared_relu | gelu
    # optional sub-configs
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    # recurrent sizes
    lru_width: int = 0                       # RG-LRU state width (0 -> d_model)
    # embeddings / io
    tie_embeddings: bool = True
    prefix_len: int = 0                      # VLM image-prefix tokens
    cond_len: int = 0                        # cross-attention conditioning length
    cond_dim: int = 0                        # conditioning embed dim (0 -> d_model)
    max_seq_len: int = 8192
    # numerics
    dtype: str = "bfloat16"                  # activation dtype
    param_dtype: str = "float32"
    # implementation switches
    attention_impl: str = "reference"        # reference | pallas
    moe_impl: str = "dense"                  # dense (grouped) | shard_map
    moe_chunk: int = 0                       # tokens per dispatch chunk (0 = all)
    remat: bool = True
    unroll_layers: bool = False              # python-loop segments (trip-count-
                                             # correct HLO cost analysis)
    # which shapes are lowerable (long_500k needs sub-quadratic paths)
    supports_long_context: bool = False

    # ------------------------------------------------------------------
    @property
    def rope_scaling(self) -> Optional[RopeScaling]:
        """The checkpoint's rope scaling (held by ``mla``, the one rope
        that takes it; None elsewhere)."""
        return self.mla.rope_scaling if self.mla is not None else None

    @property
    def mla_scale(self):
        """The MLA attention logits' scale, one for prefill, dense decode
        and the paged kernel: 1/sqrt(qk_nope + qk_rope), times YaRN's
        mscale(factor, mscale_all_dim)^2 where the rope is scaled."""
        m = self.mla
        scale = 1.0 / np.sqrt(m.qk_nope_dim + m.qk_rope_dim)
        rs = self.rope_scaling
        if rs is not None and rs.mscale_all_dim:
            scale = float(scale) * yarn_mscale(rs.factor,
                                               rs.mscale_all_dim) ** 2
        return scale

    @property
    def num_layers(self) -> int:
        return sum(len(pat) * rep for pat, rep in self.segments)

    def layer_kinds(self):
        for pat, rep in self.segments:
            for _ in range(rep):
                for s in pat:
                    yield parse_kind(s)

    @property
    def has_recurrent(self) -> bool:
        return any(k.is_recurrent for k in self.layer_kinds())

    @property
    def q_per_kv(self) -> int:
        assert self.num_heads % max(1, self.num_kv_heads) == 0
        return self.num_heads // max(1, self.num_kv_heads)

    # -- parameter counting (for 6ND roofline math) ---------------------
    def param_count(self) -> int:
        """Total parameter count (embeddings included once if tied)."""
        d, h, kv, hd, ff, v = (self.d_model, self.num_heads, self.num_kv_heads,
                               self.head_dim, self.d_ff, self.vocab_size)
        n = v * d if self.tie_embeddings else 2 * v * d
        cd = self.cond_dim or d
        for k in self.layer_kinds():
            if k.base in ("attn", "local"):
                if k.mla:
                    m = self.mla
                    qk_head = m.qk_nope_dim + m.qk_rope_dim
                    n += d * m.q_lora_rank + m.q_lora_rank * h * qk_head
                    n += d * (m.kv_lora_rank + m.qk_rope_dim)
                    n += m.kv_lora_rank * h * (m.qk_nope_dim + m.v_head_dim)
                    n += h * m.v_head_dim * d
                else:
                    n += d * h * hd + 2 * d * kv * hd + h * hd * d
            elif k.base == "mlstm":
                dm = 2 * d  # up-projection width
                n += d * 2 * dm + 3 * dm * dm // 4 + dm * d  # qkv + gates approx
            elif k.base == "slstm":
                n += 4 * d * d + d * (4 * d) // 3 * 2
            elif k.base == "rglru":
                w = self.lru_width or d
                n += 2 * d * w + 2 * w * w // 8 + w * d + 2 * w  # in/gates/out
            if k.xattn:
                n += d * h * hd + 2 * cd * kv * hd + h * hd * d
            # MLP / MoE
            if k.moe:
                mo = self.moe
                n += d * mo.num_experts  # router
                if mo.scoring_func == "sigmoid":
                    n += mo.num_experts  # selection bias
                n += mo.n_held * 3 * d * mo.d_expert
                if mo.num_shared:
                    n += mo.num_shared * 3 * d * (mo.d_shared or mo.d_expert)
            elif ff > 0:
                mult = 3 if self.mlp_kind == "swiglu" else 2
                n += mult * d * ff
        return int(n)

    def active_param_count(self) -> int:
        """Activated params per token (MoE: top_k + shared experts only)."""
        if self.moe is None:
            return self.param_count()
        mo = self.moe
        full_moe = mo.n_held * 3 * self.d_model * mo.d_expert
        act_moe = mo.top_k * 3 * self.d_model * mo.d_expert
        n_moe_layers = sum(1 for k in self.layer_kinds() if k.moe)
        return int(self.param_count() - n_moe_layers * (full_moe - act_moe))
