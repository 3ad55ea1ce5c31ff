"""DeepSeek-V3-671B [arXiv:2412.19437; huggingface.co/deepseek-ai/
DeepSeek-V3 config.json]: MLA + 1 shared / 256 routed top-8 MoE.

First 3 layers dense (d_ff=18432), remaining 58 MoE (d_expert=2048).  The
router is the published one: sigmoid scores, an ``e_score_correction_bias``
that only selects (``noaux_tc``), 8 groups of which the best 4 are kept,
top-8 renormalised and scaled by 2.5.  YaRN rope (factor 40 over the
original 4096 positions, beta_fast 32, beta_slow 1, mscale =
mscale_all_dim = 1, theta 10000), so the attention scale is
192^-0.5 x (0.1 ln 40 + 1)^2.  MLA's compressed KV cache (kv_lora 512 +
rope 64 per token) is the decode cache -- the absorbed-matrix decode path
is implemented.  MTP head omitted (a training-objective add-on, unused at
inference unless it drafts for speculation).
"""
from repro.models.config import (MLAConfig, ModelConfig, MoEConfig,
                                 RopeScaling)


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v3-671b", family="moe",
        d_model=7168, num_heads=128, num_kv_heads=128, head_dim=128,
        d_ff=18432, vocab_size=129280,
        segments=((("attn.mla",), 3), (("attn.mla.moe",), 58)),
        mlp_kind="swiglu", tie_embeddings=False,
        moe=MoEConfig(num_experts=256, top_k=8, d_expert=2048,
                      num_shared=1, d_shared=2048, scoring_func="sigmoid",
                      n_group=8, topk_group=4, routed_scaling_factor=2.5),
        mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512,
                      qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
                      rope_scaling=RopeScaling(
                          factor=40.0, original_max_position_embeddings=4096,
                          beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                          mscale_all_dim=1.0)),
        moe_impl="shard_map", rope_theta=10_000.0, max_seq_len=163840)
