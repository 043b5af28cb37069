"""deepseek-v2-lite [moe] -- 27L d_model=2048 16H vocab=102400; MLA with no
q-LoRA (kv_lora=512, nope/rope/v head dims 128/64/128) and YaRN rope
(factor 40 over 4096 positions, mscale = mscale_all_dim = 0.707); first
layer dense (d_ff 10944), then MoE: 64 routed experts of width 1408, top-6
softmax gates left unnormalised (norm_topk_prob false, routed scaling 1),
2 shared experts; the sequence-wise balance loss at alpha 0.001.
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json]

`EP8` is one chip's share of the model trained with expert parallelism
over 8 chips per layer: the router keeps its 64 outputs and top-6, the
chip holds 8 of the experts (the first eight) and an eighth of the
vocabulary (12,800 rows), and the depth is cut to the dense layer and 5
MoE layers, the stage one chip holds in a pipeline. Every width is the
published one.
"""

import dataclasses

from repro.configs.shapes import lm_shapes
from repro.models.common import ModelConfig

FULL = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    d_model=2048, vocab_size=102400,
    prologue=("mla",),
    superblock=("mla_moe",), n_super=26,
    num_heads=16, num_kv_heads=16, head_dim=128,
    d_ff=10944, mlp_act="swiglu",
    moe_experts=64, moe_top_k=6, moe_shared=2, moe_d_ff=1408,
    moe_capacity_factor=0.0, moe_norm_topk=False, moe_routed_scale=1.0,
    moe_seq_aux=0.001,
    mla_kv_lora=512, mla_q_lora=0, mla_rope_head_dim=64,
    mla_v_head_dim=128,
    rope_theta=10000.0, rope_factor=40.0, rope_beta_fast=32.0,
    rope_beta_slow=1.0, rope_mscale_all_dim=0.707,
    rope_original_max_positions=4096,
)

EP8 = dataclasses.replace(
    FULL, name="deepseek-v2-lite-ep8",
    vocab_size=FULL.vocab_size // 8, n_super=5,
    moe_experts=8, moe_experts_total=64, moe_expert_offset=0)

SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke", family="moe",
    d_model=64, vocab_size=256,
    prologue=("mla",),
    superblock=("mla_moe",), n_super=2,
    num_heads=4, num_kv_heads=4, head_dim=16,
    d_ff=128, mlp_act="swiglu",
    moe_experts=8, moe_top_k=3, moe_shared=2, moe_d_ff=32,
    moe_capacity_factor=0.0, moe_norm_topk=False, moe_routed_scale=1.0,
    moe_seq_aux=0.001,
    mla_kv_lora=32, mla_q_lora=0, mla_rope_head_dim=8,
    mla_v_head_dim=16,
    rope_theta=10000.0, rope_factor=40.0, rope_beta_fast=32.0,
    rope_beta_slow=1.0, rope_mscale_all_dim=0.707,
    rope_original_max_positions=32,
)

#: SMOKE's share on one of two chips: 4 of its 8 experts
SMOKE_EP2 = dataclasses.replace(
    SMOKE, name="deepseek-v2-lite-smoke-ep2",
    moe_experts=4, moe_experts_total=8, moe_expert_offset=0)

#: variants beside "full" and "smoke", by the name a spec gives
VARIANTS = {"ep8": EP8, "smoke_ep2": SMOKE_EP2}

SHAPES = lm_shapes(long_ok=False)
