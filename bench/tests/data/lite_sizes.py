"""The `launch.consensus4` cell's configuration file at a registry
config's small sizes, for the CPU tests of the plain DeepSeek-V2-Lite."""

import json
import pathlib

CONFIG = pathlib.Path(__file__).resolve().parents[2] / "configs" / \
    "deepseek_v2_lite_6l_ep8_pod4.json"


def bench_cfg(m, batch=2, seq=32) -> dict:
    """The cell's configuration file at the sizes of model config `m`."""
    cfg = json.loads(CONFIG.read_text())
    cfg.update(
        hidden_size=m.d_model, vocab_size=m.vocab_size,
        num_attention_heads=m.num_heads, qk_nope_head_dim=m.hd,
        qk_rope_head_dim=m.mla_rope_head_dim, v_head_dim=m.mla_v_head_dim,
        kv_lora_rank=m.mla_kv_lora, intermediate_size=m.d_ff,
        moe_intermediate_size=m.moe_d_ff, n_routed_experts=m.moe_experts,
        num_experts_per_tok=m.moe_top_k, n_shared_experts=m.moe_shared,
        num_hidden_layers=m.num_layers, first_k_dense_replace=len(m.prologue),
        norm_topk_prob=m.moe_norm_topk,
        aux_loss_alpha=m.moe_seq_aux)
    cfg["rope_scaling"] = dict(
        cfg["rope_scaling"],
        original_max_position_embeddings=m.rope_original_max_positions)
    cfg["deployment"] = dict(cfg["deployment"],
                             router_experts=m.router_width,
                             held_experts_from=m.moe_expert_offset)
    cfg["problem"] = {"kind": "lm", "params": {
        "arch": "deepseek-v2-lite", "variant": "smoke_ep2",
        "batch_per_node": batch, "seq_len": seq}}
    return cfg
