"""Kanana-2-30B-A3B [moe] -- DeepSeek-V3 block: 48L d_model=2048, MLA
(32 heads, kv_lora_rank 512, qk 128+64 RoPE, v 128, no query LoRA,
interleaved RoPE theta 1e6), first layer dense (SwiGLU 6144), then 128
routed experts of width 768 (sigmoid scores + selection bias, top-6,
renormalised, x2.448) and 2 shared experts (one SwiGLU of 1536);
vocab=128256, untied head
[hf:kakaocorp/kanana-2-30b-a3b-instruct-2601]."""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="kanana-2-30b-a3b",
    family="moe",
    n_layers=48,
    d_model=2048,
    n_heads=32,
    n_kv_heads=32,
    d_ff=6144,
    vocab_size=128256,
    head_dim=64,  # the published head_dim (= qk_rope_head_dim); MLA reads its own widths
    rope_theta=1e6,
    norm_eps=1e-6,
    n_experts=128,
    experts_per_token=6,
    shared_expert=True,
    router_aux_coef=0.0,
    moe_d_ff=768,
    shared_d_ff=1536,
    n_experts_held=128,
    score_func="sigmoid",
    routed_scaling=2.448,
    first_dense_layers=1,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    source="hf:kakaocorp/kanana-2-30b-a3b-instruct-2601",
)


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="kanana-2-30b-a3b-smoke",
        family="moe",
        n_layers=2,
        d_model=128,
        n_heads=4,
        n_kv_heads=4,
        d_ff=256,
        vocab_size=512,
        head_dim=16,
        rope_theta=1e6,
        norm_eps=1e-6,
        n_experts=4,
        experts_per_token=2,
        shared_expert=True,
        router_aux_coef=0.0,
        moe_d_ff=64,
        shared_d_ff=128,
        n_experts_held=2,
        first_expert_held=1,
        score_func="sigmoid",
        routed_scaling=2.448,
        first_dense_layers=1,
        kv_lora_rank=64,
        qk_nope_head_dim=32,
        qk_rope_head_dim=16,
        v_head_dim=32,
        source="hf:kakaocorp/kanana-2-30b-a3b-instruct-2601",
    )
