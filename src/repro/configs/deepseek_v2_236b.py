"""DeepSeek-V2 236B [arXiv:2405.04434] — MoE with multi-head latent attention
(MLA, kv_lora_rank=512, q_lora_rank=1536), one leading dense layer, then
2 shared + 160 routed experts, top-6 by group-limited greedy routing, and
YaRN RoPE (hf deepseek-ai/DeepSeek-V2 config.json)."""

from .base import MLAConfig, MoEConfig, ModelConfig, RopeScaling


def full_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-236b",
        family="moe",
        n_layers=60,
        d_model=5120,
        n_heads=128,
        n_kv_heads=128,              # MLA: KV heads = Q heads post-expansion
        d_ff=0,                      # no parallel dense branch in MoE layers
        vocab=102400,
        rope_theta=10000.0,
        rope_scaling=RopeScaling(
            factor=40.0, original_max_position_embeddings=4096,
            beta_fast=32.0, beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707,
        ),
        norm="rmsnorm",
        activation="silu",
        mla=MLAConfig(
            kv_lora_rank=512,
            q_lora_rank=1536,
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
        ),
        moe=MoEConfig(
            num_experts=160,
            top_k=6,
            d_ff_expert=1536,
            num_shared_experts=2,
            d_ff_shared=1536,
            n_group=8,
            topk_group=3,
            norm_topk_prob=False,
            routed_scaling_factor=16.0,
        ),
        first_dense_layers=1,
        first_dense_d_ff=12288,
        source="arXiv:2405.04434",
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-smoke",
        family="moe",
        n_layers=2,                  # one dense layer, one MoE layer
        d_model=128,
        n_heads=4,
        n_kv_heads=4,
        d_ff=0,
        vocab=512,
        rope_scaling=RopeScaling(
            factor=40.0, original_max_position_embeddings=4096,
            beta_fast=32.0, beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707,
        ),
        norm="rmsnorm",
        activation="silu",
        mla=MLAConfig(
            kv_lora_rank=32,
            q_lora_rank=48,
            qk_nope_head_dim=32,
            qk_rope_head_dim=16,
            v_head_dim=32,
        ),
        moe=MoEConfig(
            num_experts=8, top_k=3, d_ff_expert=64,
            num_shared_experts=2, d_ff_shared=64,
            n_group=4, topk_group=2, norm_topk_prob=False,
            routed_scaling_factor=16.0,
        ),
        first_dense_layers=1,
        first_dense_d_ff=256,
        source="arXiv:2405.04434",
    )
