"""deepseek-v2-236b [moe] — MLA kv_lora=512, 2 shared + 160 routed top-6
[arXiv:2405.04434].

60L d_model=5120 128H d_ff(expert)=1536 vocab=102400, MoE 160e top-6, the
first layer dense (d_ff=12288), YaRN rope (factor 40), top-6 weights not
renormalised and scaled by 16. Still missing: the published model's
group-limited routing (the experts' 8 groups, top-3 groups per token);
the router here picks the top-6 of all 160.
"""
from repro.configs.base import ModelConfig


def full_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-236b",
        arch_type="moe",
        source="arXiv:2405.04434 (DeepSeek-V2)",
        num_layers=60,
        d_model=5120,
        num_heads=128,
        num_kv_heads=128,    # MLA: all heads read the shared latent
        head_dim=128,
        d_ff=12288,          # the leading dense layer
        vocab_size=102400,
        use_mla=True,
        kv_lora_rank=512,
        q_lora_rank=1536,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        num_experts=160,
        num_experts_per_tok=6,
        num_shared_experts=2,
        moe_d_ff=1536,
        first_k_dense=1,
        norm_topk_prob=False,
        routed_scaling_factor=16.0,
        rope_theta=10000.0,
        rope_scaling_factor=40.0,
        rope_original_max_positions=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-236b-smoke",
        arch_type="moe",
        source="reduced variant of arXiv:2405.04434",
        num_layers=2,
        d_model=256,
        num_heads=4,
        num_kv_heads=4,
        head_dim=64,
        d_ff=128,
        vocab_size=512,
        use_mla=True,
        kv_lora_rank=64,
        q_lora_rank=96,
        qk_nope_head_dim=64,
        qk_rope_head_dim=32,
        v_head_dim=64,
        num_experts=4,
        num_experts_per_tok=2,
        num_shared_experts=1,
        moe_d_ff=128,
)
