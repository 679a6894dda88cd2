"""deepseek-v2-lite [moe] — MLA without q-LoRA (kv_lora 512), YaRN rope,
one leading dense layer, then 26 layers of 64 routed experts (top-6 by
softmax, not renormalised) plus 2 shared experts
[https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json].

27L d_model=2048 16H, dense d_ff=10944, expert d_ff=1408, vocab=102400,
untied head. ``full_config`` holds every routed expert; the benchmark
serves one chip's expert-parallel share (``experts_held``).
"""
from repro.configs.base import ModelConfig


def full_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        arch_type="moe",
        source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,     # MLA: every head reads the shared latent
        head_dim=128,
        d_ff=10944,
        vocab_size=102400,
        use_mla=True,
        kv_lora_rank=512,
        q_lora_rank=0,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=10000.0,
        rope_scaling_factor=40.0,
        rope_original_max_positions=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
        num_experts=64,
        num_experts_per_tok=6,
        num_shared_experts=2,
        moe_d_ff=1408,
        first_k_dense=1,
        norm_topk_prob=False,
        routed_scaling_factor=1.0,
        norm_eps=1e-6,
        tie_embeddings=False,
    )


def smoke_config() -> ModelConfig:
    """Same kinds of shape at CPU size: no q-LoRA, YaRN, one dense layer
    then one MoE layer, a share of 2 of the router's 4 experts."""
    return ModelConfig(
        name="deepseek-v2-lite-smoke",
        arch_type="moe",
        source="reduced variant of DeepSeek-V2-Lite",
        num_layers=2,
        d_model=256,
        num_heads=4,
        num_kv_heads=4,
        head_dim=64,
        d_ff=384,
        vocab_size=512,
        use_mla=True,
        kv_lora_rank=64,
        q_lora_rank=0,
        qk_nope_head_dim=64,
        qk_rope_head_dim=32,
        v_head_dim=64,
        rope_theta=10000.0,
        rope_scaling_factor=40.0,
        rope_original_max_positions=128,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
        num_experts=4,
        num_experts_per_tok=2,
        num_shared_experts=1,
        moe_d_ff=128,
        first_k_dense=1,
        norm_topk_prob=False,
        experts_held=2,
    )
