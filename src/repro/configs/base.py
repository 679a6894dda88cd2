"""Configuration system for the repro framework.

Every assigned architecture is described by a single frozen `ModelConfig`.
`full_config()` in each ``configs/<arch>.py`` returns the exact published
configuration; ``smoke_config()`` returns a reduced same-family variant
(<=2 layers, d_model<=512, <=4 experts) used by CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """A single architecture description covering all 6 assigned families."""

    name: str
    arch_type: str  # dense | moe | ssm | hybrid | vlm | audio
    source: str  # citation for the config

    num_layers: int = 0
    d_model: int = 0
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab_size: int = 0

    # --- attention options -------------------------------------------------
    qk_norm: bool = False
    rope_theta: float = 10000.0
    # YaRN context extension (DeepSeek-V2's ``rope_scaling`` of type
    # "yarn"); a factor of 1.0 leaves the plain rotary embedding. The
    # rotary frequencies blend interpolated and original ones between the
    # correction dims of beta_fast / beta_slow rotations, and attention
    # scales its logits by mscale(factor, mscale_all_dim)^2.
    rope_scaling_factor: float = 1.0
    rope_original_max_positions: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    sliding_window: Optional[int] = None  # native SWA (e.g. mixtral)

    # --- MLA (DeepSeek-V2) --------------------------------------------------
    use_mla: bool = False
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- MoE -----------------------------------------------------------------
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0  # per-expert hidden dim
    # leading layers with a dense MLP of width d_ff before the MoE layers
    # (DeepSeek's first_k_dense_replace)
    first_k_dense: int = 0
    # renormalise the top-k router weights to sum to one (Mixtral); False
    # keeps the softmax probabilities over all experts (DeepSeek-V2)
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # routed experts held on this chip: experts [0, experts_held) of the
    # router's num_experts (the chip's expert-parallel share); 0 = all
    experts_held: int = 0

    # --- SSM (Mamba2 / SSD) ---------------------------------------------------
    ssm_state_size: int = 0
    ssm_num_heads: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_chunk_size: int = 256

    # --- hybrid (Zamba2): shared attention block every k mamba layers ---------
    hybrid_attn_every: int = 0  # 0 => not hybrid

    # --- encoder-decoder -------------------------------------------------------
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    encoder_seq_len: int = 0  # frontend tokens seen by the encoder

    # --- modality frontends (stubs per assignment carve-out) -------------------
    modality: str = "text"  # text | vision | audio
    num_modality_tokens: int = 0  # prepended embedding tokens (vlm)

    # --- numerics / serving -----------------------------------------------------
    dtype: str = "bfloat16"
    tie_embeddings: bool = False
    kv_block_size: int = 16  # paged KV block size (tokens)
    norm_eps: float = 1e-6

    # ------------------------------------------------------------------
    @property
    def d_inner(self) -> int:
        """Mamba2 inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        if self.ssm_num_heads:
            return self.ssm_num_heads
        return self.d_inner // self.ssm_head_dim

    @property
    def is_attention_free(self) -> bool:
        return self.arch_type == "ssm"

    @property
    def uses_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def num_experts_held(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense if self.uses_moe else 0

    @property
    def latent_width(self) -> int:
        """Width of a latent (MLA) pool entry: the compressed latent and
        the decoupled rope key, zero-padded to the 128 lanes the chip
        tiles a minor dimension by (HBM holds a 576-wide row as 640
        anyway; stored so, a page's DMA is tile-aligned)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def kv_cache_dims_per_token(self) -> int:
        """Per-layer per-token cache scalar count (drives block bytes)."""
        if self.use_mla:
            return self.latent_width
        if self.arch_type == "ssm":
            return 0
        return 2 * self.num_kv_heads * self.head_dim

    def attention_layer_ids(self) -> Tuple[int, ...]:
        """Indices of layers that carry a KV cache."""
        if self.arch_type == "ssm":
            return ()
        if self.hybrid_attn_every:
            return tuple(
                i for i in range(self.num_layers)
                if (i + 1) % self.hybrid_attn_every == 0
            )
        return tuple(range(self.num_layers))
