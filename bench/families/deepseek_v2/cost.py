"""Operations and bytes of the serving engine's device calls for
DeepSeek-V2, worked out from shapes and context lengths alone.

Model FLOPs count the matrix multiplications a token needs (two per
multiply-add), as the program computes them: the attention projections
(no q-LoRA; W_uk folded into the query and W_uv into the output, the
absorbed form every decode and chunk step runs), attention over the
token's context in the latent space (a score over the whole kv_lora +
rope entry and a value over the kv_lora latent, per head and key), the
leading dense layers' MLP, in each MoE layer the router, the shared
experts and the expected routed pairs that fall on the held experts
(top-k times experts_held / num_experts per token), and the output head
over the published vocabulary. Norms, rotary embedding, softmax and
sampling are left out.

Bytes count what a call must move through HBM at the least: every weight
a decode iteration reads once per iteration (all but the embedding table,
of which only the tokens' rows are read; every held expert's weights,
since at 128 lanes each is chosen), and every live latent page. A page is
read whole (the kernel DMAs whole pages), at the pool's entry width
(``latent_width``: kv_lora + rope zero-padded to 128 lanes, as the chip
holds it), so a context of n keys costs ceil((n - 1) / page) pages: the
current token's own entry comes with the query.
"""
from __future__ import annotations

import math

# the kernels the trace reduction times per program
KERNELS = ("paged_latent_attention", "expert_ffn")

BF16 = 2


def latent_width(m: dict) -> int:
    return -(-(m["kv_lora_rank"] + m["qk_rope_head_dim"]) // 128) * 128


def attn_params(m: dict) -> int:
    D, H = m["d_model"], m["num_heads"]
    nd, rd, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    L = m["kv_lora_rank"]
    return (D * H * (nd + rd) + D * (L + rd) + L * H * nd + L * H * vd
            + H * vd * D)


def expert_params(m: dict) -> int:
    """One routed expert's SwiGLU."""
    return 3 * m["d_model"] * m["moe_d_ff"]


def held_pairs_per_token(m: dict) -> float:
    return m["num_experts_per_tok"] * m["experts_held"] / m["num_experts"]


def matmul_params(m: dict) -> float:
    """Weights a token multiplies by (expected, for the routed experts),
    output head over the real vocab."""
    D, K = m["d_model"], m["first_k_dense"]
    moe = (m["d_model"] * m["num_experts"]
           + m["num_shared_experts"] * expert_params(m)
           + held_pairs_per_token(m) * expert_params(m))
    return (m["num_layers"] * attn_params(m) + K * 3 * D * m["d_ff"]
            + (m["num_layers"] - K) * moe + D * m["vocab_size"])


def attn_flops(m: dict, n_keys: int) -> int:
    """Latent attention FLOPs of one query token over ``n_keys`` keys, all
    layers: per head and key a score over kv_lora + rope and a value over
    kv_lora."""
    L, rd = m["kv_lora_rank"], m["qk_rope_head_dim"]
    return 2 * m["num_layers"] * m["num_heads"] * (2 * L + rd) * n_keys


def token_flops(m: dict, n_keys: int) -> float:
    return 2 * matmul_params(m) + attn_flops(m, n_keys)


def decode_flops(m: dict, lens) -> float:
    return sum(token_flops(m, n) for n in lens)


def prefill_flops(m: dict, start: int, n_tokens: int) -> float:
    keys = n_tokens * start + n_tokens * (n_tokens + 1) // 2
    return 2 * matmul_params(m) * n_tokens + attn_flops(m, 1) * keys


def page_bytes(m: dict, page: int, kv_bytes: int = BF16) -> int:
    """One latent page, one layer."""
    return page * latent_width(m) * kv_bytes


def pages(n_tokens: int, page: int) -> int:
    return math.ceil(n_tokens / page) if n_tokens > 0 else 0


def embed_bytes(m: dict) -> int:
    return (-(-m["vocab_size"] // 256) * 256) * m["d_model"] * BF16


def decode_call_bytes(m: dict, weight_bytes: int, lens, page: int,
                      steps: int = 1) -> int:
    """Least HBM traffic of one decode call of ``steps`` iterations: every
    weight but the embedding table once per iteration (every held
    expert's included), and for every emitted token (``lens`` holds one
    key count per token) its lane's live latent pages and its new entry
    in every layer."""
    kv = sum(pages(n - 1, page) for n in lens) * page_bytes(m, page)
    new = len(lens) * latent_width(m) * BF16
    return (steps * (weight_bytes - embed_bytes(m))
            + m["num_layers"] * (kv + new))


def latent_decode_kernel(m: dict, lens, page: int) -> tuple:
    """(flops, bytes) of the latent kernel in one layer, summed over the
    decode iterations that emitted ``lens`` (one key count per token):
    each token's heads against its own entry and its lane's live pages."""
    H, W = m["num_heads"], latent_width(m)
    L, rd = m["kv_lora_rank"], m["qk_rope_head_dim"]
    flops = sum(2 * H * (2 * L + rd) * n for n in lens)
    kv = sum(pages(n - 1, page) for n in lens) * page_bytes(m, page)
    qo = len(lens) * (H * W + W + H * L) * BF16
    return flops, kv + qo


def expert_ffn_kernel(m: dict, tokens: float) -> tuple:
    """(flops, bytes) of the expert FFN in one layer of one decode
    iteration of ``tokens`` tokens: the expected routed pairs on the held
    experts through a SwiGLU each, and every held expert's weights read
    once plus the pairs' rows in and out."""
    pairs = tokens * held_pairs_per_token(m)
    flops = 2 * pairs * expert_params(m)
    by = (m["experts_held"] * expert_params(m) * BF16
          + 2 * pairs * m["d_model"] * BF16)
    return flops, by
