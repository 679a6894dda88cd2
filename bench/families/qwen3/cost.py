"""Operations and bytes of the serving engine's device calls, worked out
from shapes and context lengths alone.

Model FLOPs count the matrix multiplications a token needs (two per
multiply-add): the attention and MLP projections, the output head over
the published vocabulary, and attention over the token's context
(QK^T and PV, 4 * H * hd per key per layer). Norms, rotary embedding,
softmax and sampling are left out, as is usual for model FLOPs.

Bytes count what a call must move through HBM at the least: every weight
once per decode iteration (a call of K iterations reads them K times),
and every live KV page it reads once per iteration. A page is read whole
(the kernel DMAs whole pages), so a context of n tokens costs
ceil(n / page) pages.
"""
from __future__ import annotations

import math

# the kernels the trace reduction times per program
KERNELS = ("paged_attention", "paged_attention_prefill")


def matmul_params(m: dict) -> int:
    """Weights a token multiplies by, output head over the real vocab."""
    D, H, KVH, hd, F = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                        m["head_dim"], m["d_ff"])
    per_layer = D * H * hd + 2 * D * KVH * hd + H * hd * D + 3 * D * F
    return m["num_layers"] * per_layer + D * m["vocab_size"]


def attn_flops(m: dict, n_keys: int) -> int:
    """Attention FLOPs of one query token over ``n_keys`` keys, all
    layers."""
    return 4 * m["num_layers"] * m["num_heads"] * m["head_dim"] * n_keys


def token_flops(m: dict, n_keys: int) -> int:
    """Model FLOPs of one token that attends over ``n_keys`` keys
    (itself included)."""
    return 2 * matmul_params(m) + attn_flops(m, n_keys)


def decode_flops(m: dict, lens) -> int:
    """One decode iteration; ``lens`` are the running lanes' key counts
    (position of the input token + 1)."""
    return sum(token_flops(m, n) for n in lens)


def prefill_flops(m: dict, start: int, n_tokens: int) -> int:
    """One prefill chunk of ``n_tokens`` real tokens at positions
    ``start`` .. ``start + n_tokens - 1``."""
    keys = n_tokens * start + n_tokens * (n_tokens + 1) // 2
    return 2 * matmul_params(m) * n_tokens + attn_flops(m, 1) * keys


def page_bytes(m: dict, page: int, kv_bytes: int = 2) -> int:
    """K and V of one page, one layer."""
    return 2 * page * m["num_kv_heads"] * m["head_dim"] * kv_bytes


def pages(n_tokens: int, page: int) -> int:
    return math.ceil(n_tokens / page) if n_tokens > 0 else 0


def decode_call_bytes(m: dict, weight_bytes: int, lens, page: int,
                      steps: int = 1) -> int:
    """Least HBM traffic of one decode call of ``steps`` iterations:
    the weights once per iteration, and for every emitted token (``lens``
    holds one key count per token) its lane's live pages in every layer
    and its one new K/V row per layer."""
    kv = sum(pages(n, page) for n in lens) * page_bytes(m, page)
    new_kv = len(lens) * 2 * m["num_kv_heads"] * m["head_dim"] * 2
    return steps * weight_bytes + m["num_layers"] * (kv + new_kv)


def paged_decode_kernel(m: dict, lens, page: int) -> tuple:
    """(flops, bytes) of the paged-attention kernel in one layer, summed
    over the decode iterations that emitted ``lens`` (one key count per
    token): each token's query heads against its lane's live pages."""
    H, hd = m["num_heads"], m["head_dim"]
    flops = sum(4 * H * hd * n for n in lens)
    kv = sum(pages(n, page) for n in lens) * page_bytes(m, page)
    qo = len(lens) * 2 * H * hd * 2
    return flops, kv + qo
