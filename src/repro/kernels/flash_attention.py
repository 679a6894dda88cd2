"""Flash attention (prefill hot spot) as a Pallas TPU kernel.

Blockwise online-softmax over the KV sequence. The grid is
``(batch*heads, num_q_blocks, num_kv_blocks)``; TPU grids execute
sequentially in row-major order, so the innermost (kv) dimension revisits
the same output block and carries the online-softmax statistics in VMEM
scratch — the standard TPU flash pattern (cf. jax.experimental.pallas.ops
.tpu.flash_attention).

BlockSpec tiling: q/o blocks [1, blk_q, hd], k/v blocks [1, blk_k, hd].
With blk_q = blk_k = 128 and hd <= 128 the working set is well under
16 MB VMEM and all matmul dims are MXU-aligned (multiples of 128).

Causal masking skips fully-masked kv blocks (2x FLOP saving); an optional
sliding window additionally skips blocks left of the window (what makes
``long_500k`` sub-quadratic for dense archs).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLK_Q = 128
DEFAULT_BLK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scratch, l_scratch,
                  acc_scratch, *, scale: float, causal: bool,
                  window: Optional[int], blk_q: int, blk_k: int,
                  num_kv_blocks: int):
    q_idx = pl.program_id(1)
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q_start = q_idx * blk_q
    k_start = kv_idx * blk_k

    # block-level relevance: skip blocks fully above the causal diagonal
    # or fully left of the sliding window
    relevant = jnp.bool_(True)
    if causal:
        relevant &= k_start <= q_start + blk_q - 1
    if window is not None:
        relevant &= (k_start + blk_k - 1) > (q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [blk_q, hd]
        k = k_ref[0].astype(jnp.float32)  # [blk_k, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [blk_q, blk_k]

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > (q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[...]          # [blk_q, 1]
        l_prev = l_scratch[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # [blk_q, blk_k]
        alpha = jnp.exp(m_prev - m_new)               # [blk_q, 1]
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc_scratch[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scratch[...] = m_new
        l_scratch[...] = l_new
        acc_scratch[...] = acc

    @pl.when(kv_idx == num_kv_blocks - 1)
    def _finalize():
        l = l_scratch[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, ...] = (acc_scratch[...] / safe_l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "scale", "blk_q", "blk_k",
                              "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    blk_q: int = DEFAULT_BLK_Q, blk_k: int = DEFAULT_BLK_K,
                    interpret: bool = False) -> jax.Array:
    """q/k/v [B, H, S, hd] (kv heads pre-broadcast). Returns [B, H, S, hd]."""
    B, H, S, hd = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    blk_q = min(blk_q, S)
    blk_k = min(blk_k, S)
    assert S % blk_q == 0 and S % blk_k == 0, (S, blk_q, blk_k)
    nq, nk = S // blk_q, S // blk_k

    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * H, S, hd)
    vf = v.reshape(B * H, S, hd)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        blk_q=blk_q, blk_k=blk_k, num_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd)
