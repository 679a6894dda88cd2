"""Fused STEP scorer (2-layer MLP + sigmoid) as a Pallas TPU kernel.

The scorer runs inside the decode step on every token of the decode batch
(scores are consumed only at "\n\n" boundaries, but the fused evaluation
is branch-free and costs < 1e-6 of a model step — paper Appendix D). Fusing
it into one kernel keeps the hidden states in VMEM: the [B, D] decode-batch
hiddens never round-trip to HBM between the two matmuls.

Tiling: one grid row per batch block; weights [D, 512] + [512, 1] are
small enough (< 6 MB for D = 2560 in fp32) to live fully in VMEM and are
re-fetched per block — hardware-aligned (512 and D multiples of 128; the
batch block is padded to 8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLK_B = 128


def _scorer_kernel(h_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref):
    h = h_ref[...].astype(jnp.float32)          # [blk_b, D]
    z = jax.lax.dot_general(
        h, w1_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b1_ref[...][None, :]
    z = jnp.maximum(z, 0.0)                     # ReLU
    logit = jax.lax.dot_general(
        z, w2_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b2_ref[...][None, :]
    o_ref[...] = jax.nn.sigmoid(logit)          # [blk_b, 1]


@functools.partial(jax.jit, static_argnames=("blk_b", "interpret"))
def step_score(hidden: jax.Array, w1: jax.Array, b1: jax.Array,
               w2: jax.Array, b2: jax.Array, *,
               blk_b: int = DEFAULT_BLK_B,
               interpret: bool = False) -> jax.Array:
    """hidden [B, D] -> correctness scores [B] in [0, 1]."""
    B, D = hidden.shape
    Hd = w1.shape[1]
    blk_b = min(blk_b, B)
    pad = (-B) % blk_b
    h = jnp.pad(hidden, ((0, pad), (0, 0))) if pad else hidden
    nb = h.shape[0] // blk_b

    out = pl.pallas_call(
        _scorer_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((blk_b, D), lambda i: (i, 0)),
            pl.BlockSpec((D, Hd), lambda i: (0, 0)),
            pl.BlockSpec((Hd,), lambda i: (0,)),
            pl.BlockSpec((Hd, 1), lambda i: (0, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((blk_b, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((h.shape[0], 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(h, w1, b1, w2, b2)
    return out[:B, 0]
