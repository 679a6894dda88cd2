"""Mamba2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

The SSD insight (Dao & Gu, arXiv:2405.21060) is that the selective-SSM
recurrence equals a semiseparable matmul: split the sequence into chunks,
do the quadratic part within a chunk on the MXU, and carry a [H, P, N]
state across chunks. That maps perfectly onto a TPU Pallas grid:

  grid = (batch*head_groups, num_chunks) — the chunk dimension is the
  sequential ("arbitrary") one; the running state lives in VMEM scratch
  across grid steps, exactly like flash attention's online-softmax stats.

Per chunk (l = chunk len, G = heads per block, P = head dim, N = state):
  1. dA cumsum over the chunk               [G, l]
  2. intra-chunk:  (C B^T ∘ L-decay) dt x   — two [l,l]x[l,·] MXU matmuls
  3. carry-in:     C h_prev (decayed)       — [l,N]x[N,P] matmul
  4. state update: h = h*decay_l + (decay-weighted B)^T (dt x)

VMEM per step: l*(P+2N+G) + G*P*N floats; at l=128, P=64, N=128, G=4
that is ~0.4 MB — comfortably inside 16 MB, MXU dims multiple of 128
where it matters ([l,l] and [l,N] matmuls).

Heads are processed in groups of G per grid row (all sharing Bm/Cm since
ngroups=1 in Mamba2), so the B/C loads amortize across the group.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, y_ref, h_ref, h_scratch,
                *, chunk: int, heads: int, num_chunks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    x = x_ref[0].astype(jnp.float32)     # [l, G, P]
    dt = dt_ref[0].astype(jnp.float32)   # [l, G]
    A = A_ref[0].astype(jnp.float32)     # [G]
    Bm = B_ref[0].astype(jnp.float32)    # [l, N]
    Cm = C_ref[0].astype(jnp.float32)    # [l, N]
    l = x.shape[0]

    dA = dt * A[None, :]                          # [l, G]
    dA_cs = jnp.cumsum(dA, axis=0)                # inclusive cumsum [l, G]

    # C B^T once for all heads in the group: [l, l]
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    tri = row >= col

    ys = []
    for g in range(heads):  # static unroll over the head group
        cs_g = dA_cs[:, g]                          # [l]
        # L[i,j] = exp(cs_i - cs_j) for j<=i  (segment decay)
        L = jnp.exp(cs_g[:, None] - cs_g[None, :])
        L = jnp.where(tri, L, 0.0)
        scores = cb * L                             # [l, l]
        dtx = dt[:, g:g + 1] * x[:, g, :]           # [l, P]
        y_diag = jax.lax.dot_general(
            scores, dtx, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [l, P]

        # carry-in from previous chunks: y_off = (C * exp(cs)) @ h_prev^T
        h_prev = h_scratch[g]                       # [P, N]
        c_dec = Cm * jnp.exp(cs_g)[:, None]         # [l, N]
        y_off = jax.lax.dot_general(
            c_dec, h_prev, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # [l, P]
        ys.append(y_diag + y_off)

        # state update: h_new = h_prev * exp(cs_last)
        #   + sum_j exp(cs_last - cs_j) dt_j x_j B_j^T
        decay_states = jnp.exp(cs_g[-1] - cs_g)     # [l]
        bw = Bm * decay_states[:, None]             # [l, N]
        h_inc = jax.lax.dot_general(
            dtx, bw, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [P, N]
        h_scratch[g] = h_prev * jnp.exp(cs_g[-1]) + h_inc

    y_ref[0, ...] = jnp.stack(ys, axis=1).astype(y_ref.dtype)  # [l, G, P]

    @pl.when(c == num_chunks - 1)
    def _emit_state():
        h_ref[0, ...] = h_scratch[...]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "head_group", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array,
             Bm: jax.Array, Cm: jax.Array, *, chunk: int = 128,
             head_group: int = 4,
             initial_state: Optional[jax.Array] = None,
             interpret: bool = False):
    """x [B,S,H,P]; dt [B,S,H]; A [H]; Bm/Cm [B,S,N] (ngroups=1).

    Returns (y [B,S,H,P], final_state [B,H,P,N]).
    ``initial_state`` is unsupported by the kernel path (decode uses the
    single-step recurrence); it must be None.
    """
    assert initial_state is None, "kernel path starts from zero state"
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    G = min(head_group, H)
    while H % G:
        G -= 1
    HG = H // G
    nc = S // chunk

    # regroup heads: [B, S, HG, G, P] -> [B*HG, S, G, P]
    xg = x.reshape(B, S, HG, G, P).transpose(0, 2, 1, 3, 4) \
        .reshape(B * HG, S, G, P)
    dtg = dt.reshape(B, S, HG, G).transpose(0, 2, 1, 3).reshape(B * HG, S, G)
    Ag = jnp.broadcast_to(A.reshape(HG, G)[None], (B, HG, G)) \
        .reshape(B * HG, G)
    Bg = jnp.broadcast_to(Bm[:, None], (B, HG, S, N)).reshape(B * HG, S, N)
    Cg = jnp.broadcast_to(Cm[:, None], (B, HG, S, N)).reshape(B * HG, S, N)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, heads=G,
                               num_chunks=nc)

    y, h_final = pl.pallas_call(
        kernel,
        grid=(B * HG, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, G, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, chunk, G), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, G), lambda b, c: (b, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, G, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, G, P, N), lambda b, c: (b, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * HG, S, G, P), x.dtype),
            jax.ShapeDtypeStruct((B * HG, G, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((G, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xg, dtg, Ag, Bg, Cg)

    y = y.reshape(B, HG, S, G, P).transpose(0, 2, 1, 3, 4).reshape(B, S, H, P)
    h = h_final.reshape(B, HG, G, P, N).reshape(B, H, P, N)
    return y, h
