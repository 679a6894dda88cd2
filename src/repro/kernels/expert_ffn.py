"""The held experts' SwiGLU over their routed rows, as one Pallas kernel.

The dropless MoE layer (``models.layers.moe_dropless``) lays out the
(token, expert) pairs that fall on this chip's experts as rows grouped by
expert, each group starting on a row tile (``_pair_layout``). One grid
step computes one row tile: its expert's ``w_gate``/``w_up``/``w_down``
blocks ``[D, F]``/``[F, D]``, chosen through the scalar-prefetched tile ->
expert map, stay in VMEM while the next tiles of the same expert run, so
each held expert's weights are read from HBM once per call, and an expert
no pair chose is never read. Tiles past the last real one are skipped:
they keep the last real expert's weights (no fetch) and write a dummy
block past the output. At decode a held expert sees a dozen rows, so the
kernel is bound by the weights' DMA.

The weights stay stacked over the MoE layers ``[Lm, Eh, ...]`` and the
layer is a scalar-prefetched index, so the layer scan hands the kernel
the parameter buffers themselves and no layer's expert weights are
copied to feed it.

Numerics: the same roundings as the XLA SwiGLU (``layers.swiglu``) on
bf16 operands: each matmul accumulates in f32 and rounds to bf16, silu in
f32 rounded to bf16, the gated product rounded to bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _expert_ffn_kernel(layer_ref, tile_expert_ref, n_real_ref, x_ref,
                       wg_ref, wu_ref, wd_ref, o_ref):
    del layer_ref, tile_expert_ref
    f32 = jnp.float32

    @pl.when(pl.program_id(0) < n_real_ref[0])
    def _compute():
        x = x_ref[...]
        dt = x.dtype
        g = jnp.dot(x, wg_ref[...], preferred_element_type=f32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=f32)
        g = g.astype(dt).astype(f32)
        u = u.astype(dt).astype(f32)
        act = (jax.nn.silu(g).astype(dt).astype(f32) * u).astype(dt)
        o_ref[...] = jnp.dot(act, wd_ref[...],
                             preferred_element_type=f32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def expert_ffn(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
               w_down: jax.Array, layer: jax.Array, tile_expert: jax.Array,
               n_real: jax.Array, *, tile: int,
               interpret: bool = False) -> jax.Array:
    """x [n_tiles * tile, D] rows grouped by held expert; w_gate/w_up
    [Lm, Eh, D, F], w_down [Lm, Eh, F, D]; layer [] or [1] int32;
    tile_expert [n_tiles] int32, the held expert of each row tile (tiles
    past the last real one repeat its expert); n_real [1] int32, the
    tiles that hold a row. Returns [n_tiles * tile, D]; rows of tiles
    past ``n_real`` are not written."""
    rows, D = x.shape
    F = w_gate.shape[-1]
    n_tiles = rows // tile
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)

    def x_map(i, lay, te, nr):
        return jnp.maximum(jnp.minimum(i, nr[0] - 1), 0), 0

    def w_map(i, lay, te, nr):
        return lay[0], te[i], 0, 0

    def o_map(i, lay, te, nr):
        return jnp.where(i < nr[0], i, n_tiles), 0

    w_bytes = 3 * D * F * w_gate.dtype.itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((tile, D), x_map),
                  pl.BlockSpec((None, None, D, F), w_map),
                  pl.BlockSpec((None, None, D, F), w_map),
                  pl.BlockSpec((None, None, F, D), w_map)],
        out_specs=pl.BlockSpec((tile, D), o_map),
    )
    out = pl.pallas_call(
        _expert_ffn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(((n_tiles + 1) * tile, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # two buffers of the three weight blocks, and room for the
            # row tiles and the [tile, F] intermediates
            vmem_limit_bytes=2 * w_bytes + 16 * 1024 * 1024),
        interpret=interpret,
        name="expert_ffn",
    )(layer, tile_expert, n_real, x, w_gate, w_up, w_down)
    return out[:rows]
