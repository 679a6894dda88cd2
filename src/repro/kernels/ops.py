"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) every kernel executes with ``interpret=True`` —
the kernel body runs in Python against the same BlockSpec tiling it would
use on TPU. On a real TPU backend ``interpret`` resolves to False and the
kernels compile to Mosaic.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import expert_ffn as _experts
from repro.kernels import flash_attention as _flash
from repro.kernels import paged_attention as _paged
from repro.kernels import ssd_scan as _ssd
from repro.kernels import step_score as _score


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    blk_q: int = _flash.DEFAULT_BLK_Q,
                    blk_k: int = _flash.DEFAULT_BLK_K):
    return _flash.flash_attention(q, k, v, causal=causal, window=window,
                                  scale=scale, blk_q=blk_q, blk_k=blk_k,
                                  interpret=_interpret())


def paged_attention(q, k_pool, v_pool, block_tables, cache_lens, *,
                    scale: float, k_scale=None, v_scale=None):
    return _paged.paged_attention(q, k_pool, v_pool, block_tables,
                                  cache_lens, scale=scale,
                                  k_scale=k_scale, v_scale=v_scale,
                                  interpret=_interpret())


def paged_attention_prefill(q, k_pool, v_pool, block_tables, prefix_lens,
                            num_valid, own_k, own_v, *, scale: float,
                            window: Optional[int] = None,
                            k_scale=None, v_scale=None):
    return _paged.paged_attention_prefill(
        q, k_pool, v_pool, block_tables, prefix_lens, num_valid,
        own_k, own_v, scale=scale, window=window,
        k_scale=k_scale, v_scale=v_scale, interpret=_interpret())


def paged_latent_attention(q, pool, layer, block_tables, positions, own, *,
                           scale: float, window: int, out_dim: int):
    return _paged.paged_latent_attention(
        q, pool, layer, block_tables, positions, own, scale=scale,
        window=window, out_dim=out_dim, interpret=_interpret())


def expert_ffn(x, w_gate, w_up, w_down, layer, tile_expert, n_real, *,
               tile: int):
    return _experts.expert_ffn(x, w_gate, w_up, w_down, layer, tile_expert,
                               n_real, tile=tile, interpret=_interpret())


def paged_attention_sharded(mesh, q, k_pool, v_pool, block_tables,
                            cache_lens, *, scale: float,
                            k_scale=None, v_scale=None):
    """Mesh decode: ``shard_map`` over the ("data",) trace batch with the
    pool's "model"-sharded KV heads handled shard-locally. Kernel grid
    cells are independent per (lane, kv head), so each shard runs the
    exact arithmetic of its slice of the single-device grid — the mesh
    call is bit-identical to the unsharded kernel, no collectives.
    Quantized pools add ``k_scale``/``v_scale`` [NB, page, KVH], sharded
    with the pool's KV heads on "model"."""
    from jax.sharding import PartitionSpec as P

    pool = P(None, None, "model", None)
    in_specs = [P("data", "model", None), pool, pool,
                P("data", None), P("data")]
    operands = [q, k_pool, v_pool, block_tables, cache_lens]
    if k_scale is not None:
        in_specs += [P(None, None, "model"), P(None, None, "model")]
        operands += [k_scale, v_scale]

    def local(q_, kp, vp, bt, lens, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_attention(q_, kp, vp, bt, lens, scale=scale,
                               k_scale=ks, v_scale=vs)

    return jax.shard_map(
        local, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=P("data", "model", None), check_vma=False,
    )(*operands)


def paged_attention_prefill_sharded(mesh, q, k_pool, v_pool, block_tables,
                                    prefix_lens, num_valid, own_k, own_v, *,
                                    scale: float,
                                    window: Optional[int] = None,
                                    k_scale=None, v_scale=None):
    """Mesh chunked prefill. Chunk jobs run one prompt at a time (batch
    1), so only the "model" axis does real work (heads shard-local);
    the batch-1 operands replicate over "data" and every data shard
    computes the same tile. Quantized pools add ``k_scale``/``v_scale``
    [NB, page, KVH] sharded with the KV heads on "model"."""
    from jax.sharding import PartitionSpec as P

    head = P(None, None, "model", None)
    pool = P(None, None, "model", None)
    in_specs = [head, pool, pool, P(None, None), P(None), P(None),
                head, head]
    operands = [q, k_pool, v_pool, block_tables, prefix_lens, num_valid,
                own_k, own_v]
    if k_scale is not None:
        in_specs += [P(None, None, "model"), P(None, None, "model")]
        operands += [k_scale, v_scale]

    def local(q_, kp, vp, bt, pls, nv, ok, ov, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_attention_prefill(q_, kp, vp, bt, pls, nv, ok, ov,
                                       scale=scale, window=window,
                                       k_scale=ks, v_scale=vs)

    return jax.shard_map(
        local, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=head, check_vma=False,
    )(*operands)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128, head_group: int = 4,
             initial_state=None):
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                         head_group=head_group,
                         initial_state=initial_state,
                         interpret=_interpret())


def step_score(hidden, w1, b1, w2, b2, *, blk_b: int = _score.DEFAULT_BLK_B):
    return _score.step_score(hidden, w1, b1, w2, b2, blk_b=blk_b,
                             interpret=_interpret())


def step_score_params(hidden, params):
    """Convenience: scorer params dict -> fused kernel call."""
    return step_score(hidden, params["w1"], params["b1"],
                      params["w2"], params["b2"])
