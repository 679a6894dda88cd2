"""Model-zoo primitive layers (pure functions, params-as-pytrees).

All matmul weights are 2-D ``[in, out]`` so tensor-parallel sharding happens
on fused dims (always divisible by the 16-way model axis); head reshapes are
internal. Norms/softmax accumulate in fp32; weights/activations are bf16.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import kv_quant as KQ


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def rope_cos_sin(positions: jax.Array, dim: int, theta: float,
                 yarn: Optional[ModelConfig] = None):
    """positions [...]; returns cos/sin [..., dim/2] in fp32.

    ``yarn`` (a config whose ``rope_scaling_factor`` exceeds 1) takes the
    YaRN frequencies of :func:`yarn_inv_freq` and scales cos/sin by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim), as
    DeepSeek-V2's rotary embedding does."""
    if yarn is None or yarn.rope_scaling_factor <= 1.0:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                    / dim))
        angles = positions.astype(jnp.float32)[..., None] * inv_freq
        return jnp.cos(angles), jnp.sin(angles)
    inv_freq = jnp.asarray(yarn_inv_freq(yarn, dim), jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    f = yarn.rope_scaling_factor
    attn = (yarn_mscale(f, yarn.yarn_mscale)
            / yarn_mscale(f, yarn.yarn_mscale_all_dim))
    return jnp.cos(angles) * attn, jnp.sin(angles) * attn


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: ModelConfig, dim: int) -> np.ndarray:
    """YaRN inverse frequencies [dim/2]: the original ones (1/theta^(2i/d))
    below the correction dim of ``yarn_beta_fast`` rotations over the
    original context, the interpolated ones (divided by the factor) above
    that of ``yarn_beta_slow``, and a linear ramp between."""
    theta, f = cfg.rope_theta, cfg.rope_scaling_factor
    orig = cfg.rope_original_max_positions
    idx = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / theta ** idx
    inter = 1.0 / (f * theta ** idx)

    def corr_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp            # 1: original frequency, 0: interpolated
    return inter * (1.0 - keep) + extra * keep


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [..., H, hd]; cos/sin broadcastable [..., 1, hd/2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# feed-forward
# ---------------------------------------------------------------------------

def swiglu(p: dict, x: jax.Array, act_spec=None) -> jax.Array:
    """p: {w_gate [D,F], w_up [D,F], w_down [F,D]}

    ``act_spec`` (serving mesh): the column-parallel gate/up outputs are
    model-sharded on F, and contracting that sharded F against the
    replicated ``w_down`` would make GSPMD partial-sum across shards —
    a float reduction whose rounding differs from the single-device
    matmul. Constraining the activation un-sharded on F first turns the
    collective into an exact all-gather and keeps the contraction
    bit-identical to one device. The input is pinned the same way: an
    unconstrained norm output feeding the column-parallel gate/up
    matmuls could get D-sharded by GSPMD, partial-summing THEIR
    contraction instead.
    """
    if act_spec is not None:
        x = jax.lax.with_sharding_constraint(x, act_spec)
    g = x @ p["w_gate"]
    u = x @ p["w_up"]
    act = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    if act_spec is not None:
        act = jax.lax.with_sharding_constraint(act, act_spec)
    return act @ p["w_down"]


# ---------------------------------------------------------------------------
# dense / GQA attention — full sequence (train & prefill)
# ---------------------------------------------------------------------------

# Above this sequence length the naive S^2 score tensor cannot be
# materialised at long sequence lengths; attention switches to the
# chunked online-softmax path (flash semantics in plain XLA).
CHUNKED_ATTN_THRESHOLD = 1024
ATTN_CHUNK = 512


def chunked_mha(q: jax.Array, k: jax.Array, v: jax.Array,
                window: Optional[int] = None,
                chunk: int = ATTN_CHUNK,
                causal: bool = True) -> jax.Array:
    """Blockwise online-softmax attention, O(S * chunk) memory.

    q/k/v [B, H, S, hd] (kv heads pre-broadcast), scaled q expected.
    The chunk body is rematerialised (jax.checkpoint) so the backward pass
    recomputes probabilities flash-attention-style instead of saving the
    [S, S] probability tensor.
    """
    B, H, S, hd = q.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nk = S // chunk
    kc = k.reshape(B, H, nk, chunk, k.shape[-1])
    vc = v.reshape(B, H, nk, chunk, v.shape[-1])
    q_pos = jnp.arange(S)

    @jax.checkpoint
    def body(carry, inp):
        m_prev, l_prev, acc = carry
        k_blk, v_blk, blk_idx = inp  # [B,H,chunk,hd] x2, scalar
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32)  # [B,H,S,chunk]
        k_pos = blk_idx * chunk + jnp.arange(chunk)
        mask = jnp.ones((S, chunk), bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > (q_pos[:, None] - window)
        s = jnp.where(mask[None, None], s, -1e30)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(mask[None, None], jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((B, H, S, 1), -1e30, jnp.float32),
            jnp.zeros((B, H, S, 1), jnp.float32),
            jnp.zeros((B, H, S, v.shape[-1]), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        body, init,
        (jnp.moveaxis(kc, 2, 0), jnp.moveaxis(vc, 2, 0), jnp.arange(nk)))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l).astype(q.dtype)


def _attn_mask(q_len: int, kv_len: int, window: Optional[int]) -> jax.Array:
    """Causal (optionally sliding-window) boolean mask [q_len, kv_len]."""
    q_pos = jnp.arange(q_len)[:, None] + (kv_len - q_len)
    k_pos = jnp.arange(kv_len)[None, :]
    mask = k_pos <= q_pos
    if window is not None:
        mask &= k_pos > (q_pos - window)
    return mask


def gqa_attention_full(p: dict, cfg: ModelConfig, x: jax.Array,
                       positions: jax.Array,
                       window: Optional[int] = None,
                       return_kv: bool = False,
                       use_kernel: bool = False,
                       act_spec=None):
    """Full-sequence GQA attention.

    p: {wq [D, H*hd], wk [D, KVH*hd], wv [D, KVH*hd], wo [H*hd, D],
        (qk_norm) q_norm [hd], k_norm [hd]}
    x: [B, S, D]; positions: [B, S] absolute positions.
    """
    B, S, D = x.shape
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if act_spec is not None:  # exact TP: see swiglu
        x = jax.lax.with_sharding_constraint(x, act_spec)
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KVH, hd)
    v = (x @ p["wv"]).reshape(B, S, KVH, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)  # [B,S,hd/2]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    if use_kernel:
        from repro.kernels import ops as kops
        group = H // KVH
        kb = jnp.repeat(k, group, axis=2)  # broadcast kv heads
        vb = jnp.repeat(v, group, axis=2)
        out = kops.flash_attention(
            q.transpose(0, 2, 1, 3), kb.transpose(0, 2, 1, 3),
            vb.transpose(0, 2, 1, 3), window=window,
            scale=1.0 / math.sqrt(hd))
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    elif S > CHUNKED_ATTN_THRESHOLD:
        group = H // KVH
        kb = jnp.repeat(k, group, axis=2)
        vb = jnp.repeat(v, group, axis=2)
        out = chunked_mha(
            q.transpose(0, 2, 1, 3) * (1.0 / math.sqrt(hd)),
            kb.transpose(0, 2, 1, 3), vb.transpose(0, 2, 1, 3),
            window=window, chunk=ATTN_CHUNK)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    else:
        group = H // KVH
        qg = q.reshape(B, S, KVH, group, hd)
        scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                            preferred_element_type=jnp.float32)
        scores *= 1.0 / math.sqrt(hd)
        mask = _attn_mask(S, S, window)
        out = _masked_softmax_pv(scores, mask[None, None, None], v,
                                 "bkgqs,bskh->bqkgh")
        out = out.astype(x.dtype).reshape(B, S, H * hd)
    if act_spec is not None:  # exact TP: gather heads before the wo
        out = jax.lax.with_sharding_constraint(out, act_spec)  # contraction
    out = out @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


# ---------------------------------------------------------------------------
# paged GQA attention — decode (one new token per sequence)
# ---------------------------------------------------------------------------

def paged_kv_update(pool: jax.Array, block_tables: jax.Array,
                    slot_positions: jax.Array, new_kv: jax.Array) -> jax.Array:
    """Write one token's K or V per sequence into the paged pool.

    pool [N_blocks, bs, KVH, hd]; block_tables [B, bp];
    slot_positions [B] (position within the cache window);
    new_kv [B, KVH, hd].
    """
    bs = pool.shape[1]
    block_idx = slot_positions // bs
    offset = slot_positions % bs
    block_ids = jnp.take_along_axis(block_tables, block_idx[:, None], axis=1)[:, 0]
    return pool.at[block_ids, offset].set(new_kv)


def paged_kv_update_quant(pool: jax.Array, scale: jax.Array,
                          block_tables: jax.Array,
                          slot_positions: jax.Array,
                          new_kv: jax.Array) -> tuple:
    """Quantized-pool variant of :func:`paged_kv_update`.

    pool [N_blocks, bs, KVH, hd] in int8/fp8 with per-(page, slot,
    KV-head) f32 ``scale`` [N_blocks, bs, KVH]. The new token [B, KVH,
    hd] is quantized against its own per-head absmax and its codes +
    scales scattered into the written slot — no other slot is touched,
    so the stored value is a pure function of the token (write paths
    commute; see ``kv_quant``). Returns (new_pool, new_scale). Dead
    lanes share the scratch block, whose content is never read
    un-masked.
    """
    bs = pool.shape[1]
    block_idx = slot_positions // bs
    offset = slot_positions % bs
    block_ids = jnp.take_along_axis(
        block_tables, block_idx[:, None], axis=1)[:, 0]
    q, ns = KQ.quantize_pages(new_kv, pool.dtype)  # [B,KVH,hd] / [B,KVH]
    return (pool.at[block_ids, offset].set(q),
            scale.at[block_ids, offset].set(ns))


def paged_chunk_update_quant(pool: jax.Array, scale: jax.Array,
                             block_tables: jax.Array, slot: jax.Array,
                             valid: jax.Array, new_vals: jax.Array) -> tuple:
    """Quantized-pool scatter for one prefill chunk.

    pool [N_blocks, bs, KVH, hd] int8/fp8; scale [N_blocks, bs, KVH]
    f32; slot [B, C]; valid [B, C]; new_vals [B, C, KVH, hd]. Each
    chunk token is quantized against its own per-head absmax and its
    codes + scales scattered into its slot (padded entries keep the old
    content, mirroring :func:`paged_chunk_update`) — earlier chunks'
    slots are never re-rounded, so chunked and one-shot prefill write
    bit-identical pool content. Returns (new_pool, new_scale).
    """
    bs = pool.shape[1]
    B, C = slot.shape
    blk, offs = slot // bs, slot % bs
    b_idx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, C))
    q, ns = KQ.quantize_pages(new_vals, pool.dtype)  # [B,C,KVH,*]
    old_q = pool[block_tables][b_idx, blk, offs]     # [B, C, KVH, hd]
    old_s = scale[block_tables][b_idx, blk, offs]    # [B, C, KVH]
    q = jnp.where(valid[..., None, None], q, old_q)
    ns = jnp.where(valid[..., None], ns, old_s)
    bid = jnp.take_along_axis(block_tables, blk, axis=1)  # [B, C]
    return (pool.at[bid, offs].set(q),
            scale.at[bid, offs].set(ns))


def _masked_softmax_pv(scores: jax.Array, mask: jax.Array,
                       v: jax.Array, pv_einsum: str) -> jax.Array:
    """Masked softmax + PV contraction, accumulated in f32, with the
    kernel's empty-row convention: rows whose mask is all-False (e.g.
    ``cache_len == 0`` dead slots) emit ZEROS instead of softmaxing the
    -1e30 fill into a uniform average over garbage KV. This is the
    numerics contract the Pallas paged kernels follow, so the dense
    fallbacks and ``use_kernel=True`` agree within reduction-order
    noise. Returns f32."""
    scores = jnp.where(mask, scores, -1e30)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(scores - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    return jnp.einsum(pv_einsum, p, v.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def paged_attention_decode(pool_k: jax.Array, pool_v: jax.Array,
                           q: jax.Array, block_tables: jax.Array,
                           cache_lens: jax.Array, scale: float,
                           use_kernel: bool = False,
                           kernel_mesh=None, k_scale=None,
                           v_scale=None) -> jax.Array:
    """Decode attention over the paged pool.

    q [B, H, hd]; pools [N_blocks, bs, KVH, hd]; block_tables [B, bp];
    cache_lens [B] number of valid tokens. Returns [B, H, hd].

    ``kernel_mesh`` (with ``use_kernel``) routes through the shard_map
    wrapper: lanes shard over "data", the pool's KV heads over "model",
    each computed shard-locally (see ``kernels.ops``).

    ``k_scale``/``v_scale`` [N_blocks, KVH] mark a quantized pool: the
    gathered pages are dequantized (f32 cast then per-page scale) before
    the score matmul — the same multiply the Pallas kernel applies in
    its online-softmax loop, keeping both read paths aligned.
    """
    if use_kernel:
        from repro.kernels import ops as kops
        if kernel_mesh is not None:
            return kops.paged_attention_sharded(
                kernel_mesh, q, pool_k, pool_v, block_tables, cache_lens,
                scale=scale, k_scale=k_scale, v_scale=v_scale)
        return kops.paged_attention(q, pool_k, pool_v, block_tables,
                                    cache_lens, scale=scale,
                                    k_scale=k_scale, v_scale=v_scale)
    B, H, hd = q.shape
    bs = pool_k.shape[1]
    KVH = pool_k.shape[2]
    bp = block_tables.shape[1]
    # gather this sequence's blocks: [B, bp, bs, KVH, hd] -> [B, S, KVH, hd]
    k = pool_k[block_tables]
    v = pool_v[block_tables]
    if k_scale is not None:
        k = KQ.dequantize_pages(k, k_scale[block_tables])
        v = KQ.dequantize_pages(v, v_scale[block_tables])
    k = k.reshape(B, bp * bs, KVH, hd)
    v = v.reshape(B, bp * bs, KVH, hd)
    group = H // KVH
    qg = q.reshape(B, KVH, group, hd)
    scores = jnp.einsum("bkgh,bskh->bkgs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(bp * bs)[None, :] < cache_lens[:, None]
    out = _masked_softmax_pv(scores, valid[:, None, None, :], v,
                             "bkgs,bskh->bkgh")
    return out.astype(q.dtype).reshape(B, H, hd)


def gqa_attention_decode(p: dict, cfg: ModelConfig, x: jax.Array,
                         positions: jax.Array, cache: dict, layer_slot: int
                         ) -> tuple:
    """One-token decode step with paged KV cache for one layer.

    x [B, 1, D]; positions [B]; cache holds k_pool/v_pool slices for THIS
    layer plus block_tables, cache_lens, window metadata. When the cache
    carries ``k_scale``/``v_scale`` the pool is quantized: writes go
    through the page-requantize path and the attention read dequantizes.
    Returns (out [B,1,D], (new_k_pool, new_v_pool)) — with the new
    scales appended to the pool tuple on the quantized path.
    """
    B, _, D = x.shape
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    act_spec = cache.get("act_spec")
    if act_spec is not None:  # exact TP: see swiglu
        x = jax.lax.with_sharding_constraint(x, act_spec)
    q = (x @ p["wq"]).reshape(B, H, hd)
    k = (x @ p["wk"]).reshape(B, KVH, hd)
    v = (x @ p["wv"]).reshape(B, KVH, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)  # [B, hd/2]
    cos, sin = cos[:, None, :], sin[:, None, :]
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)  # rope applied at write time

    window_len = cache["window_len"]  # python int: cache capacity (tokens)
    slot = jnp.where(window_len > 0, positions % window_len, positions)
    k_scale, v_scale = cache.get("k_scale"), cache.get("v_scale")
    if k_scale is None:
        pool_k = paged_kv_update(cache["k_pool"], cache["block_tables"],
                                 slot, k)
        pool_v = paged_kv_update(cache["v_pool"], cache["block_tables"],
                                 slot, v)
    else:
        pool_k, k_scale = paged_kv_update_quant(
            cache["k_pool"], k_scale, cache["block_tables"], slot, k)
        pool_v, v_scale = paged_kv_update_quant(
            cache["v_pool"], v_scale, cache["block_tables"], slot, v)
    pool_spec = cache.get("pool_spec")
    if pool_spec is not None:
        # pin the updated per-layer pools to the serving-mesh layout so
        # the layer scan's stacked outputs keep the canonical sharding
        # (otherwise GSPMD may re-layout the dominant cache bytes around
        # the scatter and drag an all-gather into every tick)
        pool_k = jax.lax.with_sharding_constraint(pool_k, pool_spec)
        pool_v = jax.lax.with_sharding_constraint(pool_v, pool_spec)
        scale_spec = cache.get("scale_spec")
        if k_scale is not None and scale_spec is not None:
            k_scale = jax.lax.with_sharding_constraint(k_scale, scale_spec)
            v_scale = jax.lax.with_sharding_constraint(v_scale, scale_spec)
    new_lens = jnp.minimum(positions + 1, window_len) if window_len > 0 \
        else positions + 1
    out = paged_attention_decode(
        pool_k, pool_v, q, cache["block_tables"], new_lens,
        scale=1.0 / math.sqrt(hd), use_kernel=cache.get("use_kernel", False),
        kernel_mesh=cache.get("kernel_mesh"),
        k_scale=k_scale, v_scale=v_scale)
    out = out.reshape(B, 1, H * hd)
    if act_spec is not None:  # exact TP (see swiglu): gather heads first
        out = jax.lax.with_sharding_constraint(out, act_spec)
    out = out @ p["wo"]
    if k_scale is None:
        return out, (pool_k, pool_v)
    return out, (pool_k, pool_v, k_scale, v_scale)


def gqa_attention_prefill_chunk(p: dict, cfg: ModelConfig, x: jax.Array,
                                positions: jax.Array, valid: jax.Array,
                                k_pool: jax.Array, v_pool: jax.Array,
                                block_tables: jax.Array, window_len: int,
                                window: Optional[int] = None,
                                use_kernel: bool = False,
                                kernel_mesh=None,
                                pool_spec=None, act_spec=None,
                                k_scale=None, v_scale=None,
                                scale_spec=None) -> tuple:
    """Prefill one chunk of a prompt against the paged KV cache.

    The continuous-batching engine splits long prompts into fixed-size
    chunks so prefill interleaves with decode steps instead of stalling
    the running batch. Earlier chunks' KV already sits in the paged pool
    (written by previous calls); this layer writes the chunk's own KV
    into the pool, then attends the chunk's queries over the pooled
    prefix *plus* the exact (un-roundtripped) chunk KV.

    x [B, C, D]; positions [B, C] absolute prompt positions (contiguous
    across the chunk, padding included); valid [B, C] marks real tokens
    (the final chunk is right-padded to the static chunk width — padded
    slots write to the scratch block and their outputs are discarded by
    the caller). Assumes prompt_len <= window_len so slot == position
    (no wraparound during prefill; the engine gates chunked prefill on
    this).

    ``use_kernel`` runs the attention itself through the multi-query
    Pallas paged kernel (``kernels.paged_attention_prefill``): no dense
    [B, KVH, G, C, bp*bs + C] score tensor, dead pool pages skipped.
    ``kernel_mesh`` adds the shard_map routing for mesh engines.

    ``k_scale``/``v_scale`` [N_blocks, KVH] mark a quantized pool: the
    chunk's KV is written through the page-requantize scatter and the
    pooled-prefix read dequantizes (the chunk's own KV stays exact in
    both cases). Returns (out [B, C, D], new_k_pool, new_v_pool), with
    (new_k_scale, new_v_scale) appended on the quantized path.
    """
    B, C, D = x.shape
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    bs = k_pool.shape[1]
    bp = block_tables.shape[1]
    if act_spec is not None:  # exact TP: see swiglu
        x = jax.lax.with_sharding_constraint(x, act_spec)
    q = (x @ p["wq"]).reshape(B, C, H, hd)
    k = (x @ p["wk"]).reshape(B, C, KVH, hd)
    v = (x @ p["wv"]).reshape(B, C, KVH, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)  # [B,C,hd/2]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    # scatter the chunk's KV into the pool (padded slots -> scratch 0)
    slot = positions % window_len                      # [B, C] == positions
    if k_scale is None:
        block_ids = jnp.take_along_axis(block_tables, slot // bs, axis=1)
        block_ids = jnp.where(valid, block_ids, 0)
        offs = slot % bs
        new_k_pool = k_pool.at[block_ids, offs].set(k)
        new_v_pool = v_pool.at[block_ids, offs].set(v)
        new_k_scale = new_v_scale = None
    else:
        new_k_pool, new_k_scale = paged_chunk_update_quant(
            k_pool, k_scale, block_tables, slot, valid, k)
        new_v_pool, new_v_scale = paged_chunk_update_quant(
            v_pool, v_scale, block_tables, slot, valid, v)
    if pool_spec is not None:  # serving mesh: keep the pool layout pinned
        new_k_pool = jax.lax.with_sharding_constraint(new_k_pool, pool_spec)
        new_v_pool = jax.lax.with_sharding_constraint(new_v_pool, pool_spec)
        if new_k_scale is not None and scale_spec is not None:
            new_k_scale = jax.lax.with_sharding_constraint(
                new_k_scale, scale_spec)
            new_v_scale = jax.lax.with_sharding_constraint(
                new_v_scale, scale_spec)

    if use_kernel:
        from repro.kernels import ops as kops
        # positions are contiguous across the chunk (engine contract),
        # so the chunk start doubles as the pooled-prefix length and the
        # valid prefix length is a per-row count
        prefix_lens = positions[:, 0].astype(jnp.int32)
        num_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
        args = (q, new_k_pool, new_v_pool, block_tables, prefix_lens,
                num_valid, k, v)
        kw = dict(scale=1.0 / math.sqrt(hd), window=window,
                  k_scale=new_k_scale, v_scale=new_v_scale)
        if kernel_mesh is not None:
            out = kops.paged_attention_prefill_sharded(kernel_mesh, *args,
                                                       **kw)
        else:
            out = kops.paged_attention_prefill(*args, **kw)
        out = out.reshape(B, C, H * hd)
    else:
        # keys/values = [pooled prefix (earlier chunks) ++ exact own
        # chunk]. The pool side is masked to positions strictly before
        # this chunk, so within-chunk attention never round-trips
        # through the (bf16 or quantized) pool — only the cross-chunk
        # prefix does, exactly as decode reads it later.
        kc = new_k_pool[block_tables]
        vc = new_v_pool[block_tables]
        if new_k_scale is not None:
            kc = KQ.dequantize_pages(kc, new_k_scale[block_tables])
            vc = KQ.dequantize_pages(vc, new_v_scale[block_tables])
        kc = kc.reshape(B, bp * bs, KVH, hd)
        vc = vc.reshape(B, bp * bs, KVH, hd)
        keys = jnp.concatenate([kc, k.astype(kc.dtype)], axis=1)
        vals = jnp.concatenate([vc, v.astype(vc.dtype)], axis=1)

        q_pos = positions[:, :, None]                      # [B, C, 1]
        chunk_start = positions[:, :1, None]               # [B, 1, 1]
        pool_pos = jnp.arange(bp * bs)[None, None, :]      # pool slot == pos
        pool_mask = pool_pos < chunk_start                 # earlier chunks
        own_pos = positions[:, None, :]                    # [B, 1, C]
        own_mask = (own_pos <= q_pos) & valid[:, None, :]  # causal + no pad
        mask = jnp.concatenate(
            [jnp.broadcast_to(pool_mask, (B, C, bp * bs)),
             jnp.broadcast_to(own_mask, (B, C, C))], axis=2)
        if window is not None:
            all_pos = jnp.concatenate(
                [jnp.broadcast_to(pool_pos, (B, 1, bp * bs)),
                 jnp.broadcast_to(own_pos, (B, 1, C))], axis=2)
            mask &= all_pos > (q_pos - window)
        # padded queries fully masked -> zeros, the kernel's convention
        mask &= valid[:, :, None]

        group = H // KVH
        qg = q.reshape(B, C, KVH, group, hd)
        scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, keys,
                            preferred_element_type=jnp.float32)
        scores *= 1.0 / math.sqrt(hd)
        out = _masked_softmax_pv(scores, mask[:, None, None], vals,
                                 "bkgqs,bskh->bqkgh")
        out = out.astype(x.dtype).reshape(B, C, H * hd)
    if act_spec is not None:  # exact TP (see swiglu): gather heads first
        out = jax.lax.with_sharding_constraint(out, act_spec)
    out = out @ p["wo"]
    if new_k_scale is None:
        return out, new_k_pool, new_v_pool
    return out, new_k_pool, new_v_pool, new_k_scale, new_v_scale


# ---------------------------------------------------------------------------
# cross attention (enc-dec)
# ---------------------------------------------------------------------------

def cross_attention(p: dict, cfg: ModelConfig, x: jax.Array,
                    enc_k: jax.Array, enc_v: jax.Array) -> jax.Array:
    """x [B, S, D]; enc_k/enc_v [B, T, KVH, hd] precomputed at prefill."""
    B, S, D = x.shape
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    group = H // KVH
    qg = q.reshape(B, S, KVH, group, hd)
    scores = jnp.einsum("bqkgh,btkh->bkgqt", qg, enc_k,
                        preferred_element_type=jnp.float32)
    scores *= 1.0 / math.sqrt(hd)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgqt,btkh->bqkgh", probs, enc_v).reshape(B, S, H * hd)
    return out @ p["wo"]


def cross_kv(p: dict, cfg: ModelConfig, enc_out: jax.Array):
    B, T, D = enc_out.shape
    KVH, hd = cfg.num_kv_heads, cfg.head_dim
    k = (enc_out @ p["wk"]).reshape(B, T, KVH, hd)
    v = (enc_out @ p["wv"]).reshape(B, T, KVH, hd)
    return k, v


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(nope + rope), times mscale(factor, mscale_all_dim)^2 under
    YaRN (DeepSeek-V2 scales its softmax by the square)."""
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    if cfg.rope_scaling_factor > 1.0 and cfg.yarn_mscale_all_dim:
        m = yarn_mscale(cfg.rope_scaling_factor, cfg.yarn_mscale_all_dim)
        scale *= m * m
    return scale


def _mla_rope(cfg: ModelConfig, positions: jax.Array):
    return rope_cos_sin(positions, cfg.qk_rope_head_dim, cfg.rope_theta,
                        yarn=cfg)


def _mla_q(p: dict, cfg: ModelConfig, x: jax.Array, cos, sin) -> tuple:
    """(q_nope [..., H, nope], roped q_rope [..., H, rope]) of x [..., D]:
    through the low-rank pair ``wq_a``/``wq_b`` with q-LoRA, one ``wq``
    [D, H*(nope+rope)] without. cos/sin [..., rope/2]."""
    H, nd, rd = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        q = rms_norm(x @ p["wq_a"], p["q_a_norm"], cfg.norm_eps) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(*x.shape[:-1], H, nd + rd)
    q_rope = apply_rope(q[..., nd:], cos[..., None, :], sin[..., None, :])
    return q[..., :nd], q_rope


def _mla_entry(p: dict, cfg: ModelConfig, x: jax.Array, cos, sin):
    """Latent cache entries [..., kv_lora + rope] of x [..., D]: the
    normed compressed latent, then the roped key shared by every head."""
    L = cfg.kv_lora_rank
    kv_a = x @ p["wkv_a"]
    c_kv = rms_norm(kv_a[..., :L], p["kv_a_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv_a[..., None, L:], cos[..., None, :],
                        sin[..., None, :])[..., 0, :]
    return jnp.concatenate([c_kv, k_rope], axis=-1)


def _mla_absorbed_q(p: dict, cfg: ModelConfig, q_nope, q_rope):
    """[q_nope W_uk | q_rope] [..., H, kv_lora + rope]: the query that
    scores a whole latent entry in one dot."""
    L, H, nd = cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim
    q_abs = jnp.einsum("...hd,lhd->...hl", q_nope,
                       p["wk_b"].reshape(L, H, nd))
    return jnp.concatenate([q_abs, q_rope.astype(q_abs.dtype)], axis=-1)


def _mla_out(p: dict, cfg: ModelConfig, o_lat: jax.Array, act_spec=None):
    """Latent attention output [..., H, kv_lora] -> [..., D] through
    W_uv and wo."""
    L, H, vd = cfg.kv_lora_rank, cfg.num_heads, cfg.v_head_dim
    out = jnp.einsum("...hl,lhd->...hd", o_lat, p["wv_b"].reshape(L, H, vd))
    out = out.reshape(*out.shape[:-2], H * vd)
    if act_spec is not None:  # exact TP (see swiglu): gather heads first
        out = jax.lax.with_sharding_constraint(out, act_spec)
    return out @ p["wo"]


def mla_attention_full(p: dict, cfg: ModelConfig, x: jax.Array,
                       positions: jax.Array, return_kv: bool = False,
                       act_spec=None):
    """Full-sequence MLA (train / prefill), per-head keys and values.

    p: {wq [D, H*(nope+rope)] | wq_a [D, q_lora], wq_b [q_lora,
        H*(nope+rope)], q_a_norm [q_lora]; wkv_a [D, kv_lora + rope],
        kv_a_norm [kv_lora], wk_b [kv_lora, H*nope], wv_b [kv_lora, H*v],
        wo [H*v, D]}
    """
    B, S, D = x.shape
    H = cfg.num_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    if act_spec is not None:  # exact TP: see swiglu
        x = jax.lax.with_sharding_constraint(x, act_spec)
    cos, sin = _mla_rope(cfg, positions)
    q_nope, q_rope = _mla_q(p, cfg, x, cos, sin)
    entry = _mla_entry(p, cfg, x, cos, sin)            # [B,S,kv_lora+rd]
    c_kv, k_rope = entry[..., :cfg.kv_lora_rank], entry[..., cfg.kv_lora_rank:]

    k_nope = (c_kv @ p["wk_b"]).reshape(B, S, H, nd)
    v = (c_kv @ p["wv_b"]).reshape(B, S, H, vd)

    scale = mla_softmax_scale(cfg)
    if S > CHUNKED_ATTN_THRESHOLD:
        # fold the shared roped key into per-head keys and run the
        # chunked online-softmax path (what lowers at 32k)
        qh = jnp.concatenate([q_nope, q_rope], axis=-1) * scale
        kh = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None], (B, S, H, rd))],
            axis=-1)
        out = chunked_mha(qh.transpose(0, 2, 1, 3),
                          kh.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), chunk=ATTN_CHUNK)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * vd)
    else:
        s_nope = jnp.einsum("bqhd,bshd->bhqs", q_nope, k_nope,
                            preferred_element_type=jnp.float32)
        s_rope = jnp.einsum("bqhd,bsd->bhqs", q_rope, k_rope,
                            preferred_element_type=jnp.float32)
        scores = (s_nope + s_rope) * scale
        mask = _attn_mask(S, S, None)
        out = _masked_softmax_pv(scores, mask[None, None], v,
                                 "bhqs,bshd->bqhd")
        out = out.astype(x.dtype).reshape(B, S, H * vd)
    if act_spec is not None:  # exact TP (see swiglu): gather heads first
        out = jax.lax.with_sharding_constraint(out, act_spec)
    out = out @ p["wo"]
    if return_kv:
        # paged-cache entry = [compressed latent | roped shared key]
        return out, entry
    return out


def pad_last(x: jax.Array, width: int) -> jax.Array:
    """x zero-padded on its last dimension to ``width``."""
    extra = width - x.shape[-1]
    if not extra:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, extra)])


def latent_attention(q: jax.Array, pool: jax.Array, layer, block_tables,
                     positions: jax.Array, entry: jax.Array, *,
                     scale: float, window_len: int, out_dim: int,
                     use_kernel: bool = False) -> jax.Array:
    """Absorbed decode attention over the latent pool plus each lane's own
    entry.

    q [B, H, E] (E = kv_lora + rope, ``_mla_absorbed_q``); pool [La, NB,
    bs, W], the whole latent pool (W = ``cfg.latent_width`` >= E, the
    entries zero-padded), read at ``layer``; it holds each lane's
    earlier tokens at slot ``position % window_len`` (``positions`` [B]
    is the current token's position); entry [B, E] is the current token's
    own entry, attended exactly. With ``positions >= window_len`` the
    window has rolled: the slot the current token will overwrite holds a
    token that left the window and is masked. Returns the latent output
    [B, H, out_dim] (the first ``out_dim`` columns of the probabilities
    times the entries).

    ``use_kernel`` runs the Pallas latent face
    (``kernels.paged_latent_attention``): only live pages are read, several
    per DMA; the dense path gathers each lane's whole window.
    """
    q, entry = pad_last(q, pool.shape[-1]), pad_last(entry, pool.shape[-1])
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.paged_latent_attention(
            q, pool, layer, block_tables, positions, entry, scale=scale,
            window=window_len, out_dim=out_dim)
    B, H, E = q.shape
    bs, bp = pool.shape[2], block_tables.shape[1]
    seq = pool[layer][block_tables].reshape(B, bp * bs, E)
    keys = jnp.concatenate([seq, entry[:, None].astype(seq.dtype)], axis=1)
    slot = jnp.arange(bp * bs)[None, :]
    n = jnp.minimum(positions, window_len)[:, None]
    skip = jnp.where(positions >= window_len, positions % window_len,
                     -1)[:, None]
    valid = jnp.concatenate(
        [(slot < n) & (slot != skip), jnp.ones((B, 1), bool)], axis=1)
    scores = jnp.einsum("bhe,bse->bhs", q, keys,
                        preferred_element_type=jnp.float32) * scale
    out = _masked_softmax_pv(scores, valid[:, None, :], keys[..., :out_dim],
                             "bhs,bsl->bhl")
    return out.astype(q.dtype)


def mla_attention_decode(p: dict, cfg: ModelConfig, x: jax.Array,
                         positions: jax.Array, pool: jax.Array, layer,
                         block_tables: jax.Array, window_len: int,
                         use_kernel: bool = False, act_spec=None) -> tuple:
    """Absorbed-weight MLA decode of one token per lane.

    x [B, 1, D]; pool [La, NB, bs, kv_lora + rope], the whole latent pool
    (read only, at ``layer``). Returns (out [B, 1, D], entry [B, kv_lora +
    rope]): the caller writes the entry into the pool after the layer
    scan, so no layer copies the pool.

    The absorption trick (also used by DeepSeek's own inference): fold
    W_uk into the query and W_uv into the output so attention runs in the
    latent space, no per-head K/V materialised.
    """
    if act_spec is not None:  # exact TP: see swiglu
        x = jax.lax.with_sharding_constraint(x, act_spec)
    xt = x[:, 0]
    cos, sin = _mla_rope(cfg, positions)               # [B, rd/2]
    q_nope, q_rope = _mla_q(p, cfg, xt, cos, sin)
    entry = _mla_entry(p, cfg, xt, cos, sin)           # [B, L+rd]
    q = _mla_absorbed_q(p, cfg, q_nope, q_rope)        # [B, H, L+rd]
    o_lat = latent_attention(q, pool, layer, block_tables, positions, entry,
                             scale=mla_softmax_scale(cfg),
                             window_len=window_len, out_dim=cfg.kv_lora_rank,
                             use_kernel=use_kernel)
    return _mla_out(p, cfg, o_lat, act_spec)[:, None], entry


def mla_attention_prefill_chunk(p: dict, cfg: ModelConfig, x: jax.Array,
                                positions: jax.Array, valid: jax.Array,
                                pool: jax.Array, layer,
                                block_tables: jax.Array) -> tuple:
    """Prefill one chunk of a prompt against the latent pool, absorbed.

    x [B, C, D]; positions [B, C] (contiguous, the chunk right-padded,
    ``valid`` [B, C] marking real tokens); pool [La, NB, bs, E] holds
    the earlier chunks at slot == position (prefill never wraps). The
    chunk's queries attend over the pooled prefix and, causally, over the
    chunk's own exact entries. Returns (out [B, C, D], entries [B, C, E]),
    which the caller writes into the pool after the layer scan."""
    B, C, D = x.shape
    L = cfg.kv_lora_rank
    bs, bp = pool.shape[2], block_tables.shape[1]
    cos, sin = _mla_rope(cfg, positions)               # [B, C, rd/2]
    q_nope, q_rope = _mla_q(p, cfg, x, cos, sin)
    entry = _mla_entry(p, cfg, x, cos, sin)            # [B, C, E]
    E = pool.shape[-1]
    q = pad_last(_mla_absorbed_q(p, cfg, q_nope, q_rope), E)   # [B,C,H,E]
    seq = pool[layer][block_tables].reshape(B, bp * bs, E)
    keys = jnp.concatenate([seq, pad_last(entry, E).astype(seq.dtype)],
                           axis=1)
    pool_pos = jnp.arange(bp * bs)[None, None, :]
    pool_mask = pool_pos < positions[:, :1, None]      # earlier chunks
    own_mask = ((positions[:, None, :] <= positions[:, :, None])
                & valid[:, None, :])
    mask = jnp.concatenate(
        [jnp.broadcast_to(pool_mask, (B, C, bp * bs)), own_mask], axis=2)
    mask &= valid[:, :, None]       # padded queries -> zeros
    scores = jnp.einsum("bche,bse->bhcs", q, keys,
                        preferred_element_type=jnp.float32)
    scores *= mla_softmax_scale(cfg)
    o_lat = _masked_softmax_pv(scores, mask[:, None], keys[..., :L],
                               "bhcs,bsl->bchl").astype(x.dtype)
    return _mla_out(p, cfg, o_lat), entry


# ---------------------------------------------------------------------------
# MoE, dropless, over this chip's share of the experts (serving path)
# ---------------------------------------------------------------------------

EXPERT_TILE = 16      # rows of (token, expert) pairs per expert-FFN tile


def route(router_w: jax.Array, cfg: ModelConfig, xt: jax.Array) -> tuple:
    """Top-k routing over all ``cfg.num_experts`` router outputs: softmax
    in f32, greedy top-k, renormalised only with ``norm_topk_prob``, then
    scaled by ``routed_scaling_factor``. xt [T, D] -> (gates [T, E],
    weights [T, K] f32, experts [T, K])."""
    gates = jax.nn.softmax((xt @ router_w).astype(jnp.float32), axis=-1)
    weights, sel = jax.lax.top_k(gates, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-9)
    return gates, weights * cfg.routed_scaling_factor, sel


def _pair_layout(local: jax.Array, n_held: int, tile: int) -> tuple:
    """Rows of the (token, expert) pairs that fall on held experts, grouped
    by expert, each group starting on a multiple of ``tile``.

    local [P] holds each pair's expert index among the held ones (-1 or
    >= n_held: not held). Returns (dest [P]: the pair's row, or the row
    count for a pair not held; counts [n_held]; tile_expert [n_tiles]: the
    expert of each row tile, tiles past the last real one repeating its
    expert; n_real [1]: tiles that hold a pair) for a static row count of
    n_tiles * tile."""
    P = local.shape[0]
    held = (local >= 0) & (local < n_held)
    onehot = (local[:, None] == jnp.arange(n_held)[None, :]) & held[:, None]
    onehot = onehot.astype(jnp.int32)
    counts = jnp.sum(onehot, axis=0)                         # [Eh]
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    tiles = -(-counts // tile)                               # per expert
    tile_end = jnp.cumsum(tiles)
    start = (tile_end - tiles) * tile
    n_tiles = -(-(P + n_held * (tile - 1)) // tile)
    rows = n_tiles * tile
    e = jnp.clip(local, 0, n_held - 1)
    dest = jnp.where(held, start[e] + rank, rows)
    n_real = tile_end[-1]
    t = jnp.minimum(jnp.arange(n_tiles), jnp.maximum(n_real - 1, 0))
    tile_expert = jnp.minimum(
        jnp.sum(tile_end[None, :] <= t[:, None], axis=1), n_held - 1)
    return dest, counts, tile_expert.astype(jnp.int32), n_real[None]


def expert_ffn_xla(xs: jax.Array, experts: dict, layer, counts: jax.Array
                   ) -> jax.Array:
    """SwiGLU of each held expert over its rows of ``xs`` [M, D] (grouped
    by expert, ``counts`` rows each, rows past them zero), as ragged
    matmuls; experts {w_gate/w_up [Lm, Eh, D, F], w_down [Lm, Eh, F, D]}
    read at ``layer``."""
    w = {k: jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
         for k, v in experts.items()}
    g = jax.lax.ragged_dot(xs, w["w_gate"], counts)
    u = jax.lax.ragged_dot(xs, w["w_up"], counts)
    act = jax.nn.silu(g.astype(jnp.float32)).astype(xs.dtype) * u
    return jax.lax.ragged_dot(act, w["w_down"], counts)


def moe_dropless(p: dict, cfg: ModelConfig, x: jax.Array, *,
                 experts: Optional[dict] = None, layer=0,
                 first_expert: int = 0, use_kernel: bool = False,
                 tile: int = EXPERT_TILE) -> tuple:
    """Dropless MoE over the experts this chip holds.

    The router scores all ``cfg.num_experts`` experts (``route``); of the
    top-k pairs, those that fall on the held experts
    [first_expert, first_expert + num_experts_held) are computed, each
    held expert's rows in one grouped matmul, and summed with their
    router weights; the shared experts are added once. What the experts
    held elsewhere would add is left out (on one chip the layer runs
    without its all-to-all). No pair is dropped.

    p: {router [D, E], experts {w_gate/w_up [Eh, D, F], w_down [Eh, F, D]}
    (or, stacked over the MoE layers, passed as ``experts`` with the
    layer's index ``layer``), (optional) shared {w_gate, w_up, w_down}}.
    ``use_kernel`` runs the held experts' FFN as the Pallas ``expert_ffn``
    kernel (each held expert's weights read once per call), else as XLA
    ragged matmuls.

    Returns (out [B, S, D], aux_loss, load [B*S, Eh]: each token's pairs
    on each held expert).
    """
    B, S, D = x.shape
    E, K, Eh = cfg.num_experts, cfg.num_experts_per_tok, cfg.num_experts_held
    T = B * S
    xt = x.reshape(T, D)
    if experts is None:
        experts = {k: v[None] for k, v in p["experts"].items()}
    gates, weights, sel = route(p["router"], cfg, xt)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(sel, E, dtype=jnp.float32), axis=1),
                  axis=0)
    aux_loss = E * jnp.sum(me * ce)

    local = (sel - first_expert).reshape(T * K)
    dest, counts, tile_expert, n_real = _pair_layout(
        local, Eh, tile if use_kernel else 1)
    rows = tile_expert.shape[0] * (tile if use_kernel else 1)
    token = jnp.arange(T * K) // K
    xs = jnp.zeros((rows, D), x.dtype).at[dest].set(xt[token], mode="drop")
    if use_kernel:
        from repro.kernels import ops as kops
        ys = kops.expert_ffn(xs, experts["w_gate"], experts["w_up"],
                             experts["w_down"], layer, tile_expert, n_real,
                             tile=tile)
    else:
        ys = expert_ffn_xla(xs, experts, layer, counts)
    y_pair = ys.at[dest].get(mode="fill", fill_value=0)          # [T*K, D]
    w_pair = weights.reshape(T * K, 1)
    out = jnp.sum((y_pair.astype(jnp.float32) * w_pair).reshape(T, K, D),
                  axis=1).astype(x.dtype)
    if cfg.num_shared_experts:
        out = out + swiglu(p["shared"], xt)
    held = (local >= 0) & (local < Eh)
    load = jnp.sum(jax.nn.one_hot(jnp.where(held, local, Eh), Eh,
                                  dtype=jnp.int32).reshape(T, K, Eh), axis=1)
    return out.reshape(B, S, D), aux_loss, load


# ---------------------------------------------------------------------------
# Mamba2 (SSD) — chunked full-sequence + single-step decode
# ---------------------------------------------------------------------------

def _segsum(a: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{j<k<=i} a[..., k]."""
    T = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), dtype=bool), 0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array,
                Bm: jax.Array, Cm: jax.Array, chunk: int,
                initial_state: Optional[jax.Array] = None,
                use_kernel: bool = False):
    """SSD (state-space duality) scan, chunked.

    x  [B, S, H, P]   (P = head dim)
    dt [B, S, H]      (softplus'd step sizes)
    A  [H]            (negative decay rates)
    Bm [B, S, N], Cm [B, S, N]  (shared across heads, ngroups=1)
    Returns (y [B,S,H,P], final_state [B,H,P,N]).
    """
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                             initial_state=initial_state)
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    nc = S // chunk
    xc = x.reshape(B, nc, chunk, H, P)
    dtc = dt.reshape(B, nc, chunk, H)
    Bc = Bm.reshape(B, nc, chunk, N)
    Cc = Cm.reshape(B, nc, chunk, N)

    dA = dtc * A[None, None, None, :]  # [B,nc,l,H]
    dA = jnp.moveaxis(dA, -1, 2)  # [B,nc,H,l]
    dA_cumsum = jnp.cumsum(dA, axis=-1)

    # 1. intra-chunk (quadratic within chunk)
    L = jnp.exp(_segsum(dA))  # [B,nc,H,l,l]
    scores = jnp.einsum("bcln,bcsn->bcls", Cc, Bc)[:, :, None] * L
    y_diag = jnp.einsum("bchls,bcsh,bcshp->bclhp", scores, dtc, xc)

    # 2. chunk states
    decay_states = jnp.exp(dA_cumsum[..., -1:] - dA_cumsum)  # [B,nc,H,l]
    states = jnp.einsum("bcln,bchl,bclh,bclhp->bchpn",
                        Bc, decay_states, dtc, xc)

    # 3. inter-chunk recurrence (scan over chunks)
    chunk_decay = jnp.exp(dA_cumsum[..., -1])  # [B,nc,H]
    if initial_state is None:
        initial_state = jnp.zeros((B, H, P, N), dtype=states.dtype)

    def step(h, inp):
        st, dec = inp
        h_new = h * dec[..., None, None] + st
        return h_new, h

    final_state, h_prev = jax.lax.scan(
        step, initial_state,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)  # [B,nc,H,P,N] state BEFORE chunk

    # 4. state -> output contribution
    state_decay = jnp.exp(dA_cumsum)  # [B,nc,H,l]
    y_off = jnp.einsum("bcln,bchpn,bchl->bclhp", Cc, h_prev, state_decay)

    y = (y_diag + y_off).reshape(B, S, H, P)
    return y, final_state


def ssd_decode_step(state: jax.Array, x: jax.Array, dt: jax.Array,
                    A: jax.Array, Bm: jax.Array, Cm: jax.Array):
    """Single-token SSD recurrence.

    state [B,H,P,N]; x [B,H,P]; dt [B,H]; A [H]; Bm/Cm [B,N].
    Returns (y [B,H,P], new_state).
    """
    dA = jnp.exp(dt * A[None, :])  # [B,H]
    dBx = jnp.einsum("bh,bn,bhp->bhpn", dt, Bm, x)
    new_state = state * dA[..., None, None] + dBx
    y = jnp.einsum("bhpn,bn->bhp", new_state, Cm)
    return y, new_state


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x [B,S,C]; w [W,C]; b [C]."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :]
              for i in range(W))
    return out + b[None, None, :]


def causal_conv1d_step(conv_state: jax.Array, x_t: jax.Array,
                       w: jax.Array, b: jax.Array):
    """conv_state [B, W-1, C]; x_t [B, C]. Returns (y [B,C], new_state)."""
    full = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # [B,W,C]
    y = jnp.einsum("bwc,wc->bc", full, w) + b[None, :]
    return y, full[:, 1:, :]


def mamba2_mixer_full(p: dict, cfg: ModelConfig, x: jax.Array,
                      use_kernel: bool = False, return_state: bool = False):
    """Full-sequence Mamba2 mixer.

    p: {w_in [D, d_inner*2 + 2N + H], conv_w [W, d_inner+2N], conv_b,
        A_log [H], D [H], dt_bias [H], norm_w [d_inner], w_out [d_inner, D]}
    With return_state, also returns (ssm_state [B,H,P,N],
    conv_state [B,W-1,di+2N]) for decode continuation.
    """
    B, S, D = x.shape
    di, N, H = cfg.d_inner, cfg.ssm_state_size, cfg.ssm_heads
    P = cfg.ssm_head_dim
    zxbcdt = x @ p["w_in"]
    z = zxbcdt[..., :di]
    xbc_raw = zxbcdt[..., di:di + di + 2 * N]
    dt = zxbcdt[..., di + di + 2 * N:]  # [B,S,H]
    xbc = jax.nn.silu(causal_conv1d(xbc_raw, p["conv_w"], p["conv_b"])
                      .astype(jnp.float32)).astype(x.dtype)
    xs = xbc[..., :di].reshape(B, S, H, P)
    Bm = xbc[..., di:di + N]
    Cm = xbc[..., di + N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    chunk = min(cfg.ssm_chunk_size, S)
    pad = (-S) % chunk
    xs_f = xs.astype(jnp.float32)
    Bm_f, Cm_f = Bm.astype(jnp.float32), Cm.astype(jnp.float32)
    if pad:
        # dt=0 on padding keeps the state exactly (decay 1, input 0)
        xs_f = jnp.pad(xs_f, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt_p = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm_f = jnp.pad(Bm_f, ((0, 0), (0, pad), (0, 0)))
        Cm_f = jnp.pad(Cm_f, ((0, 0), (0, pad), (0, 0)))
    else:
        dt_p = dt
    y, final_state = ssd_chunked(xs_f, dt_p, A, Bm_f, Cm_f, chunk=chunk,
                                 use_kernel=use_kernel)
    y = y[:, :S]
    y = y + xs.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(B, S, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                 p["norm_w"], cfg.norm_eps)
    out = y @ p["w_out"]
    if return_state:
        W = cfg.ssm_conv_width
        conv_state = xbc_raw[:, -(W - 1):, :] if S >= W - 1 else jnp.pad(
            xbc_raw, ((0, 0), (W - 1 - S, 0), (0, 0)))
        return out, final_state, conv_state.astype(x.dtype)
    return out


def mamba2_mixer_decode(p: dict, cfg: ModelConfig, x: jax.Array,
                        ssm_state: jax.Array, conv_state: jax.Array):
    """One-token Mamba2 step. x [B,1,D]. Returns (out, new_ssm, new_conv)."""
    B, _, D = x.shape
    di, N, H = cfg.d_inner, cfg.ssm_state_size, cfg.ssm_heads
    P = cfg.ssm_head_dim
    zxbcdt = (x[:, 0] @ p["w_in"])
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + di + 2 * N]
    dt = zxbcdt[..., di + di + 2 * N:]
    xbc, new_conv = causal_conv1d_step(conv_state, xbc, p["conv_w"], p["conv_b"])
    xbc = jax.nn.silu(xbc.astype(jnp.float32)).astype(x.dtype)
    xs = xbc[..., :di].reshape(B, H, P)
    Bm = xbc[..., di:di + N]
    Cm = xbc[..., di + N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, new_state = ssd_decode_step(
        ssm_state, xs.astype(jnp.float32), dt, A,
        Bm.astype(jnp.float32), Cm.astype(jnp.float32))
    y = y + xs.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, :, None]
    y = y.reshape(B, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                 p["norm_w"], cfg.norm_eps)
    return (y @ p["w_out"])[:, None, :], new_state, new_conv
