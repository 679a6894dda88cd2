"""Quantized paged-KV pool support: dtype registry, per-page per-KV-head
scale quantization, and byte accounting.

The paged pools (``k_pool``/``v_pool``: ``[layers, blocks, block_size,
kv_heads, head_dim]``) can be stored in four dtypes, selected by
``EngineConfig.kv_dtype`` (env ``REPRO_KV_DTYPE``, CLI ``--kv-dtype``):

- ``f32`` / ``bf16`` — plain floating-point pools, no scales. ``bf16``
  is the default (and the historical hardcoded pool dtype), and is
  pinned token/score/prune-identical to ``f32`` at engine scale.
- ``int8`` / ``fp8`` — quantized pools with one f32 scale per
  (page, slot, KV head), stored as extra cache entries ``k_scale``/
  ``v_scale`` of shape ``[layers, blocks, block_size, kv_heads]``.
  Dequantization is ``q.astype(f32) * scale``; the scale is
  ``absmax / qmax`` over the token's ``head_dim`` vector. ``fp8`` uses
  ``float8_e4m3fn``.

The scale granularity is per SLOT, not per page, and that choice is
load-bearing: each cached token quantizes independently from its own
absmax, so a slot's stored code is a pure function of the token value
written there. Every write path — one-shot prefill scatter, chunked
prefill, per-token decode appends, COW block copies — therefore
produces bit-identical pool content for the same tokens, and recycled
blocks carry no history (a stale neighbour cannot leak into a fresh
token's scale). This is what keeps the engine's scheduling-transparency
pins (prefix-cache on/off, chunked-vs-one-shot prefill, warm-vs-cold
pool) EXACT under quantization, where a per-page absmax would have to
re-round earlier tokens on every append. The cost is one extra f32 per
(slot, kv head) — ``1/head_dim`` of the int8 pool bytes, ~1.5% at
``head_dim=64`` — which ``pool_block_bytes`` accounts for.

Both the dense-math attention fallback and the Pallas multi-query kernel
apply the *same* dequant (cast to f32, multiply by the slot scale), so
the two read paths stay numerically aligned — the kernel-vs-dense
identity pins hold under every ``kv_dtype``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.configs.base import ModelConfig

KV_DTYPES = ("f32", "bf16", "int8", "fp8")

# Largest representable magnitude per quantized dtype: int8 uses the
# symmetric range [-127, 127]; float8_e4m3fn tops out at 448.
_QMAX_INT8 = 127.0
_QMAX_FP8 = 448.0


def kv_pool_dtype(kv_dtype: str):
    """Map a ``kv_dtype`` setting to the pool storage jnp dtype."""
    if kv_dtype == "f32":
        return jnp.float32
    if kv_dtype == "bf16":
        return jnp.bfloat16
    if kv_dtype == "int8":
        return jnp.int8
    if kv_dtype == "fp8":
        return jnp.float8_e4m3fn
    raise ValueError(
        f"unknown kv_dtype {kv_dtype!r}; expected one of {KV_DTYPES}")


def is_quantized(kv_dtype: str) -> bool:
    return kv_dtype in ("int8", "fp8")


def kv_bytes_per_scalar(kv_dtype: str) -> int:
    """Pool storage bytes per cached scalar (excluding scale overhead)."""
    return {"f32": 4, "bf16": 2, "int8": 1, "fp8": 1}[kv_dtype]


def _qmax(qdtype) -> float:
    return _QMAX_INT8 if jnp.dtype(qdtype) == jnp.dtype(jnp.int8) \
        else _QMAX_FP8


def quantize_pages(x: jnp.ndarray, qdtype) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize f32 KV values ``[..., head_dim]`` to ``qdtype`` with a
    fresh absmax scale per leading index (one scale per token vector —
    the per-slot granularity that makes writes order-independent, see
    the module docstring).

    Returns ``(q, scale)`` where ``scale`` has shape ``x.shape[:-1]``.
    All-zero vectors get scale 1.0 so dequantization stays exact and
    division is well-defined.

    Scales are stored as f32 but rounded to the bf16 grid. This keeps
    ``code * scale`` EXACT in f32 (8-bit code mantissa x 8-bit scale
    mantissa fits f32's 24), which is what lets the Pallas kernel's
    per-page online-softmax accumulation stay bit-identical to the
    dense fallback's one-shot contraction — the same mechanism that
    makes the bf16 pool's kernel/dense identity exact. A full-precision
    scale would make every dequantized product carry rounding noise,
    and the two read paths' different summation orders would surface
    it as ulp-level logit drift.
    """
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(absmax > 0.0, absmax / _qmax(qdtype), 1.0)
    scale = scale.astype(jnp.bfloat16).astype(jnp.float32)
    y = xf / scale[..., None]
    if jnp.dtype(qdtype) == jnp.dtype(jnp.int8):
        q = jnp.clip(jnp.round(y), -_QMAX_INT8, _QMAX_INT8).astype(jnp.int8)
    else:
        q = jnp.clip(y, -_QMAX_FP8, _QMAX_FP8).astype(qdtype)
    return q, scale


def dequantize_pages(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`quantize_pages`: ``q [..., hd]`` with
    ``scale [...]`` back to f32. Also used on dtype-gathered pool
    slices (``pool[block_tables]`` with ``scale[block_tables]``) —
    any leading batch axes broadcast."""
    return q.astype(jnp.float32) * scale[..., None]


def resolve_kv_dtype(setting: str, cfg: ModelConfig,
                     chunk_supported: bool) -> str:
    """Validate a ``kv_dtype`` setting against the model architecture.

    Quantized pools cover the block-addressed GQA pool (``k_pool``/
    ``v_pool``, which carry the per-slot scale arrays, on the paths the
    chunked-prefill scatter serves); the latent pool of MLA has no scales,
    and SSM / hybrid / encoder-decoder caches keep full-precision pools:
    they raise here, so users hit one clear error at engine construction
    instead of a shape error mid-serve. ``f32``/``bf16`` only re-type the
    pools and are accepted everywhere.
    """
    if setting not in KV_DTYPES:
        raise ValueError(
            f"unknown kv_dtype {setting!r}; expected one of {KV_DTYPES}")
    if is_quantized(setting) and (cfg.use_mla or not chunk_supported):
        raise NotImplementedError(
            f"kv_dtype={setting!r} is only supported for dense GQA "
            f"architectures (arch_type={cfg.arch_type!r}, "
            f"use_mla={cfg.use_mla}); see docs/SUPPORT_MATRIX.md")
    return setting


def pool_block_bytes(cfg: ModelConfig, kv_dtype: str) -> int:
    """HBM bytes one KV block occupies across all attention layers —
    pool storage plus (for quantized dtypes) the per-page f32 scales.
    This is what `AdmissionPressure` byte accounting reports per block.
    """
    la = len(cfg.attention_layer_ids())
    per_token = cfg.kv_cache_dims_per_token
    n = la * cfg.kv_block_size * per_token * kv_bytes_per_scalar(kv_dtype)
    if is_quantized(kv_dtype):
        # one f32 scale per (layer, page, slot, kv_head), for K and V
        n += la * 2 * cfg.kv_block_size * cfg.num_kv_heads * 4
    return n


def init_scales(cfg: ModelConfig, num_blocks: int,
                kv_dtype: str) -> Optional[jnp.ndarray]:
    """Fresh unit scales ``[attn_layers, num_blocks, block_size,
    kv_heads]`` for a quantized pool (zero-filled pools dequantize to
    exact zeros), or ``None`` for float pools."""
    if not is_quantized(kv_dtype):
        return None
    la = len(cfg.attention_layer_ids())
    return jnp.ones((la, num_blocks, cfg.kv_block_size, cfg.num_kv_heads),
                    jnp.float32)
