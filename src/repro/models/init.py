"""Parameter initialization for every architecture family.

Layer params are STACKED along a leading [num_layers] axis so the forward
pass can ``lax.scan`` over layers — essential for compile time at 512
devices. Hybrid (zamba2) stacks as [groups, layers_per_group, ...] with a
single shared attention block.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

DTYPE = jnp.bfloat16


def padded_vocab(cfg: ModelConfig) -> int:
    """Vocab padded to a multiple of 256 so 16-way sharding is even."""
    return -(-cfg.vocab_size // 256) * 256


def _norm(key, *shape):
    del key
    return jnp.ones(shape, DTYPE)


def _dense(key, fan_in, *shape, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(DTYPE)


def _attn_params(key, cfg: ModelConfig, stack=()) -> dict:
    D, H, KVH, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    out_scale = 1.0 / math.sqrt(H * hd) / math.sqrt(2 * max(cfg.num_layers, 1))
    p = {
        "wq": _dense(ks[0], D, *stack, D, H * hd),
        "wk": _dense(ks[1], D, *stack, D, KVH * hd),
        "wv": _dense(ks[2], D, *stack, D, KVH * hd),
        "wo": _dense(ks[3], H * hd, *stack, H * hd, D, scale=out_scale),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((*stack, hd), DTYPE)
        p["k_norm"] = jnp.ones((*stack, hd), DTYPE)
    return p


def _mla_params(key, cfg: ModelConfig, stack=()) -> dict:
    D, H = cfg.d_model, cfg.num_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    L, QL = cfg.kv_lora_rank, cfg.q_lora_rank
    ks = jax.random.split(key, 6)
    out_scale = 1.0 / math.sqrt(H * vd) / math.sqrt(2 * cfg.num_layers)
    p = {
        "wkv_a": _dense(ks[2], D, *stack, D, L + rd),
        "wk_b": _dense(ks[3], L, *stack, L, H * nd),
        "wv_b": _dense(ks[4], L, *stack, L, H * vd),
        "wo": _dense(ks[5], H * vd, *stack, H * vd, D, scale=out_scale),
        "kv_a_norm": jnp.ones((*stack, L), DTYPE),
    }
    if QL:
        p["wq_a"] = _dense(ks[0], D, *stack, D, QL)
        p["wq_b"] = _dense(ks[1], QL, *stack, QL, H * (nd + rd))
        p["q_a_norm"] = jnp.ones((*stack, QL), DTYPE)
    else:
        p["wq"] = _dense(ks[0], D, *stack, D, H * (nd + rd))
    return p


def _mlp_params(key, cfg: ModelConfig, d_ff=None, stack=()) -> dict:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    down_scale = 1.0 / math.sqrt(F) / math.sqrt(2 * max(cfg.num_layers, 1))
    return {
        "w_gate": _dense(ks[0], D, *stack, D, F),
        "w_up": _dense(ks[1], D, *stack, D, F),
        "w_down": _dense(ks[2], F, *stack, F, D, scale=down_scale),
    }


def _moe_params(key, cfg: ModelConfig, stack=()) -> dict:
    """The router over all ``num_experts``; the routed experts this chip
    holds (``num_experts_held``)."""
    D, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    Eh = cfg.num_experts_held
    ks = jax.random.split(key, 5)
    down_scale = 1.0 / math.sqrt(F) / math.sqrt(2 * cfg.num_layers)
    p = {
        "router": _dense(ks[0], D, *stack, D, E),
        "experts": {
            "w_gate": _dense(ks[1], D, *stack, Eh, D, F),
            "w_up": _dense(ks[2], D, *stack, Eh, D, F),
            "w_down": _dense(ks[3], F, *stack, Eh, F, D, scale=down_scale),
        },
    }
    if cfg.num_shared_experts:
        p["shared"] = _mlp_params(ks[4], cfg,
                                  d_ff=F * cfg.num_shared_experts, stack=stack)
    return p


def _mamba_params(key, cfg: ModelConfig, stack=()) -> dict:
    D, di, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state_size, cfg.ssm_heads
    conv_ch = di + 2 * N
    in_dim = 2 * di + 2 * N + H
    ks = jax.random.split(key, 4)
    out_scale = 1.0 / math.sqrt(di) / math.sqrt(2 * cfg.num_layers)
    return {
        "w_in": _dense(ks[0], D, *stack, D, in_dim),
        "conv_w": _dense(ks[1], cfg.ssm_conv_width,
                         *stack, cfg.ssm_conv_width, conv_ch),
        "conv_b": jnp.zeros((*stack, conv_ch), DTYPE),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)), (*stack, H)
        ).astype(jnp.float32),
        "D": jnp.ones((*stack, H), jnp.float32),
        "dt_bias": jnp.broadcast_to(
            jnp.log(jnp.expm1(jnp.full((H,), 0.01, jnp.float32))),
            (*stack, H)).astype(jnp.float32),
        "norm_w": jnp.ones((*stack, di), DTYPE),
        "w_out": _dense(ks[2], di, *stack, di, D, scale=out_scale),
    }


def _decoder_layer(key, cfg: ModelConfig, stack=(), cross=False,
                   moe=None) -> dict:
    ks = jax.random.split(key, 4)
    if cfg.use_mla:
        attn = _mla_params(ks[0], cfg, stack)
    else:
        attn = _attn_params(ks[0], cfg, stack)
    p = {"ln1": jnp.ones((*stack, cfg.d_model), DTYPE), "attn": attn,
         "ln2": jnp.ones((*stack, cfg.d_model), DTYPE)}
    if cfg.uses_moe if moe is None else moe:
        p["moe"] = _moe_params(ks[1], cfg, stack)
    else:
        p["mlp"] = _mlp_params(ks[1], cfg, stack=stack)
    if cross:
        p["ln_cross"] = jnp.ones((*stack, cfg.d_model), DTYPE)
        p["cross"] = _attn_params(ks[2], cfg, stack)
    return p


def init_params(cfg: ModelConfig, rng: jax.Array) -> dict:
    V = padded_vocab(cfg)
    D = cfg.d_model
    ks = jax.random.split(rng, 8)
    params: dict = {
        "embed": _dense(ks[0], D, V, D, scale=0.02),
        "final_norm": jnp.ones((D,), DTYPE),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense(ks[1], D, D, V)

    if cfg.arch_type == "ssm":
        L = cfg.num_layers
        params["layers"] = {
            "norm": jnp.ones((L, D), DTYPE),
            "mixer": _mamba_params(ks[2], cfg, stack=(L,)),
        }
    elif cfg.arch_type == "hybrid":
        assert cfg.num_layers % cfg.hybrid_attn_every == 0
        G = cfg.num_layers // cfg.hybrid_attn_every
        per = cfg.hybrid_attn_every
        params["layers"] = {
            "norm": jnp.ones((G, per, D), DTYPE),
            "mixer": _mamba_params(ks[2], cfg, stack=(G, per)),
        }
        params["shared_attn"] = _decoder_layer(ks[3], cfg)  # single block
    elif cfg.is_encoder_decoder:
        Le, Ld = cfg.num_encoder_layers, cfg.num_layers
        enc = {"ln1": jnp.ones((Le, D), DTYPE),
               "attn": _attn_params(ks[2], cfg, (Le,)),
               "ln2": jnp.ones((Le, D), DTYPE),
               "mlp": _mlp_params(ks[3], cfg, stack=(Le,))}
        params["encoder"] = enc
        params["encoder_norm"] = jnp.ones((D,), DTYPE)
        params["layers"] = _decoder_layer(ks[4], cfg, (Ld,), cross=True)
    else:  # dense / moe / vlm
        L, K = cfg.num_layers, cfg.first_k_dense
        if K:   # leading dense layers, then the MoE layers
            params["dense_layers"] = _decoder_layer(ks[5], cfg, (K,),
                                                    moe=False)
        params["layers"] = _decoder_layer(ks[2], cfg, (L - K,))
    return params


def count_params(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
