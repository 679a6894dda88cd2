"""Plain reference for DeepSeek-V2 (latent attention, leading dense
layers, routed and shared experts), written from the published
architecture and independent of the program under test.

One sequence at a time, in float32 at the highest matmul precision, layer
by layer (a scan over each stack of layer weights, each layer upcast on
its own), with attention, the experts and the output head in blocks of
rows so that a long sequence fits once the engine is freed.

DeepSeek-V2, as its ``modeling_deepseek.py`` describes it:
- attention (MLA) without q-LoRA: q = x Wq split into a no-rope part and
  a rope part per head; the compressed latent c = RMSNorm(x W_dkv[:L])
  and a rope key x W_dkv[L:] shared by every head; per-head keys
  [c W_uk | rope key] and values c W_uv, computed in full (not absorbed);
- rotary embedding with YaRN (factor, original context, beta_fast /
  beta_slow, mscale / mscale_all_dim), transcribed here from the
  published formula: frequencies blended between the original and the
  interpolated ones by a linear ramp between the correction dims, cos and
  sin times mscale(factor, mscale) / mscale(factor, mscale_all_dim), and
  the softmax scale 1/sqrt(nope + rope) times mscale(factor,
  mscale_all_dim)^2;
- the first ``first_k_dense`` layers a dense SwiGLU MLP; the rest MoE:
  a softmax over all ``num_experts`` router outputs, greedy top-k,
  renormalised only with ``norm_topk_prob``, times
  ``routed_scaling_factor``, and the shared experts added;
- a final RMSNorm and an untied output head.

Departures, each a fixed relabelling or the benchmark's cut:
- the rotary embedding pairs the two halves of the rope part (dims i and
  i + rope/2), where the published code pairs neighbours (2i, 2i + 1):
  the same as the published model with the rope columns of Wq and
  W_dkv permuted, which random weights do not tell apart;
- only the experts this chip holds (``experts_held``, the first of the
  router's ``num_experts``) are computed, as the program computes them:
  each runs densely over every token, masked by its routed weight (zero
  where the token did not choose it); what the other chips' experts add
  is left out, in the program and here alike.

``low`` computes the same forward pass below the stated precision (bf16):
the control of the comparison. Every weight matrix is rounded to fp8
(e4m3) with one scale per output column, every matmul input, the latent
and every head of K and V to fp8 with one scale per row: the rounding of
an fp8 latent pool or fp8 matmuls, the step below bf16 that a faster path
would take.
"""
from __future__ import annotations

import importlib.util
import math
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _qwen3(part: str):
    path = Path(__file__).resolve().parents[1] / "qwen3" / f"{part}.py"
    spec = importlib.util.spec_from_file_location(f"_qwen3_{part}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_q = _qwen3("reference")
HI, _f8, _rms = _q.HI, _q._f8, _q._rms
BLOCK = 256          # rows of queries / tokens / output-head rows per block


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """DeepseekV2YarnRotaryEmbedding's inverse frequencies [dim/2]."""
    def corr(rot):
        return (dim * math.log(original / (rot * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float32)
                                     / dim))
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def _rope_tables(m, T):
    rd, f = m["qk_rope_head_dim"], m["rope_scaling_factor"]
    if f > 1:
        inv = yarn_inv_freq(rd, m["rope_theta"], f,
                            m["rope_original_max_positions"],
                            m["yarn_beta_fast"], m["yarn_beta_slow"])
        amp = (yarn_mscale(f, m["yarn_mscale"])
               / yarn_mscale(f, m["yarn_mscale_all_dim"]))
    else:
        inv = 1.0 / m["rope_theta"] ** (np.arange(0, rd, 2) / rd)
        amp = 1.0
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)
    return jnp.cos(ang) * amp, jnp.sin(ang) * amp          # [T, rd/2]


def _rope(x, cos, sin):
    """x [T, ..., rd]; the two halves of x as the pairs."""
    half = x.shape[-1] // 2
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def softmax_scale(m):
    s = 1.0 / math.sqrt(m["qk_nope_head_dim"] + m["qk_rope_head_dim"])
    f = m["rope_scaling_factor"]
    if f > 1 and m["yarn_mscale_all_dim"]:
        s *= yarn_mscale(f, m["yarn_mscale_all_dim"]) ** 2
    return s


@partial(jax.jit, static_argnames=("m", "low"))
def forward(params, scorer, tokens, targets, *, m, low=False):
    """tokens [T] (T a multiple of BLOCK); targets [K, T] (targets[k, i] is
    a token whose logit at position i is asked about). ``m`` is the
    configuration's ``model`` group as a tuple of items.

    Returns per position: the log-sum-exp, each target's logit, and the
    step scorer's score of the hidden state.
    """
    m = dict(m)
    H, D = m["num_heads"], m["d_model"]
    nd, rd, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    Lr, V, eps = m["kv_lora_rank"], m["vocab_size"], m["norm_eps"]
    E, K = m["num_experts"], m["num_experts_per_tok"]
    Eh = m["experts_held"] or E
    T = tokens.shape[0]
    f32 = jnp.float32
    keep = lambda a: a  # noqa: E731
    wlo = (lambda w: _f8(w, -2)) if low else keep     # per output column
    alo = (lambda x: _f8(x, -1)) if low else keep     # per row
    scale = softmax_scale(m)
    cos, sin = _rope_tables(m, T)
    pos = jnp.arange(T)

    x = params["embed"][:V].astype(f32)[tokens]

    def attention(x, a):
        h = alo(_rms(x, a["ln1"], eps))
        q = _mm(h, wlo(a["wq"])).reshape(T, H, nd + rd)
        kv_a = _mm(h, wlo(a["wkv_a"]))
        c = alo(_rms(kv_a[:, :Lr], a["kv_a_norm"], eps))   # the latent
        k_rope = _rope(kv_a[:, Lr:], cos, sin)              # [T, rd]
        q = jnp.concatenate([q[..., :nd], _rope(q[..., nd:], cos, sin)], -1)
        k_nope = _mm(c, wlo(a["wk_b"])).reshape(T, H, nd)
        v = alo(_mm(c, wlo(a["wv_b"])).reshape(T, H, vd))
        k = alo(jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, None], (T, H, rd))], -1))

        def attend(qb_start):
            qb = jax.lax.dynamic_slice_in_dim(q, qb_start, BLOCK)
            s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * scale
            qpos = qb_start + jnp.arange(BLOCK)
            s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s,
                          -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

        o = jax.lax.map(attend, jnp.arange(0, T, BLOCK)).reshape(T, H * vd)
        return x + _mm(alo(o), wlo(a["wo"]))

    def swiglu(h, w):
        g = _mm(h, wlo(w["w_gate"]))
        u = _mm(h, wlo(w["w_up"]))
        return _mm(alo(jax.nn.silu(g) * u), wlo(w["w_down"]))

    def dense_layer(x, lp):
        lp = jax.tree.map(lambda t: t.astype(f32), lp)
        x = attention(x, {**lp["attn"], "ln1": lp["ln1"]})
        return x + swiglu(alo(_rms(x, lp["ln2"], eps)), lp["mlp"]), None

    def moe_layer(x, lp):
        lp = jax.tree.map(lambda t: t.astype(f32), lp)
        x = attention(x, {**lp["attn"], "ln1": lp["ln1"]})
        h = alo(_rms(x, lp["ln2"], eps))
        moe = lp["moe"]
        gates = jax.nn.softmax(_mm(h, wlo(moe["router"])), axis=-1)
        w, sel = jax.lax.top_k(gates, K)
        if m["norm_topk_prob"]:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        w = w * m["routed_scaling_factor"]
        # each token's weight on each held expert, zero where not chosen
        held_w = jnp.sum(jax.nn.one_hot(sel, E, dtype=f32)[..., :Eh]
                         * w[..., None], axis=1)            # [T, Eh]
        ex = {k_: wlo(v_) for k_, v_ in moe["experts"].items()}

        def experts(b):
            hb = jax.lax.dynamic_slice_in_dim(h, b, BLOCK)
            wb = jax.lax.dynamic_slice_in_dim(held_w, b, BLOCK)
            g = jnp.einsum("td,edf->etf", hb, ex["w_gate"], precision=HI)
            u = jnp.einsum("td,edf->etf", hb, ex["w_up"], precision=HI)
            y = jnp.einsum("etf,efd->etd", alo(jax.nn.silu(g) * u),
                           ex["w_down"], precision=HI)
            return jnp.einsum("etd,te->td", y, wb, precision=HI)

        routed = jax.lax.map(experts, jnp.arange(0, T, BLOCK)).reshape(T, D)
        return x + routed + swiglu(h, moe["shared"]), None

    if "dense_layers" in params:
        x, _ = jax.lax.scan(dense_layer, x, params["dense_layers"])
    x, _ = jax.lax.scan(moe_layer, x, params["layers"])
    hid = _rms(x, params["final_norm"].astype(f32), eps)     # [T, D]
    z = jax.nn.relu(_mm(hid, scorer["w1"]) + scorer["b1"])
    score = jax.nn.sigmoid(_mm(z, scorer["w2"]) + scorer["b2"])[:, 0]
    head = wlo(params["lm_head"][:, :V].astype(f32))          # [D, V]

    def logits(i):
        hb = alo(jax.lax.dynamic_slice_in_dim(hid, i, BLOCK))
        lg = _mm(hb, head)                                    # [BLOCK, V]
        tb = jax.lax.dynamic_slice_in_dim(targets, i, BLOCK, axis=1)
        tgt = jnp.take_along_axis(lg, tb.T, axis=1).T        # [K, BLOCK]
        return jax.nn.logsumexp(lg, axis=-1), tgt

    lse, tgt = jax.lax.map(logits, jnp.arange(0, T, BLOCK))
    tgt = jnp.moveaxis(tgt, 1, 0).reshape(targets.shape[0], T)
    return {"lse": lse.reshape(T), "tgt": tgt, "score": score}
