"""Plain reference for a dense Qwen3 decoder, written from the published
architecture and independent of the program under test.

One sequence at a time, in float32 at the highest matmul precision, layer
by layer (a scan over the stacked layer weights, each layer upcast on its
own), with attention and the output head in blocks of rows so that a long
sequence fits beside nothing else. Qwen3: RMSNorm before attention and
MLP, RMSNorm of each query and key head (qk-norm), rotary embedding on
the two halves of a head, grouped-query attention (query head h reads KV
head h // (H / KVH)), SwiGLU MLP, a final RMSNorm and an output head tied
to the embedding.

``low`` computes the same forward pass below the stated precision (bf16):
the control of the comparison. Every weight matrix is rounded to fp8
(e4m3) with one scale per output column (per embedding row for the tied
head), every matmul input and every head of K and V to fp8 with one scale
per row: the rounding of an fp8 KV pool or fp8 matmuls, the step below
bf16 that a faster path would take.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
BLOCK = 256          # rows of queries / output-head rows per block


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


F8_MAX = 448.0       # largest finite float8_e4m3fn


def _f8(x, axis):
    """x rounded to fp8 (e4m3) with one symmetric scale per slice along
    ``axis`` (the scale maps the slice's largest magnitude to F8_MAX)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _f8_cols(w):
    """fp8 with one scale per output column of w [in, out]."""
    return _f8(w, 0)


def _f8_rows(x):
    """fp8 with one scale per row of x [..., in]."""
    return _f8(x, -1)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv          # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("m", "low"))
def forward(params, scorer, tokens, targets, *, m, low=False):
    """tokens [T] (T a multiple of BLOCK); targets [K, T] (targets[k, i] is
    a token whose logit at position i is asked about). ``m`` is the
    configuration's ``model`` group as a tuple of items.

    Returns per position: the log-sum-exp, each target's logit, and the
    step scorer's score of the hidden state.
    """
    m = dict(m)
    D, H, KVH, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], \
        m["head_dim"]
    V, eps, theta = m["vocab_size"], m["norm_eps"], m["rope_theta"]
    G = H // KVH
    T = tokens.shape[0]
    f32 = jnp.float32
    keep = lambda a: a  # noqa: E731
    wlo, alo = (_f8_cols, _f8_rows) if low else (keep, keep)

    emb = params["embed"][:V].astype(f32)                   # [V, D]
    if low:
        emb = _f8_cols(emb.T).T
    pos = jnp.arange(T)
    x = emb[tokens]

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        a, mlp = lp["attn"], lp["mlp"]
        h = alo(_rms(x, lp["ln1"], eps))
        q = _mm(h, wlo(a["wq"])).reshape(T, H, hd)
        k = _mm(h, wlo(a["wk"])).reshape(T, KVH, hd)
        v = _mm(h, wlo(a["wv"])).reshape(T, KVH, hd)
        q = _rope(_rms(q, a["q_norm"], eps), pos, theta)
        k = _rope(_rms(k, a["k_norm"], eps), pos, theta)
        k, v = alo(k), alo(v)
        kg = jnp.repeat(k, G, axis=1)                       # [T, H, hd]
        vg = jnp.repeat(v, G, axis=1)

        def attend(qb_start):
            qb = jax.lax.dynamic_slice_in_dim(q, qb_start, BLOCK)
            s = jnp.einsum("qhd,khd->hqk", qb, kg, precision=HI)
            s = s / jnp.sqrt(f32(hd))
            qpos = qb_start + jnp.arange(BLOCK)
            s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s,
                          -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("hqk,khd->qhd", p, vg, precision=HI)

        o = jax.lax.map(attend, jnp.arange(0, T, BLOCK))
        o = o.reshape(T, H * hd)
        x = x + _mm(alo(o), wlo(a["wo"]))
        h = alo(_rms(x, lp["ln2"], eps))
        g = _mm(h, wlo(mlp["w_gate"]))
        u = _mm(h, wlo(mlp["w_up"]))
        x = x + _mm(alo(jax.nn.silu(g) * u), wlo(mlp["w_down"]))
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    hid = _rms(x, params["final_norm"].astype(f32), eps)     # [T, D]
    z = jax.nn.relu(_mm(hid, scorer["w1"]) + scorer["b1"])
    score = jax.nn.sigmoid(_mm(z, scorer["w2"]) + scorer["b2"])[:, 0]

    def head(i):
        hb = alo(jax.lax.dynamic_slice_in_dim(hid, i, BLOCK))
        lg = _mm(hb, emb.T)                                  # [BLOCK, V]
        tb = jax.lax.dynamic_slice_in_dim(targets, i, BLOCK, axis=1)
        tgt = jnp.take_along_axis(lg, tb.T, axis=1).T        # [K, BLOCK]
        return jax.nn.logsumexp(lg, axis=-1), tgt

    lse, tgt = jax.lax.map(head, jnp.arange(0, T, BLOCK))
    tgt = jnp.moveaxis(tgt, 1, 0).reshape(targets.shape[0], T)
    return {"lse": lse.reshape(T), "tgt": tgt, "score": score}
