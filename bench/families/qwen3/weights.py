"""Random weights for a dense Qwen3-style decoder, made from a key.

The harness makes the key from the configuration file's ``weights_seed``:
the weights stand for one checkpoint, the same in every run of a cell.

The benchmark makes the weights itself, in one jitted call on the device,
in the type they are served in (bf16; the step scorer in f32), and hands
the same arrays to the engine and to the plain reference. The tree has the
layout the engine's ``params`` argument takes:

    embed [Vp, D] (tied output head), final_norm [D],
    layers: ln1, ln2 [L, D]; attn: wq [L, D, H*hd], wk/wv [L, D, KVH*hd],
            wo [L, H*hd, D], q_norm/k_norm [L, hd];
            mlp: w_gate/w_up [L, D, F], w_down [L, F, D]

Vp pads the vocabulary to a multiple of 256; the padded rows are zero and
never sampled. Norm scales are drawn around 1 so that a path which skips a
norm scale reads wrong. The step token's embedding row is scaled by
``step_row_scale``: the output head is tied, so this raises that token's
logit and makes sampled traces cross a step boundary every few tens to a
few hundred tokens, as thinking traces break paragraphs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SCORER_HIDDEN = 512   # STEP paper, Appendix A: d_model -> 512 (ReLU) -> 1


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def _normal(key, shape, scale, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _norm_scale(key, shape):
    return jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2).astype(
        jnp.bfloat16)


def make_weights(m: tuple, key: jax.Array, step_id: int,
                 step_row_scale: float) -> tuple:
    """(params, scorer) for the model sizes ``m`` (the items of a
    configuration file's ``model`` group). Call under ``jax.jit`` with
    ``m``, ``step_id`` and ``step_row_scale`` static."""
    m = dict(m)
    D, L = m["d_model"], m["num_layers"]
    H, KVH, hd, F = m["num_heads"], m["num_kv_heads"], m["head_dim"], m["d_ff"]
    V, Vp = m["vocab_size"], padded_vocab(m["vocab_size"])
    ks = iter(jax.random.split(key, 20))
    out_scale = 1.0 / math.sqrt(2 * L)
    embed = _normal(next(ks), (Vp, D), 0.02, jnp.float32)
    embed = embed.at[step_id].multiply(step_row_scale)
    embed = jnp.where(jnp.arange(Vp)[:, None] < V, embed, 0.0)
    params = {
        "embed": embed.astype(jnp.bfloat16),
        "final_norm": _norm_scale(next(ks), (D,)),
        "layers": {
            "ln1": _norm_scale(next(ks), (L, D)),
            "ln2": _norm_scale(next(ks), (L, D)),
            "attn": {
                "wq": _normal(next(ks), (L, D, H * hd), 1 / math.sqrt(D)),
                "wk": _normal(next(ks), (L, D, KVH * hd), 1 / math.sqrt(D)),
                "wv": _normal(next(ks), (L, D, KVH * hd), 1 / math.sqrt(D)),
                "wo": _normal(next(ks), (L, H * hd, D),
                              out_scale / math.sqrt(H * hd)),
                "q_norm": _norm_scale(next(ks), (L, hd)),
                "k_norm": _norm_scale(next(ks), (L, hd)),
            },
            "mlp": {
                "w_gate": _normal(next(ks), (L, D, F), 1 / math.sqrt(D)),
                "w_up": _normal(next(ks), (L, D, F), 1 / math.sqrt(D)),
                "w_down": _normal(next(ks), (L, F, D),
                                  out_scale / math.sqrt(F)),
            },
        },
    }
    scorer = {
        "w1": _normal(next(ks), (D, SCORER_HIDDEN), math.sqrt(2.0 / D),
                      jnp.float32),
        "b1": _normal(next(ks), (SCORER_HIDDEN,), 0.1, jnp.float32),
        "w2": _normal(next(ks), (SCORER_HIDDEN, 1),
                      math.sqrt(1.0 / SCORER_HIDDEN), jnp.float32),
        "b2": _normal(next(ks), (1,), 0.1, jnp.float32),
    }
    return params, scorer
