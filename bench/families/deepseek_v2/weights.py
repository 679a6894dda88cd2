"""Random weights for DeepSeek-V2 (latent attention, a leading dense layer,
routed and shared experts), made from a key.

The harness makes the key from the configuration file's ``weights_seed``:
the weights stand for one checkpoint, the same in every run of a cell.
They are made in one jitted call on the device, in the type they are
served in (bf16; the step scorer in f32), one layer at a time where a
stack is large (so no f32 copy of a whole stack is ever held), and handed
to the engine and to the plain reference alike. The tree has the layout
the engine's ``params`` argument takes:

    embed [Vp, D], lm_head [D, Vp] (untied), final_norm [D],
    dense_layers (the first ``first_k_dense`` layers) and layers (the
    rest), each stacked over its layers:
        ln1, ln2 [n, D]
        attn: wq [n, D, H*(nope+rope)] (no q-LoRA), wkv_a [n, D, kv_lora
              + rope], kv_a_norm [n, kv_lora], wk_b [n, kv_lora, H*nope],
              wv_b [n, kv_lora, H*v], wo [n, H*v, D]
        dense_layers: mlp {w_gate/w_up [n, D, d_ff], w_down [n, d_ff, D]}
        layers: moe {router [n, D, E] (all experts),
                     experts {w_gate/w_up [n, Eh, D, F], w_down
                              [n, Eh, F, D]} (the held experts),
                     shared {w_gate/w_up [n, D, S*F], w_down [n, S*F, D]}}

Vp pads the vocabulary to a multiple of 256; the padded rows are zero and
never sampled. Norm scales are drawn around 1 so that a path which skips
a norm scale reads wrong. The head is untied, so the step token's
column of ``lm_head`` is scaled by ``step_row_scale``: that raises the
token's logit and makes sampled traces close a reasoning step every few
tens to a few hundred tokens.
"""
from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp


def _qwen3(part: str):
    path = Path(__file__).resolve().parents[1] / "qwen3" / f"{part}.py"
    spec = importlib.util.spec_from_file_location(f"_qwen3_{part}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_q = _qwen3("weights")
SCORER_HIDDEN, padded_vocab = _q.SCORER_HIDDEN, _q.padded_vocab
_normal, _norm_scale = _q._normal, _q._norm_scale


def _stack(key, n, shape, scale):
    """[n, *shape] bf16 normals, made one layer at a time."""
    return jax.lax.map(lambda k: _normal(k, shape, scale),
                       jax.random.split(key, n))


def _attn(ks, m, n, out_scale):
    D, H = m["d_model"], m["num_heads"]
    nd, rd, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    Lr = m["kv_lora_rank"]
    return {
        "wq": _stack(next(ks), n, (D, H * (nd + rd)), 1 / math.sqrt(D)),
        "wkv_a": _stack(next(ks), n, (D, Lr + rd), 1 / math.sqrt(D)),
        "kv_a_norm": _norm_scale(next(ks), (n, Lr)),
        "wk_b": _stack(next(ks), n, (Lr, H * nd), 1 / math.sqrt(Lr)),
        "wv_b": _stack(next(ks), n, (Lr, H * vd), 1 / math.sqrt(Lr)),
        "wo": _stack(next(ks), n, (H * vd, D), out_scale / math.sqrt(H * vd)),
    }


def _mlp(ks, m, n, F, out_scale):
    D = m["d_model"]
    return {"w_gate": _stack(next(ks), n, (D, F), 1 / math.sqrt(D)),
            "w_up": _stack(next(ks), n, (D, F), 1 / math.sqrt(D)),
            "w_down": _stack(next(ks), n, (F, D), out_scale / math.sqrt(F))}


def make_weights(m: tuple, key: jax.Array, step_id: int,
                 step_row_scale: float) -> tuple:
    """(params, scorer) for the model sizes ``m`` (the items of a
    configuration file's ``model`` group). Call under ``jax.jit`` with
    ``m``, ``step_id`` and ``step_row_scale`` static."""
    m = dict(m)
    D, L, K = m["d_model"], m["num_layers"], m["first_k_dense"]
    F, Eh = m["moe_d_ff"], m["experts_held"]
    V, Vp = m["vocab_size"], padded_vocab(m["vocab_size"])
    ks = iter(jax.random.split(key, 40))
    out_scale = 1.0 / math.sqrt(2 * L)
    live = jnp.arange(Vp) < V
    embed = jnp.where(live[:, None], _normal(next(ks), (Vp, D), 0.02), 0)
    head = _normal(next(ks), (D, Vp), 1 / math.sqrt(D), jnp.float32)
    head = head.at[:, step_id].multiply(step_row_scale)
    head = jnp.where(live[None, :], head, 0.0).astype(jnp.bfloat16)

    def layers(n, moe):
        p = {"ln1": _norm_scale(next(ks), (n, D)),
             "ln2": _norm_scale(next(ks), (n, D)),
             "attn": _attn(ks, m, n, out_scale)}
        if not moe:
            p["mlp"] = _mlp(ks, m, n, m["d_ff"], out_scale)
            return p
        experts = jax.lax.map(
            lambda k: _mlp(iter(jax.random.split(k, 3)), m, Eh, F,
                           out_scale),
            jax.random.split(next(ks), n))
        p["moe"] = {
            "router": _stack(next(ks), n, (D, m["num_experts"]),
                             1 / math.sqrt(D)),
            "experts": experts,
            "shared": _mlp(ks, m, n, F * m["num_shared_experts"], out_scale),
        }
        return p

    params = {"embed": embed.astype(jnp.bfloat16), "lm_head": head,
              "final_norm": _norm_scale(next(ks), (D,)),
              "dense_layers": layers(K, False), "layers": layers(L - K, True)}
    scorer = {
        "w1": _normal(next(ks), (D, SCORER_HIDDEN), math.sqrt(2.0 / D),
                      jnp.float32),
        "b1": _normal(next(ks), (SCORER_HIDDEN,), 0.1, jnp.float32),
        "w2": _normal(next(ks), (SCORER_HIDDEN, 1),
                      math.sqrt(1.0 / SCORER_HIDDEN), jnp.float32),
        "b2": _normal(next(ks), (1,), 0.1, jnp.float32),
    }
    return params, scorer
