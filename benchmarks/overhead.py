"""Appendix D: step-scorer computational overhead.

Paper formula: relative FLOPs per generated step
    2 m (d + 1) / (2 N t)
with m = 512 scorer hidden, d = model hidden, N = non-embedding params,
t = tokens per step. We report (a) the paper's analytic ratio for each
FULL config and (b) the measured XLA-FLOPs ratio (scorer vs decode step)
from cost_analysis on the serving model."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.registry import ASSIGNED_ARCHS, get_config, \
    serving_config
from repro.core.scorer import SCORER_HIDDEN, init_scorer, scorer_score
from repro.launch.compile_cache import enable_compile_cache
from repro.models.init import count_params, init_params, padded_vocab

# paper setting: t ~ 1e2 tokens per reasoning step (App. D); the synthetic
# task's steps are ~12 tokens, which only matters for the tiny serving
# model where the scorer is deliberately outsized relative to 1M params
AVG_TOKENS_PER_STEP = 100


def analytic_ratio(cfg) -> float:
    d = cfg.d_model
    V = padded_vocab(cfg)
    # shapes only — granite/deepseek full configs are 20-236B params
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    import numpy as np
    n_all = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    n = n_all - V * d * (1 if cfg.tie_embeddings else 2)
    return (2 * SCORER_HIDDEN * (d + 1)) / (2 * n * AVG_TOKENS_PER_STEP)


def measured_ratio() -> float:
    cfg = serving_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    scorer = init_scorer(jax.random.PRNGKey(1), cfg.d_model)

    from repro.models.model import decode_step, init_decode_cache
    B = 16
    cache = init_decode_cache(cfg, B, 256)
    toks = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)

    dec = jax.jit(lambda p, c: decode_step(p, cfg, toks, pos, c,
                                           window_len=256)).lower(
        params, cache).compile()
    sc = jax.jit(lambda sp, h: scorer_score(sp, h)).lower(
        scorer, jnp.zeros((B, cfg.d_model))).compile()
    f_dec = float(dec.cost_analysis().get("flops", 0.0))
    f_sc = float(sc.cost_analysis().get("flops", 0.0))
    return f_sc / max(f_dec, 1.0)


def run(verbose: bool = False):
    rows = []
    for arch in ("qwen3-1.7b", "granite-20b", "deepseek-v2-236b",
                 "phi4-mini-3.8b"):
        cfg = get_config(arch)
        rows.append({"arch": arch, "kind": "analytic_full_cfg",
                     "ratio": analytic_ratio(cfg)})
    rows.append({"arch": "serving-model", "kind": "measured_xla",
                 "ratio": measured_ratio()})
    return rows


def main():
    enable_compile_cache()
    rows = run()
    print("overhead: arch, kind, scorer_flops_ratio")
    for r in rows:
        print(f"{r['arch']},{r['kind']},{r['ratio']:.2e}")
    full = [r for r in rows if r["kind"] == "analytic_full_cfg"]
    # paper: <1e-6 for 4-14B models; our smallest assigned arch is 1.7B
    # so the bound relaxes proportionally
    assert all(r["ratio"] < 1e-5 for r in full), \
        "scorer overhead must be negligible"
    return rows


if __name__ == "__main__":
    main()
