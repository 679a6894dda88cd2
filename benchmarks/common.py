"""Shared benchmark artifacts: the trained reasoning LM + step scorer.

Built once (``python -m benchmarks.common``) and cached under
``benchmarks/artifacts/``; every table/figure benchmark loads from here so
results are comparable across benchmarks.
"""
from __future__ import annotations

import json
import os
from typing import Optional, Tuple

import jax
import numpy as np

from repro.configs.registry import serving_config
from repro.core.pipeline import build_step_scorer
from repro.core.scorer import init_scorer
from repro.launch.compile_cache import enable_compile_cache
from repro.models.init import init_params
from repro.training.checkpoint import load_pytree, save_pytree
from repro.training.trainer import TrainConfig, train_lm

ART_DIR = os.path.join(os.path.dirname(__file__), "artifacts")
MODEL_PATH = os.path.join(ART_DIR, "model.npz")
SCORER_PATH = os.path.join(ART_DIR, "scorer.npz")
INFO_PATH = os.path.join(ART_DIR, "info.json")

TRAIN_STEPS = int(os.environ.get("REPRO_TRAIN_STEPS", "4000"))


def build_artifacts(verbose: bool = True) -> None:
    cfg = serving_config()
    os.makedirs(ART_DIR, exist_ok=True)
    tcfg = TrainConfig(steps=TRAIN_STEPS, seq_len=128, batch_size=32,
                       peak_lr=2e-3, warmup=100, log_every=100)
    if verbose:
        print(f"[artifacts] training LM for {tcfg.steps} steps ...")
    params, history = train_lm(cfg, tcfg, verbose=verbose)
    save_pytree(MODEL_PATH, params)

    if verbose:
        print("[artifacts] building step scorer (sample -> verify -> train)")
    scorer, info = build_step_scorer(params, cfg, n_problems=96,
                                     n_samples=8, per_class=160,
                                     verbose=verbose)
    save_pytree(SCORER_PATH, scorer)
    with open(INFO_PATH, "w") as f:
        json.dump({"train_final_loss": history[-1]["loss"],
                   "scorer_info": {k: v for k, v in info.items()
                                   if k != "history"}}, f, indent=2)
    if verbose:
        print(f"[artifacts] done: correct-rate="
              f"{info['sampled_correct_rate']:.2f} "
              f"steps={info['num_steps']} "
              f"fallback={info['fallback_rendered']}")


def bench_requests(tok, n_requests: int, n_traces: int, seed: int,
                   n_steps=(8, 12), method: str = "sc") -> list:
    """The shared synthetic request workload of the engine perf
    benchmarks (decode_throughput, sharded_serving): deterministic
    problems rendered to prompts, one fresh policy per request."""
    from repro.core.pruning import make_policy
    from repro.data.arithmetic import make_prompt
    from repro.serving import Request, make_problems

    problems = make_problems(n_requests, seed=seed, n_steps=n_steps)
    return [
        Request(request_id=i,
                prompt_tokens=tok.encode(make_prompt(p), add_bos=True),
                n_traces=n_traces, policy=make_policy(method))
        for i, p in enumerate(problems)
    ]


def load_artifacts() -> Tuple[dict, dict, dict]:
    """Returns (params, scorer_params, cfg). Builds on first use."""
    cfg = serving_config()
    if not (os.path.exists(MODEL_PATH) and os.path.exists(SCORER_PATH)):
        build_artifacts()
    like_params = init_params(cfg, jax.random.PRNGKey(0))
    params = load_pytree(MODEL_PATH, like_params)
    like_scorer = init_scorer(jax.random.PRNGKey(0), cfg.d_model)
    scorer = load_pytree(SCORER_PATH, like_scorer)
    return params, scorer, cfg


if __name__ == "__main__":
    enable_compile_cache()
    build_artifacts()
