"""Decode-throughput benchmark: tokens/s vs ``decode_horizon``.

Serves the same greedy workload at decode_horizon 1, 4 and 8 and
measures end-to-end decode throughput. The horizon fuses K decode
iterations (model step + sampling + confidence + step-boundary scoring)
into one jitted ``lax.scan`` call, so the per-token host cost — jit
dispatch, device->host sync, the Python tick — amortizes over K tokens.
Outputs are asserted token-identical across horizons (greedy), so the
speedup is pure scheduling, not different generations.

Writes ``BENCH_decode.json`` — uploaded and regression-checked by the CI
benchmark-smoke job against ``benchmarks/reference/`` (the ``min_abs``
rule pins the acceptance floor: >= 1.5x tokens/s at horizon 8).

Uses randomly-initialised weights (perf numbers don't need a trained
model) on a deliberately small model variant: per-token model compute is
the same work at every horizon (the scan runs the full step per
iteration), so on the CI CPU runners — where XLA's per-op overhead makes
even the smoke model's step several ms — a larger model would only bury
the scheduling overhead this benchmark exists to measure. On a real
accelerator the step is orders of magnitude faster and the horizon's
amortization applies at full model scale.

    PYTHONPATH=src python -m benchmarks.decode_throughput [--out path.json]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax

from benchmarks.common import bench_requests
from repro.configs.registry import serving_config
from repro.core.pruning import make_policy
from repro.core.trace import TraceStatus
from repro.data.tokenizer import get_tokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models.init import init_params
from repro.serving import Engine, EngineConfig, SamplingParams

HORIZONS = (1, 4, 8)
N_REQUESTS = 2
N_TRACES = 4
MAX_NEW = 96
NUM_BLOCKS = 96
CAPACITY = 128
SEED = 1234
# init seed chosen so the random-init model's greedy generations run to
# the token cap (several seeds emit EOS after ~10 tokens, leaving too
# few decode ticks to measure)
PARAMS_SEED = 1


def bench_config():
    """Small-batch decode-bound regime (see module docstring). Sized so
    the per-iteration model step leaves the per-tick host overhead as
    the dominant cost at horizon 1 — the quantity the horizon
    amortizes — with enough headroom over the CI gate's 1.5x floor to
    absorb shared-runner timing noise."""
    return dataclasses.replace(
        serving_config(), num_layers=1, d_model=32, d_ff=64,
        num_heads=2, num_kv_heads=2, head_dim=16)


def _requests(tok):
    return bench_requests(tok, N_REQUESTS, N_TRACES, seed=SEED)


def run(verbose: bool = False) -> dict:
    cfg = bench_config()
    params = init_params(cfg, jax.random.PRNGKey(PARAMS_SEED))
    tok = get_tokenizer()

    per_horizon = {}
    outputs = {}
    for K in HORIZONS:
        ecfg = EngineConfig(
            max_batch=N_REQUESTS * N_TRACES, num_blocks=NUM_BLOCKS,
            capacity=CAPACITY, max_new_tokens=MAX_NEW,
            sampling=SamplingParams(temperature=0.0, top_k=0, top_p=1.0,
                                    max_new_tokens=MAX_NEW),
            decode_horizon=K,
            # cache off: the warmup pass replays the timed prompts — warm
            # hits would skip prefill and distort the horizon comparison
            prefix_cache=False)
        engine = Engine(params, cfg, ecfg, make_policy("sc"))
        # warm the jit caches with the full request set (prefill has one
        # compile per prompt length, first-token flush one per admission
        # wave width) so the timed pass measures steady-state scheduling
        engine.serve_batch(_requests(tok))

        # best of 5 timed replays (CI runners are noisy; the scheduler
        # is deterministic so every replay generates identical traces)
        wall = float("inf")
        for _ in range(5):
            requests = _requests(tok)
            fallbacks_before = engine.counters.horizon_fallbacks
            jax.block_until_ready(params)  # nothing in flight before t0
            t0 = time.perf_counter()
            results = engine.serve_batch(requests)
            # every timed quantity below is host data, so the device
            # work is fully drained here; block_until_ready pins t0
            wall = min(wall, time.perf_counter() - t0)

            for r in results:
                assert all(t.status == TraceStatus.FINISHED
                           for t in r.traces)
            assert (engine.block_mgr.free_blocks
                    == engine.block_mgr.num_blocks - 1)
            engine.block_mgr.check_invariants()

        tokens = sum(r.total_tokens for r in results)
        decode_s = sum(r.decode_s for r in results)
        outputs[K] = [
            [t.output_tokens for t in r.traces] for r in results]
        per_horizon[str(K)] = {
            "tokens": tokens,
            "wall_s": wall,
            "decode_s": decode_s,
            "tok_per_s": tokens / wall,
            # per-replay count (the schedule is deterministic, so every
            # replay falls back identically)
            "horizon_fallbacks": (engine.counters.horizon_fallbacks
                                  - fallbacks_before),
        }
        if verbose:
            print(f"decode_horizon={K}: {tokens} tokens in {wall:.2f}s "
                  f"({tokens / wall:.1f} tok/s, "
                  f"decode {decode_s:.2f}s)")

    # greedy outputs must be identical at every horizon — the speedup is
    # scheduling, not different generations
    for K in HORIZONS[1:]:
        assert outputs[K] == outputs[HORIZONS[0]], (
            f"horizon {K} diverged from horizon {HORIZONS[0]}")

    base = per_horizon["1"]["tok_per_s"]
    payload = {
        "benchmark": "decode_throughput",
        "config": {
            "n_requests": N_REQUESTS, "n_traces": N_TRACES,
            "max_new_tokens": MAX_NEW, "num_blocks": NUM_BLOCKS,
            "capacity": CAPACITY, "horizons": list(HORIZONS),
            "seed": SEED,
        },
        "horizons": per_horizon,
        "outputs_identical": True,
        "speedup_4x": per_horizon["4"]["tok_per_s"] / base,
        "speedup_8x": per_horizon["8"]["tok_per_s"] / base,
    }
    if verbose:
        print(f"speedup: x{payload['speedup_4x']:.2f} @K=4, "
              f"x{payload['speedup_8x']:.2f} @K=8")
    return payload


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_decode.json"))
    args = ap.parse_args()
    payload = run(verbose=True)
    out = os.path.abspath(args.out)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out}")
    return payload


if __name__ == "__main__":
    main()
