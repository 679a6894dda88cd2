"""Sharded-serving benchmark: the Engine over a simulated 4-device mesh.

Runs the same greedy workload on a single-device engine and on a
``(data=2, model=2)`` mesh engine (4 simulated CPU devices via
``--xla_force_host_platform_device_count``), asserts the generations are
token-identical — the exactness-preserving TP layout's contract, see
docs/ENGINE.md "Sharded serving" — and reports throughput for both.

On simulated CPU devices the mesh path pays real collective overhead
for no real parallelism (all "devices" share the host), so the sharded
throughput is EXPECTED to trail the single-device engine here; the
structural fields (identity, completion, token counts) are the tight CI
gate, the throughput ratio only a collapse guard. On a real accelerator
mesh the same code path is where the >1-chip memory and compute scaling
comes from.

Writes ``BENCH_sharded.json`` — uploaded and regression-checked by the
CI benchmark-smoke job against ``benchmarks/reference/``.

    python -m benchmarks.sharded_serving [--out path.json]
"""
from __future__ import annotations

import os

# must happen before jax initializes: on a CPU host, simulate 4 devices
# unless the caller already forced a device count (an accelerator host
# uses its real devices)
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

import argparse  # noqa: E402  (env must be set before jax imports)
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

from benchmarks.common import bench_requests  # noqa: E402
from repro.configs.registry import serving_config  # noqa: E402
from repro.core.pruning import make_policy  # noqa: E402
from repro.core.trace import TraceStatus  # noqa: E402
from repro.data.tokenizer import get_tokenizer  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models.init import init_params  # noqa: E402
from repro.serving import Engine, EngineConfig, SamplingParams  # noqa: E402

MESH_SHAPE = (2, 2)  # (data, model)
N_REQUESTS = 2
N_TRACES = 4
MAX_NEW = 64
NUM_BLOCKS = 96
CAPACITY = 128
DECODE_HORIZON = 4
SEED = 1234
# init seed chosen so the random-init model's greedy generations run to
# the token cap under partitionable-threefry init (JAX's default);
# early-EOS seeds leave too few decode ticks
PARAMS_SEED = 0


def bench_config():
    """Small serving-smoke variant (random init: identity and relative
    throughput need no trained weights). Sized so the mesh engine's
    per-tick collectives are visible but the run stays CI-friendly."""
    return dataclasses.replace(
        serving_config(), num_layers=2, d_model=64, d_ff=128,
        num_heads=4, num_kv_heads=2, head_dim=16)


def _requests(tok):
    return bench_requests(tok, N_REQUESTS, N_TRACES, seed=SEED)


def _run_engine(engine, tok):
    engine.serve_batch(_requests(tok))  # warm the jit caches
    wall = float("inf")
    results = None
    for _ in range(3):
        requests = _requests(tok)
        jax.block_until_ready(engine.params)
        t0 = time.perf_counter()
        results = engine.serve_batch(requests)
        wall = min(wall, time.perf_counter() - t0)
        for r in results:
            assert all(t.status == TraceStatus.FINISHED for t in r.traces)
        assert (engine.block_mgr.free_blocks
                == engine.block_mgr.num_blocks - 1)
        engine.block_mgr.check_invariants()
    tokens = sum(r.total_tokens for r in results)
    outputs = [[t.output_tokens for t in r.traces] for r in results]
    return {"tokens": tokens, "wall_s": wall,
            "tok_per_s": tokens / wall}, outputs


def run(verbose: bool = False) -> dict:
    if jax.device_count() < MESH_SHAPE[0] * MESH_SHAPE[1]:
        raise SystemExit(
            f"needs {MESH_SHAPE[0] * MESH_SHAPE[1]} devices, have "
            f"{jax.device_count()}; run with XLA_FLAGS="
            f"--xla_force_host_platform_device_count=4")
    cfg = bench_config()
    params = init_params(cfg, jax.random.PRNGKey(PARAMS_SEED))
    tok = get_tokenizer()
    ecfg = EngineConfig(
        max_batch=N_REQUESTS * N_TRACES, num_blocks=NUM_BLOCKS,
        capacity=CAPACITY, max_new_tokens=MAX_NEW,
        sampling=SamplingParams(temperature=0.0, top_k=0, top_p=1.0,
                                max_new_tokens=MAX_NEW),
        decode_horizon=DECODE_HORIZON,
        # cache off: single vs mesh replays the same prompts — warm hits
        # would skip prefill work and invalidate the blessed timings
        prefix_cache=False)

    single = Engine(params, cfg, ecfg, make_policy("sc"))
    stats_single, out_single = _run_engine(single, tok)
    if verbose:
        print(f"single-device: {stats_single['tokens']} tokens, "
              f"{stats_single['tok_per_s']:.1f} tok/s")

    mesh = make_host_mesh(*MESH_SHAPE)
    sharded = Engine(params, cfg, ecfg, make_policy("sc"), mesh=mesh)
    stats_sharded, out_sharded = _run_engine(sharded, tok)
    if verbose:
        print(f"mesh {MESH_SHAPE}: {stats_sharded['tokens']} tokens, "
              f"{stats_sharded['tok_per_s']:.1f} tok/s")

    # the contract: sharding must not change a single generated token
    assert out_sharded == out_single, "mesh generations diverged"

    payload = {
        "benchmark": "sharded_serving",
        "config": {
            "devices": jax.device_count(),
            "mesh": {"data": MESH_SHAPE[0], "model": MESH_SHAPE[1]},
            "n_requests": N_REQUESTS, "n_traces": N_TRACES,
            "max_new_tokens": MAX_NEW, "num_blocks": NUM_BLOCKS,
            "capacity": CAPACITY, "decode_horizon": DECODE_HORIZON,
            "seed": SEED,
        },
        "outputs_identical": True,
        "single": stats_single,
        "sharded": stats_sharded,
        "sharded_over_single_x": (stats_sharded["tok_per_s"]
                                  / stats_single["tok_per_s"]),
    }
    if verbose:
        print(f"sharded/single throughput: "
              f"x{payload['sharded_over_single_x']:.2f} "
              f"(simulated devices: overhead-only, see docstring)")
    return payload


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_sharded.json"))
    args = ap.parse_args()
    payload = run(verbose=True)
    out = os.path.abspath(args.out)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out}")
    return payload


if __name__ == "__main__":
    main()
