"""Prefix-sharing benchmark: prompt prefill cost, shared vs per-trace.

The STEP paper's serving engine fans one prompt out into N traces. Without
prefix sharing the engine prefills the identical prompt N times (N
sequential full-sequence forwards) and each trace owns private copies of
the prompt's KV blocks. With ``EngineConfig.share_prompt_prefix`` the
prompt is prefilled ONCE, its blocks are forked (refcount++) into every
trace's block table, and each trace copy-on-writes only the prompt's tail
block when its first generated token lands there.

Reported per mode: prefill seconds, peak pool blocks in use, and the
generated tokens (greedy), which must be identical across modes.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import time

import jax

from repro.configs.registry import serving_config
from repro.core.pruning import make_policy
from repro.data.arithmetic import gen_problem, make_prompt
from repro.data.tokenizer import get_tokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models.init import init_params
from repro.serving import Engine, EngineConfig, SamplingParams

N_TRACES = 16
MAX_NEW = 32
NUM_BLOCKS = 160   # roomy pool: isolate prefill cost from contention
CAPACITY = 128
MIN_SPEEDUP = 5.0  # acceptance floor at N=16


def _build_engine(params, cfg, share: bool) -> Engine:
    ecfg = EngineConfig(
        max_batch=N_TRACES, num_blocks=NUM_BLOCKS, capacity=CAPACITY,
        max_new_tokens=MAX_NEW,
        sampling=SamplingParams(temperature=0.0, top_k=0, top_p=1.0,
                                max_new_tokens=MAX_NEW),
        share_prompt_prefix=share,
        # cache off: this benchmark isolates WITHIN-request sharing; a
        # cross-request hit would zero the very prefill being measured
        prefix_cache=False)
    return Engine(params, cfg, ecfg, make_policy("sc"))


def run(verbose: bool = False):
    cfg = serving_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer()
    # a multi-block prompt (> 2 full KV blocks) so full blocks are shared,
    # not just COW-duplicated tail blocks
    problem = gen_problem(random.Random(7), n_steps=(14, 16))
    prompt = tok.encode(make_prompt(problem), add_bos=True)
    if verbose:
        print(f"prompt: {len(prompt)} tokens "
              f"({-(-len(prompt) // cfg.kv_block_size)} blocks)")

    rows = []
    for share in (True, False):
        eng = _build_engine(params, cfg, share)
        eng.serve(prompt, 1)  # warm the jit caches outside the timed run
        t0 = time.perf_counter()
        res = eng.serve(prompt, N_TRACES)
        wall = time.perf_counter() - t0
        assert eng.block_mgr.free_blocks == eng.block_mgr.num_blocks - 1
        eng.block_mgr.check_invariants()
        rows.append({
            "mode": "shared" if share else "per-trace",
            "prefill_s": res.prefill_s,
            "wall_s": wall,
            "peak_blocks": res.peak_blocks_used,
            "tokens": [t.output_tokens for t in res.traces],
        })
        if verbose:
            print(f"  {rows[-1]['mode']}: prefill={res.prefill_s:.3f}s "
                  f"wall={wall:.2f}s peak_blocks={res.peak_blocks_used}")
    return rows


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_prefill_sharing.json"))
    args, _ = ap.parse_known_args()
    rows = run(verbose=True)
    shared = next(r for r in rows if r["mode"] == "shared")
    private = next(r for r in rows if r["mode"] == "per-trace")
    print("prefill_sharing: mode, prefill_s, wall_s, peak_blocks")
    for r in rows:
        print(f"{r['mode']},{r['prefill_s']:.3f},{r['wall_s']:.2f},"
              f"{r['peak_blocks']}")

    assert shared["tokens"] == private["tokens"], \
        "greedy outputs must be identical across prefill modes"
    speedup = private["prefill_s"] / max(shared["prefill_s"], 1e-9)
    saved = private["peak_blocks"] - shared["peak_blocks"]
    print(f"# prefill speedup {speedup:.1f}x at N={N_TRACES} "
          f"(identical greedy outputs); {saved} fewer peak blocks")
    assert speedup >= MIN_SPEEDUP, \
        f"expected >= {MIN_SPEEDUP}x prefill reduction, got {speedup:.1f}x"

    out = os.path.abspath(args.out)
    payload = {
        "benchmark": "prefill_sharing",
        "config": {"n_traces": N_TRACES, "max_new_tokens": MAX_NEW,
                   "num_blocks": NUM_BLOCKS, "capacity": CAPACITY},
        "prefill_speedup_x": speedup,
        "peak_blocks_saved": saved,
        "shared": {k: shared[k] for k in
                   ("prefill_s", "wall_s", "peak_blocks")},
        "per_trace": {k: private[k] for k in
                      ("prefill_s", "wall_s", "peak_blocks")},
    }
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out}")
    return rows


if __name__ == "__main__":
    main()
