"""Table 4: GPU-memory sensitivity — STEP accuracy as the KV pool budget
varies (paper sweeps utilisation 0.5-0.9; smaller pools trigger pruning
earlier). The claim: accuracy is stable across budgets because the
scorer identifies promising traces early.

``--kv-quant`` runs the quantized-pool leg instead: a FIXED HBM byte
budget is converted to ``num_blocks`` per ``kv_dtype`` via
``kv_quant.pool_block_bytes``, so cheaper pool dtypes literally buy more
blocks, and the engine serves the same STEP workload under each dtype.
Reported per dtype: blocks afforded, traces sustained to completion
(unpruned), accuracy, and the scorer's pooled pairwise rank accuracy
(the Fig. 5 metric, computed from engine step scores) — emitted as
``BENCH_kv_quant.json`` and gated by ``check_regression`` (int8 must
sustain >= 1.8x the f32 trace count at the same byte budget with rank
accuracy within the drift bound; bf16 must stay token-identical to
f32)."""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from benchmarks.common import load_artifacts
from repro.core.pruning import make_policy
from repro.core.scorer import rank_accuracy
from repro.core.trace import TraceStatus
from repro.data.arithmetic import make_prompt
from repro.data.tokenizer import get_tokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models import kv_quant
from repro.serving import Engine, EngineConfig, SamplingParams, \
    evaluate_method, make_problems

N_PROBLEMS = 6
N_TRACES = 16
MAX_NEW = 120
# num_blocks fractions of the "full" pool (16 traces x 9 blocks each)
FRACTIONS = (0.5, 0.6, 0.7, 0.8, 0.9)
FULL_BLOCKS = 16 * 9

# --kv-quant leg: the byte budget every dtype must fit in, expressed as
# the f32 pool size that makes STEP prune hard (the regime where extra
# blocks translate into sustained traces). Problems are a notch easier
# than the fraction sweep's (4-6 steps vs 6-9) so the sustained traces
# populate BOTH answer classes — the pooled rank-accuracy metric needs
# correct and incorrect finished traces (the tiny artifact model's
# sampled-correct rate is ~9%, see benchmarks/artifacts/info.json)
KVQ_PROBLEMS = 6
KVQ_N_STEPS = (4, 6)
KVQ_F32_BLOCKS = 14


def run(verbose: bool = False):
    params, scorer, cfg = load_artifacts()
    problems = make_problems(N_PROBLEMS, seed=67, n_steps=(6, 9))
    rows = []
    for frac in FRACTIONS:
        blocks = max(8, int(FULL_BLOCKS * frac))
        # per-trace prefill: the sweep's "memory full" thresholds assume
        # every trace owns private prompt blocks; sharing would shift the
        # pruning onset per budget (docs/ENGINE.md, memory accounting)
        ecfg = EngineConfig(max_batch=N_TRACES, num_blocks=blocks,
                            capacity=256, max_new_tokens=MAX_NEW,
                            sampling=SamplingParams(max_new_tokens=MAX_NEW),
                            share_prompt_prefix=False)
        res = evaluate_method("step", params, cfg, problems, N_TRACES,
                              ecfg, scorer_params=scorer, verbose=verbose)
        rows.append({"memory_fraction": frac, "num_blocks": blocks,
                     "accuracy": res.accuracy,
                     "pruned": res.num_pruned,
                     "wait_s": res.total_wait_s})
    return rows


def run_kv_quant(verbose: bool = False):
    """Fixed-HBM sweep over ``kv_dtype``: every dtype gets
    ``budget // pool_block_bytes(dtype)`` blocks and serves the same
    STEP workload. Sustained = traces finishing unpruned (deterministic:
    the engine RNG is seeded per serve). A separate equal-blocks bf16
    leg checks token identity against f32 — at the shared byte budget
    bf16 affords 2x the blocks, which changes the pruning schedule and
    thus the tokens by construction, so identity is only meaningful when
    nothing but the pool dtype differs."""
    params, scorer, cfg = load_artifacts()
    tok = get_tokenizer()
    problems = make_problems(KVQ_PROBLEMS, seed=67, n_steps=KVQ_N_STEPS)
    budget = KVQ_F32_BLOCKS * kv_quant.pool_block_bytes(cfg, "f32")

    dtypes = ["f32", "bf16", "int8", "fp8"]  # fp8 informational: gates
    # cover int8 only

    def serve_leg(dt, nb):
        ecfg = EngineConfig(
            max_batch=N_TRACES, num_blocks=nb, capacity=256,
            max_new_tokens=MAX_NEW, kv_dtype=dt,
            sampling=SamplingParams(max_new_tokens=MAX_NEW),
            share_prompt_prefix=False)
        sustained = pruned = correct_q = 0
        pos, neg = [], []
        toks = []
        for qid, p in enumerate(problems):
            eng = Engine(params, cfg, ecfg, make_policy("step"),
                         scorer_params=scorer)
            res = eng.serve(tok.encode(make_prompt(p), add_bos=True),
                            N_TRACES, request_id=qid)
            assert eng.pool_drained()
            pruned += res.num_pruned
            correct_q += int(res.answer is not None
                             and int(res.answer) == p.answer)
            for t in res.traces:
                toks.append(t.output_tokens)
                if t.status != TraceStatus.FINISHED:
                    continue
                sustained += 1
                ok = (t.answer is not None
                      and t.answer == str(p.answer))
                (pos if ok else neg).append(t.score)
        return sustained, pruned, correct_q, pos, neg, toks

    t0 = time.perf_counter()
    per_dtype = {}
    tokens_by_dtype = {}
    for dt in dtypes:
        nb = max(6, budget // kv_quant.pool_block_bytes(cfg, dt))
        sustained, pruned, correct_q, pos, neg, toks = serve_leg(dt, nb)
        # pooled Fig. 5 metric over the engine's own step scores; 0.5
        # (chance) if a class is empty — the blessed reference run must
        # have both (check when re-blessing)
        ra = (rank_accuracy(np.asarray(pos), np.asarray(neg))
              if pos and neg else 0.5)
        per_dtype[dt] = {
            "num_blocks": int(nb),
            "bytes_per_block": kv_quant.pool_block_bytes(cfg, dt),
            "sustained": int(sustained),
            "pruned": int(pruned),
            "accuracy": correct_q / len(problems),
            "rank_acc": float(ra),
            "pos_traces": len(pos),
            "neg_traces": len(neg),
        }
        tokens_by_dtype[dt] = toks
        if verbose:
            d = per_dtype[dt]
            print(f"  [{dt}] blocks={nb} sustained={sustained} "
                  f"pruned={pruned} acc={d['accuracy']:.2f} "
                  f"rank_acc={ra:.3f} (pos={len(pos)} neg={len(neg)})")

    # equal-blocks legs: every dtype at f32's block count, so only the
    # pool dtype differs. Two contracts live here. (1) bf16 tokens must
    # match f32 exactly — activations are bf16, so the f32 pool stores
    # identical values. (2) rank-accuracy drift is only a NUMERICS
    # statement on a comparable trace population: at the shared byte
    # budget each dtype sustains a different trace set (a capacity /
    # selection effect, the point of the sweep), so scorer drift is
    # measured here instead, where schedules coincide up to
    # quantization noise.
    f32_ra = per_dtype["f32"]["rank_acc"]
    nb_f32 = per_dtype["f32"]["num_blocks"]
    equal_blocks = {}
    for dt in dtypes:
        if dt == "f32":
            continue
        sustained, _, _, pos, neg, toks = serve_leg(dt, nb_f32)
        ra = (rank_accuracy(np.asarray(pos), np.asarray(neg))
              if pos and neg else 0.5)
        equal_blocks[dt] = {
            "sustained": int(sustained),
            "rank_acc": float(ra),
            "pos_traces": len(pos),
            "neg_traces": len(neg),
            "tokens_identical_f32": toks == tokens_by_dtype["f32"],
        }
        if verbose:
            print(f"  [{dt}@f32-blocks] rank_acc={ra:.3f} "
                  f"identical={equal_blocks[dt]['tokens_identical_f32']}")

    payload = {
        "benchmark": "kv_quant",
        "config": {"problems": KVQ_PROBLEMS, "traces": N_TRACES,
                   "max_new": MAX_NEW, "budget_bytes": budget},
        "dtypes": per_dtype,
        "equal_blocks": equal_blocks,
        "tokens_identical_bf16_f32":
            equal_blocks["bf16"]["tokens_identical_f32"],
        "traces_per_byte_ratio_int8_over_f32":
            per_dtype["int8"]["sustained"]
            / max(per_dtype["f32"]["sustained"], 1),
        "rank_acc_drift": {
            dt: abs(equal_blocks[dt]["rank_acc"] - f32_ra)
            for dt in equal_blocks},
        "wall_s": time.perf_counter() - t0,
    }
    return payload


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--kv-quant", action="store_true",
                    help="run the fixed-HBM kv_dtype sweep instead of "
                         "the Table 4 fraction sweep")
    ap.add_argument("--out", default=None,
                    help="write the kv-quant payload to this JSON path "
                         "(default ../BENCH_kv_quant.json)")
    args = ap.parse_args()
    if args.kv_quant:
        payload = run_kv_quant(verbose=True)
        out = os.path.abspath(args.out or os.path.join(
            os.path.dirname(__file__), "..", "BENCH_kv_quant.json"))
        with open(out, "w") as f:
            json.dump(payload, f, indent=2)
        r = payload["traces_per_byte_ratio_int8_over_f32"]
        print(f"# int8 sustains x{r:.2f} the f32 traces at "
              f"{payload['config']['budget_bytes']} pool bytes "
              f"(gate: >= 1.8)")
        print(f"# wrote {out}")
        return payload

    rows = run()
    print("table4_memory: memory_fraction, num_blocks, accuracy, pruned, "
          "wait_s")
    for r in rows:
        print(f"{r['memory_fraction']},{r['num_blocks']},"
              f"{r['accuracy']:.3f},{r['pruned']},{r['wait_s']:.2f}")
    accs = [r["accuracy"] for r in rows]
    print(f"# accuracy spread: {max(accs) - min(accs):.3f} "
          f"(paper: stable within ~2 points); wait=0 at every budget")
    return rows


if __name__ == "__main__":
    main()
