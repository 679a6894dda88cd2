"""Table 1: accuracy / avg tokens / latency for CoT, SC, Slim-SC,
DeepConf, STEP across the synthetic reasoning benchmark (the paper's
main result, laptop scale)."""
from __future__ import annotations

from benchmarks.common import load_artifacts
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import EngineConfig, SamplingParams, evaluate_method, \
    make_problems

N_PROBLEMS = 8
N_TRACES = 16
# pool sized so the FULL trace set cannot fit — the paper's regime where
# SC queues (Fig. 2c) and STEP prunes
NUM_BLOCKS = 56
MAX_NEW = 120

METHODS = ("cot", "sc", "slimsc", "deepconf", "step")


def run(verbose: bool = False):
    params, scorer, cfg = load_artifacts()
    problems = make_problems(N_PROBLEMS, seed=11, n_steps=(6, 9))
    # per-trace prefill: keep the paper-regime wait/preemption columns
    # comparable with table3_breakdown (docs/ENGINE.md)
    ecfg = EngineConfig(max_batch=N_TRACES, num_blocks=NUM_BLOCKS,
                        capacity=256, max_new_tokens=MAX_NEW,
                        sampling=SamplingParams(max_new_tokens=MAX_NEW),
                        share_prompt_prefix=False)
    rows = []
    for method in METHODS:
        pkw = {"warmup": 4} if method == "deepconf" else {}
        res = evaluate_method(method, params, cfg, problems, N_TRACES,
                              ecfg, scorer_params=scorer,
                              policy_kwargs=pkw, verbose=verbose)
        rows.append({
            "method": method, "accuracy": res.accuracy,
            "avg_tokens": res.avg_tokens,
            "avg_latency_s": res.avg_latency_s,
            "wait_s": res.total_wait_s,
            "pruned": res.num_pruned, "preemptions": res.num_preemptions,
        })
    return rows


def main():
    enable_compile_cache()
    rows = run()
    print("table1_main: method, accuracy, avg_tokens, avg_latency_s, "
          "wait_s, pruned, preemptions")
    for r in rows:
        print(f"{r['method']},{r['accuracy']:.3f},{r['avg_tokens']:.0f},"
              f"{r['avg_latency_s']:.2f},{r['wait_s']:.2f},"
              f"{r['pruned']},{r['preemptions']}")
    sc = next(r for r in rows if r["method"] == "sc")
    st = next(r for r in rows if r["method"] == "step")
    speedup = sc["avg_latency_s"] / max(st["avg_latency_s"], 1e-9)
    print(f"# STEP vs SC: {speedup:.2f}x latency speedup "
          f"(paper claims 1.8x-3.3x), accuracy "
          f"{st['accuracy'] - sc['accuracy']:+.3f}")
    return rows


if __name__ == "__main__":
    main()
