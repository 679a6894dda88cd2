"""Fig. 4: latency-scaling — accuracy vs latency at trace budgets
N in {1, 8, 16} for SC and STEP (paper uses {1, 16, 32, 64})."""
from __future__ import annotations

from benchmarks.common import load_artifacts
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import EngineConfig, SamplingParams, evaluate_method, \
    make_problems

N_PROBLEMS = 6
BUDGETS = (1, 8, 16)
MAX_NEW = 120


def run(verbose: bool = False):
    params, scorer, cfg = load_artifacts()
    problems = make_problems(N_PROBLEMS, seed=31, n_steps=(6, 9))
    rows = []
    for n in BUDGETS:
        # pool scales with budget but stays undersized (paper setting)
        blocks = max(12, int(n * 1.6) + 4)
        # per-trace prefill: undersized-pool pressure assumes private
        # prompt blocks per trace (docs/ENGINE.md)
        ecfg = EngineConfig(max_batch=max(n, 1), num_blocks=blocks,
                            capacity=256, max_new_tokens=MAX_NEW,
                            sampling=SamplingParams(max_new_tokens=MAX_NEW),
                            share_prompt_prefix=False)
        for method in ("sc", "step"):
            if n == 1 and method == "step":
                continue  # single trace: no pruning possible
            res = evaluate_method(method, params, cfg, problems, n, ecfg,
                                  scorer_params=scorer, verbose=verbose)
            rows.append({"n": n, "method": method,
                         "accuracy": res.accuracy,
                         "avg_latency_s": res.avg_latency_s})
    return rows


def main():
    enable_compile_cache()
    rows = run()
    print("fig4_scaling: n, method, accuracy, avg_latency_s")
    for r in rows:
        print(f"{r['n']},{r['method']},{r['accuracy']:.3f},"
              f"{r['avg_latency_s']:.2f}")
    return rows


if __name__ == "__main__":
    main()
