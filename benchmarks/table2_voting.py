"""Table 2: answer-aggregation strategies over the SAME trace set —
majority voting vs STEP-scorer-weighted voting (the paper also compares
a 7B PRM; our stand-in for an external reward model is an oracle-free
confidence weighting)."""
from __future__ import annotations

import random

import numpy as np

from benchmarks.common import load_artifacts
from repro.core.pipeline import sample_traces
from repro.core.scorer import scorer_score
from repro.core.voting import majority_vote, weighted_vote
from repro.data.arithmetic import gen_problem
from repro.data.tokenizer import get_tokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import forward_full

import jax.numpy as jnp

N_PROBLEMS = 12
N_SAMPLES = 8


def run(verbose: bool = False):
    params, scorer, cfg = load_artifacts()
    tok = get_tokenizer()
    rng = random.Random(57)
    problems = [gen_problem(rng, (6, 9)) for _ in range(N_PROBLEMS)]
    traces = sample_traces(params, cfg, problems, N_SAMPLES, seed=57)

    by_problem: dict = {}
    for t in traces:
        by_problem.setdefault(id(t.problem), (t.problem, []))[1].append(t)

    n_major = n_weighted = n_conf = 0
    for _, (p, ts) in by_problem.items():
        answers, scores, confs = [], [], []
        for t in ts:
            ids = t.token_ids
            toks = jnp.asarray(np.array(ids, np.int32)[None])
            out = forward_full(params, cfg, toks)
            hidden = np.asarray(out["hidden"][0], np.float32)
            stop = ids.index(tok.think_close_id) \
                if tok.think_close_id in ids else len(ids)
            bpos = [i for i in range(t.prompt_len, stop)
                    if ids[i] == tok.step_id]
            s = float(np.mean(np.asarray(scorer_score(
                scorer, jnp.asarray(hidden[bpos]))))) if bpos else 0.5
            logits = np.asarray(out["logits"][0], np.float32)
            lp = logits - np.log(
                np.exp(logits).sum(-1, keepdims=True))
            conf = float(np.exp(np.mean(
                [lp[i, ids[i + 1]] for i in range(t.prompt_len - 1,
                                                  len(ids) - 1)])))
            answers.append(t.answer)
            scores.append(s)
            confs.append(conf)
        gold = str(p.answer)
        a_m = majority_vote(answers)
        a_w = weighted_vote(answers, scores)
        a_c = weighted_vote(answers, confs)
        n_major += (a_m == gold)
        n_weighted += (a_w == gold)
        n_conf += (a_c == gold)
    n = len(by_problem)
    return [{"voting": "majority", "accuracy": n_major / n},
            {"voting": "confidence_weighted", "accuracy": n_conf / n},
            {"voting": "step_weighted", "accuracy": n_weighted / n}]


def main():
    enable_compile_cache()
    rows = run()
    print("table2_voting: voting, accuracy")
    for r in rows:
        print(f"{r['voting']},{r['accuracy']:.3f}")
    return rows


if __name__ == "__main__":
    main()
