"""Fig. 5: pairwise ranking accuracy of the hidden-state step scorer vs
token-level confidence, as a function of the trace prefix fraction k%.

The paper's claim: the scorer separates correct from incorrect traces
EARLY (RankAcc well above 0.5 from 25% of steps) and beats mean token
confidence at every prefix."""
from __future__ import annotations

import numpy as np

from benchmarks.common import load_artifacts
from repro.core.pipeline import sample_traces
from repro.core.scorer import rank_accuracy, scorer_score
from repro.data.arithmetic import gen_problem
from repro.data.tokenizer import get_tokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import forward_full

import jax.numpy as jnp
import random

N_PROBLEMS = 12
N_SAMPLES = 8
PREFIXES = (0.25, 0.5, 0.75, 1.0)


def run(verbose: bool = False):
    params, scorer, cfg = load_artifacts()
    tok = get_tokenizer()
    rng = random.Random(41)
    problems = [gen_problem(rng, (6, 9)) for _ in range(N_PROBLEMS)]
    traces = sample_traces(params, cfg, problems, N_SAMPLES, seed=41)

    # per-trace: step-boundary hidden scores + token confidences by prefix
    per_q: dict = {}
    for t in traces:
        ids = t.token_ids
        stop = ids.index(tok.think_close_id) if tok.think_close_id in ids \
            else len(ids)
        toks = jnp.asarray(np.array(ids, np.int32)[None])
        out = forward_full(params, cfg, toks)
        hidden = np.asarray(out["hidden"][0], np.float32)
        logits = np.asarray(out["logits"][0], np.float32)
        bpos = [i for i in range(t.prompt_len, stop)
                if ids[i] == tok.step_id]
        if not bpos:
            continue
        sscores = np.asarray(scorer_score(scorer, jnp.asarray(hidden[bpos])))
        # token confidence: prob of the realised next token
        lp = logits[:-1] - np.log(np.exp(logits[:-1]).sum(-1, keepdims=True))
        conf = np.exp([lp[i, ids[i + 1]]
                       for i in range(t.prompt_len - 1, stop - 1)])
        key = id(t.problem)
        per_q.setdefault(key, {"pos": [], "neg": []})
        bucket = "pos" if t.correct else "neg"
        per_q[key][bucket].append((sscores, conf))

    rows = []
    for frac in PREFIXES:
        accs_s, accs_c = [], []
        for q in per_q.values():
            if not q["pos"] or not q["neg"]:
                continue

            def prefix_mean(arrs, f):
                return np.array([a[:max(1, int(len(a) * f))].mean()
                                 for a in arrs])

            sp = prefix_mean([p[0] for p in q["pos"]], frac)
            sn = prefix_mean([p[0] for p in q["neg"]], frac)
            cp = prefix_mean([p[1] for p in q["pos"]], frac)
            cn = prefix_mean([p[1] for p in q["neg"]], frac)
            accs_s.append(rank_accuracy(sp, sn))
            accs_c.append(rank_accuracy(cp, cn))
        rows.append({"prefix": frac,
                     "rankacc_scorer": float(np.nanmean(accs_s)),
                     "rankacc_confidence": float(np.nanmean(accs_c))})
    return rows


def main():
    enable_compile_cache()
    rows = run()
    print("fig5_rankacc: prefix_frac, rankacc_scorer, rankacc_confidence")
    for r in rows:
        print(f"{r['prefix']},{r['rankacc_scorer']:.3f},"
              f"{r['rankacc_confidence']:.3f}")
    return rows


if __name__ == "__main__":
    main()
