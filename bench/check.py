"""Whether what the timed path served is correct.

Once the window has closed, a sample of the served traces, drawn from the
seed and holding the longest and the most scored, is run through the plain reference
of the configuration's family (``bench/families/<family>/reference.py``)
as one sequence each: its prompt followed by the tokens the engine
served. Two numbers are compared, each against its limit in
``bench/limits/<cell>.json``:

- ``logprob_err``: over every served token of the sample (the first comes
  from the prefill's last position, the rest from decode), the widest gap
  between the log-probability the engine reported for the token (its
  confidence, under the untempered distribution) and the reference's. A
  token altered after it was sampled keeps the confidence of the token
  that was sampled, and reads far off;
- ``score_err``: over every step boundary in the sample, the widest gap
  between the step score the engine's scorer gave and the reference
  scorer's score of the reference hidden state (present where the cell's
  policy scores steps).

``control`` runs the same comparison with the reference computed below
the stated precision in the engine's place (``reference.forward(low=
True)``), on the same served tokens.
"""
from __future__ import annotations

import math
from typing import List

import jax.numpy as jnp
import numpy as np

NUMBERS = ("logprob_err", "score_err")


def pick(served: List, rng: np.random.Generator, n: int) -> List:
    """``served``: every trace with a served token. Returns the longest,
    the one with the most scored step boundaries (so that the scores are
    compared wherever the window scored any), and a seeded draw of the
    others, ``n`` in all."""
    pool = sorted(served, key=lambda t: (-t.num_tokens, t.request_id,
                                         t.trace_id))
    if not pool or n <= 0:
        return []
    chosen = [pool[0]]
    scored = max(pool, key=lambda t: len(t.step_scores))
    if n > 1 and scored is not pool[0] and scored.step_scores:
        chosen.append(scored)
    rest = [t for t in pool if all(t is not c for c in chosen)]
    idx = rng.choice(len(rest), min(n - len(chosen), len(rest)),
                     replace=False)
    chosen += [rest[i] for i in sorted(idx)]
    return chosen


def _pad(xs: List[int], block: int) -> np.ndarray:
    """Right-pad to a power of two (at least two of the reference's
    blocks), so that the reference compiles a few programs, not one per
    length."""
    n = 2 * block
    while n < len(xs):
        n *= 2
    out = np.zeros((n,), np.int32)
    out[: len(xs)] = xs
    return out


def _run(reference, params, scorer, inputs, targets, mt, low) -> dict:
    out = reference.forward(params, scorer, jnp.asarray(inputs),
                            jnp.asarray(targets[None]), m=mt, low=low)
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


def compare(reference, params, scorer, m: dict, sample: List,
            step_id: int, scores_expected: bool,
            control: bool = False) -> dict:
    """The numbers for ``sample`` (from ``pick``), against ``reference``,
    the family's reference module. With ``control`` the reference below
    the stated precision stands in for the engine."""
    mt = tuple(sorted(m.items()))
    worst = {k: 0.0 for k in NUMBERS}
    counts = {k: 0 for k in NUMBERS}
    for trace in sample:
        out = list(trace.output_tokens)
        seq = list(trace.prompt_tokens) + out
        P, n = len(trace.prompt_tokens), len(out)
        inputs = _pad(seq[:-1], reference.BLOCK)
        targets = _pad(seq[1:], reference.BLOCK)
        at = slice(P - 1, P - 1 + n)
        scored = [P + j for j in range(n - 1) if out[j] == step_id]
        ref = _run(reference, params, scorer, inputs, targets, mt, False)
        ref_logp = ref["tgt"][0][at] - ref["lse"][at]
        if control:
            low = _run(reference, params, scorer, inputs, targets, mt, True)
            logp = low["tgt"][0][at] - low["lse"][at]
            scores = low["score"][scored]
        else:
            logp = np.log(np.maximum(
                np.asarray(trace.token_confidences, np.float64), 1e-300))
            scores = np.asarray(trace.step_scores, np.float64)
        err = np.abs(logp - ref_logp)
        worst["logprob_err"] = max(worst["logprob_err"], float(err.max()))
        counts["logprob_err"] += n
        if len(scores) != len(scored):
            # a boundary scored twice or missed: no agreement to measure
            worst["score_err"] = math.inf
            counts["score_err"] += len(scored)
        elif scored:
            e = np.abs(scores - ref["score"][scored]).max()
            worst["score_err"] = max(worst["score_err"], float(e))
            counts["score_err"] += len(scored)
    result = {k: (worst[k] if counts[k] else None) for k in NUMBERS}
    if not scores_expected:
        result["score_err"] = None
    result["counts"] = counts
    return result


def judge(numbers: dict, limits: dict) -> bool:
    """Correct when every number the cell compares was measured and lies
    within its limit."""
    ok = True
    for k, lim in limits.items():
        v = numbers.get(k)
        ok &= v is not None and v <= lim
    return ok
