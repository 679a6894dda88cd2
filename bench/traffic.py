"""One general generator for every traffic mix under ``bench/traffic/``.

A mix is a JSON file of parameters; nothing here names a mix. Every seed
gets the same multiset of sizes, drawn at fixed quantiles, in an order the
seed shuffles, so seeds change which request is long, not how much work a
run holds.

Keys of a mix file (all lengths in tokens):

- ``requests``: how many; ``n_traces``: traces per request.
- ``arrivals``: ``{"kind": "backlog"}``: all due at t=0, an offline batch.
- ``prompt_len``: ``{"min", "max", "median", "sigma", "step"}``, a
  lognormal clipped to [min, max] (most prompts short, a heavy tail), in
  steps of ``step`` tokens from ``min`` (optional).
- ``max_new_tokens``: a number, or a lognormal as ``prompt_len``.
- ``sampling``: temperature, top_k, top_p.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

# token ids below this are the synthetic tokenizer's specials and digits;
# prompts draw from the rest of the vocabulary so that none is a step or
# end-of-sequence marker
FIRST_PLAIN_ID = 32


@dataclasses.dataclass
class Item:
    """One request of a mix, before the engine's own types."""
    index: int
    prompt: List[int]
    arrival_s: float
    max_new_tokens: int


def quantile_lengths(n: int, spec, rng: np.random.Generator) -> List[int]:
    """``n`` lengths at the quantiles (i + 0.5) / n of a lognormal clipped
    to [min, max], in the order ``rng`` shuffles them."""
    if isinstance(spec, (int, float)):
        return [int(spec)] * n
    lo, hi = spec["min"], spec["max"]
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    nd = NormalDist()
    step = spec.get("step", 1)
    vals = [min(max(math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)), lo), hi)
            for i in range(n)]
    vals = [lo + step * int(round((v - lo) / step)) for v in vals]
    return [min(v, hi) for v in (vals[i] for i in rng.permutation(n))]


def generate(mix: dict, seed: int, vocab: int) -> List[Item]:
    """The mix's requests for one run."""
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    kind = mix["arrivals"]["kind"]
    if kind != "backlog":
        raise ValueError(f"unknown arrival kind {kind!r}")
    n = mix["requests"]
    plens = quantile_lengths(n, mix["prompt_len"], rng)
    news = quantile_lengths(n, mix["max_new_tokens"], rng)
    return [Item(index=i,
                 prompt=rng.integers(FIRST_PLAIN_ID, vocab, plens[i]).tolist(),
                 arrival_s=0.0, max_new_tokens=news[i])
            for i in range(n)]
