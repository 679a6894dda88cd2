"""The decode readers on a decode call of two iterations: the same work
split into calls of one iteration each reads the same roofline and HBM
share, since a call of K iterations runs the kernel K times per layer and
reads the weights K times."""
import types

import pytest

from bench.families.qwen3 import cost
from bench.metrics import decode_hbm_share, paged_decode_roofline
from bench.observer import Burst
from bench.tests import tiny

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LAYERS = tiny.TINY_MODEL["num_layers"]
WEIGHTS = 10_000_000


def ctx(bursts, kernel_s, module_s):
    """A traced window of ``bursts`` whose kernel events took
    ``kernel_s`` each (one per layer and iteration) and whose decode
    calls took ``module_s`` each."""
    runs = LAYERS * sum(b.steps for b in bursts)
    trace = {"kernels": {paged_decode_roofline.KEY:
                         {"s": kernel_s * runs, "count": runs}},
             "modules": {decode_hbm_share.PROGRAM:
                         {"s": module_s * len(bursts),
                          "count": len(bursts)}}}
    obs = types.SimpleNamespace(traced=lambda: (bursts, []))
    return types.SimpleNamespace(trace=trace, obs=obs, model=tiny.TINY_MODEL,
                                 peaks=PEAKS, page=16, weight_bytes=WEIGHTS,
                                 cost=cost)


def burst(lens, steps):
    return Burst(t=0.0, n_lanes=2, tokens=len(lens), steps=steps, gaps=[],
                 lens=lens, step_tokens=0, step_scores=0)


def test_two_iterations_read_as_two_calls():
    # lanes at 100 and 1000 keys, two tokens each
    one = [burst([100, 1000], 1), burst([101, 1001], 1)]
    two = [burst([100, 101, 1000, 1001], 2)]
    k = 1e-5
    assert paged_decode_roofline.read(ctx(two, k, 2e-3)) == pytest.approx(
        paged_decode_roofline.read(ctx(one, k, 1e-3)))
    assert decode_hbm_share.read(ctx(two, k, 2e-3)) == pytest.approx(
        decode_hbm_share.read(ctx(one, k, 1e-3)))


def test_nothing_traced_reads_nothing():
    c = ctx([], 1e-5, 1e-3)
    assert paged_decode_roofline.read(c) is None
    assert decode_hbm_share.read(c) is None
