"""FLOP and byte counts of bench/families/qwen3/cost.py against hand
counts for one Qwen3-4B decode call."""
import json
from pathlib import Path

from bench.families.qwen3 import cost as flops

M = json.loads((Path(__file__).resolve().parents[1] / "configs"
                / "qwen3-4b-thinking.json").read_text())["model"]


def test_qwen3_4b_matmul_params():
    # per layer: q 2560*4096 + k,v 2*2560*1024 + o 4096*2560
    #            + gate,up,down 3*2560*9728 = 100,925,440
    # 36 layers + tied head 2560*151,936 = 4,022,272,000
    assert flops.matmul_params(M) == 36 * 100_925_440 + 388_956_160
    assert flops.matmul_params(M) == 4_022_272_000


def test_qwen3_4b_decode_call_flops():
    # two lanes at 100 and 1000 keys: 2 * 2 * 4,022,272,000 for the
    # matmuls, 4 * 36 layers * 32 heads * 128 = 589,824 per key
    assert flops.decode_flops(M, [100, 1000]) == (
        2 * 8_044_544_000 + 589_824 * 1100)


def test_qwen3_4b_decode_call_bytes():
    # a page of K and V in one layer: 2 * 16 * 8 * 128 * 2 B = 65,536;
    # 100 keys -> 7 pages, 1000 keys -> 63 pages; one new K/V row per
    # lane and layer: 2 * 8 * 128 * 2 B = 4,096
    w = 8_044_544_000
    got = flops.decode_call_bytes(M, w, [100, 1000], page=16)
    assert got == w + 36 * (70 * 65_536 + 2 * 4_096)


def test_qwen3_4b_two_step_decode_call_bytes():
    # a call of two iterations reads the weights twice; the lanes' four
    # tokens each read their own live pages: 101 keys -> 7 pages, 1001
    # keys -> 63 pages
    w = 8_044_544_000
    got = flops.decode_call_bytes(M, w, [100, 101, 1000, 1001], page=16,
                                  steps=2)
    assert got == 2 * w + 36 * (140 * 65_536 + 4 * 4_096)


def test_paged_kernels():
    f, b = flops.paged_decode_kernel(M, [100, 1000], page=16)
    assert f == 4 * 32 * 128 * 1100
    assert b == 70 * 65_536 + 2 * 2 * 32 * 128 * 2


def test_prefill_flops_matches_tokens():
    # a chunk is the sum of its tokens, each attending over its prefix
    want = sum(flops.token_flops(M, 300 + i + 1) for i in range(200))
    assert flops.prefill_flops(M, 300, 200) == want
