"""Paged-attention kernel benchmark: Pallas vs dense, decode + chunked
prefill.

Times the two engine-facing paged-attention ops — the fused-decode
single-query op and the chunked-prefill multi-query op — through both
the dense jnp fallback and the Pallas kernel (interpret mode on this
CPU container; compiled Mosaic on TPU), at a serving-shaped config:
a large paged pool (the per-device HBM budget) holding a short live
prefix, i.e. the steady-state regime where most of the block table is
ahead of the write frontier.

What the kernel structurally eliminates, visible even in interpret mode:

  * chunked prefill: the dense path materializes a
    ``[B, KVH, G, C, bp*bs + C]`` score tensor per layer — every pool
    slot is scored and masked, live or not. The kernel's online-softmax
    grid touches only pages that hold visible tokens and never
    materializes the score tensor. This is the gated win
    (``prefill.speedup_x``, ``min_abs`` floor in check_regression).
  * decode: the dense path gathers the ENTIRE block table
    (``pool_k[block_tables]`` -> [B, bp*bs, KVH, hd]) per layer per
    token. The kernel reads only live pages. On CPU the per-grid-step
    interpret overhead (one Python-traced body per page) masks the
    saved bytes, so decode numbers are collapse-guarded only; on TPU
    the grid loop is hardware-sequenced and the saved HBM traffic is
    the win.

Numerics are asserted (kernel vs dense allclose) before timing, so the
speedup is never measured against a diverged implementation. Writes
``BENCH_paged_kernel.json``, regression-checked by the CI bench-smoke
job against ``benchmarks/reference/``.

    PYTHONPATH=src python -m benchmarks.paged_kernel [--out path.json]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels import ref
from repro.launch.compile_cache import enable_compile_cache
from repro.models.layers import paged_attention_decode

# Serving-shaped op config: big pool, short live prefix. Page size 64
# (vs the engine-test default 16) is the TPU-tuned tile — it also keeps
# the interpret-mode grid short enough that CPU timings reflect the
# structural work saved, not per-step Python overhead.
BATCH = 2
HEADS = 8
KV_HEADS = 2
HEAD_DIM = 64
PAGE = 64
CAPACITY = 4096
CHUNK = 64          # prefill chunk width (tokens)
PREFIX = 128        # live pooled tokens ahead of the chunk
DECODE_LEN = 192    # live cache length at the decode step
REPEATS = 10
SEED = 0


def _timeit(fn, *args, n=REPEATS):
    jax.block_until_ready(fn(*args))  # warm the jit cache
    best = float("inf")
    for _ in range(3):  # best-of-3 batches of n (CI runners are noisy)
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / n)
    return best


def _pool(key, nb):
    return jax.random.normal(key, (nb, PAGE, KV_HEADS, HEAD_DIM),
                             jnp.bfloat16)


def run(verbose: bool = False) -> dict:
    scale = 1.0 / math.sqrt(HEAD_DIM)
    bp = CAPACITY // PAGE
    nb = BATCH * bp + 1
    ks = jax.random.split(jax.random.PRNGKey(SEED), 6)
    k_pool, v_pool = _pool(ks[0], nb), _pool(ks[1], nb)
    bt = jnp.arange(1, BATCH * bp + 1, dtype=jnp.int32).reshape(BATCH, bp)

    # ---- chunked prefill ------------------------------------------------
    q = jax.random.normal(ks[2], (BATCH, CHUNK, HEADS, HEAD_DIM),
                          jnp.bfloat16)
    own_k = jax.random.normal(ks[3], (BATCH, CHUNK, KV_HEADS, HEAD_DIM),
                              jnp.bfloat16)
    own_v = jax.random.normal(ks[4], (BATCH, CHUNK, KV_HEADS, HEAD_DIM),
                              jnp.bfloat16)
    prefix_lens = jnp.full((BATCH,), PREFIX, jnp.int32)
    num_valid = jnp.full((BATCH,), CHUNK, jnp.int32)
    pf_args = (q, k_pool, v_pool, bt, prefix_lens, num_valid, own_k, own_v)

    pf_dense = jax.jit(
        lambda *a: ref.paged_attention_prefill_ref(*a, scale=scale))
    pf_kernel = jax.jit(
        lambda *a: kops.paged_attention_prefill(*a, scale=scale))

    diff_pf = float(jnp.max(jnp.abs(
        pf_kernel(*pf_args).astype(jnp.float32)
        - pf_dense(*pf_args).astype(jnp.float32))))
    t_pf_dense = _timeit(pf_dense, *pf_args)
    t_pf_kernel = _timeit(pf_kernel, *pf_args)

    # ---- decode ---------------------------------------------------------
    qd = jax.random.normal(ks[5], (BATCH * 4, HEADS, HEAD_DIM),
                           jnp.bfloat16)
    btd = jnp.tile(bt, (4, 1))[:BATCH * 4]
    lens = jnp.full((BATCH * 4,), DECODE_LEN, jnp.int32)
    de_args = (qd, k_pool, v_pool, btd, lens)

    de_dense = jax.jit(lambda q_, kp, vp, t, ln: paged_attention_decode(
        kp, vp, q_, t, ln, scale=scale))
    de_kernel = jax.jit(
        lambda *a: kops.paged_attention(*a, scale=scale))

    diff_de = float(jnp.max(jnp.abs(
        de_kernel(*de_args).astype(jnp.float32)
        - de_dense(*de_args).astype(jnp.float32))))
    t_de_dense = _timeit(de_dense, *de_args)
    t_de_kernel = _timeit(de_kernel, *de_args)

    outputs_close = bool(diff_pf < 2e-2 and diff_de < 2e-2)
    payload = {
        "benchmark": "paged_kernel",
        "config": {
            "batch": BATCH, "heads": HEADS, "kv_heads": KV_HEADS,
            "head_dim": HEAD_DIM, "page": PAGE, "capacity": CAPACITY,
            "chunk": CHUNK, "prefix": PREFIX, "decode_len": DECODE_LEN,
            "interpret": jax.default_backend() == "cpu",
        },
        "prefill": {
            "dense_ms": t_pf_dense * 1e3,
            "kernel_ms": t_pf_kernel * 1e3,
            "speedup_x": t_pf_dense / t_pf_kernel,
        },
        "decode": {
            "dense_ms": t_de_dense * 1e3,
            "kernel_ms": t_de_kernel * 1e3,
            "speedup_x": t_de_dense / t_de_kernel,
        },
        "max_abs_diff": {"prefill": diff_pf, "decode": diff_de},
        "outputs_close": outputs_close,
    }
    if verbose:
        print(f"chunked prefill: dense {t_pf_dense * 1e3:.2f}ms  "
              f"kernel {t_pf_kernel * 1e3:.2f}ms  "
              f"x{payload['prefill']['speedup_x']:.2f} "
              f"(max diff {diff_pf:.2e})")
        print(f"decode:          dense {t_de_dense * 1e3:.2f}ms  "
              f"kernel {t_de_kernel * 1e3:.2f}ms  "
              f"x{payload['decode']['speedup_x']:.2f} "
              f"(max diff {diff_de:.2e})")
    assert outputs_close, "kernel diverged from dense — timing meaningless"
    return payload


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_paged_kernel.json"))
    args = ap.parse_args()
    payload = run(verbose=True)
    out = os.path.abspath(args.out)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out}")
    return payload


if __name__ == "__main__":
    main()
