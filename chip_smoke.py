#!/usr/bin/env python3
"""Drive the STEP serving engine's main path once on a TPU.

    python chip_smoke.py                 # one chip: kernels, phases a-c
    python chip_smoke.py --four-chips    # (data=2, model=2) mesh vs one chip

The model is ``qwen3-4b-thinking`` at its published widths (d_model 2560,
32/8 GQA heads, head_dim 128, d_ff 9728, vocab 151,936, 36 layers) with
random bf16 weights drawn from ``--seed``.
Nothing is downloaded. Everything runs in this one process, through the
engine's public API (``Engine``, ``EngineConfig``, ``Request``,
``serve_batch``) with the kernel path, fault plan, pool dtype, prefix
cache and scheduler set explicitly, so no ``REPRO_*`` variable can change
the path.

One chip:
  kernels  the compiled Pallas kernels (paged decode bf16/int8, chunked
           prefill, fused step scorer) against the jnp references in
           ``repro.kernels.ref`` on a small input at the model's widths;
  a        requests of N=16 traces on a pool that holds them all;
  b        STEP requests whose traces need more blocks than the pool
           holds, so memory-triggered pruning runs;
  c        one request served twice, so the prefix cache hits.
Four chips: the same greedy requests through a one-chip engine and a
mesh engine; their tokens must be identical.

Prompts are synthetic-tokenizer ids; outputs stay ids (the model samples
over the full vocabulary, so EOS is rare and traces run to their limit).
The last line of stdout is ``{"ok": true, "device": {...}}``. Without a
TPU, or when any phase fails, the script exits non-zero before printing
it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_TRACES = 16
PROMPT_LEN = 48        # fixed: every prompt is one 64-token prefill chunk
PREFILL_CHUNK = 64
MAX_BATCH = 32         # two requests of N=16 decode together
MAX_NEW = 128          # phase a/c generation length
PRESSURE_NEW = 320     # phase b: 32 traces x 23 blocks > the pool
CAPACITY = 384         # >= PROMPT_LEN + PRESSURE_NEW
NUM_BLOCKS = 448       # holds phase a (32 x 11 blocks), not phase b
KERNEL_ATOL = 3e-2     # bf16 output rounding; a wrong page or head is O(1)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-vs-one-chip identity phase")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def make_prompts(n, seed):
    """``n`` prompts of PROMPT_LEN synthetic-tokenizer ids: arithmetic
    problems run together, as a few-shot prompt would be."""
    from repro.data.arithmetic import make_prompt
    from repro.data.tokenizer import get_tokenizer
    from repro.serving.evaluate import make_problems
    tok = get_tokenizer()
    problems = make_problems(4 * n, seed=seed, n_steps=(6, 8))
    return [tok.encode(" ".join(make_prompt(p)
                                for p in problems[4 * i:4 * i + 4]),
                       add_bos=True)[:PROMPT_LEN] for i in range(n)]


def count_cache_events() -> dict:
    """Persistent compile-cache hits and misses of this process, through
    JAX's monitoring events; the compiles a serve waits for are the
    engine's own ``counters``."""
    import jax.monitoring as mon
    seen = {"cache_hits": 0, "cache_misses": 0}

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            seen["cache_misses"] += 1

    mon.register_event_listener(on_event)
    return seen


def check_close(name, got, want):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        raise AssertionError(f"{name}: shape {got.shape} vs {want.shape} "
                             f"or non-finite output")
    err = float(np.max(np.abs(got - want)))
    print(f"kernel={name} shape={list(got.shape)} max_abs_err={err!r}")
    if err > KERNEL_ATOL:
        raise AssertionError(f"{name}: max abs error {err} > {KERNEL_ATOL}")


def check_kernels(cfg, seed):
    """Compiled kernels vs the jnp references at the model's head widths."""
    import jax
    import jax.numpy as jnp

    from repro.core.scorer import init_scorer, scorer_score
    from repro.kernels import ops as kops
    from repro.kernels import ref
    from repro.models import kv_quant

    H, KVH, hd, page = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                        cfg.kv_block_size)
    B, NB, bp, C = 4, 40, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kf = jax.random.normal(ks[1], (NB, page, KVH, hd), jnp.float32)
    vf = jax.random.normal(ks[2], (NB, page, KVH, hd), jnp.float32)
    bt = jax.random.permutation(ks[3], NB)[:B * bp].reshape(B, bp)
    bt = bt.astype(jnp.int32)
    lens = jnp.array([0, 1, 37, bp * page], jnp.int32)
    scale = 1.0 / math.sqrt(hd)
    bf = jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        qb, kb, vb = q.astype(bf), kf.astype(bf), vf.astype(bf)
        check_close("decode_bf16",
                    kops.paged_attention(qb, kb, vb, bt, lens, scale=scale),
                    ref.paged_attention_ref(qb.astype(jnp.float32),
                                            kb.astype(jnp.float32),
                                            vb.astype(jnp.float32), bt, lens,
                                            scale))
        kq, k_s = kv_quant.quantize_pages(kf, jnp.int8)
        vq, v_s = kv_quant.quantize_pages(vf, jnp.int8)
        check_close("decode_int8",
                    kops.paged_attention(qb, kq, vq, bt, lens, scale=scale,
                                         k_scale=k_s, v_scale=v_s),
                    ref.paged_attention_ref(
                        qb.astype(jnp.float32),
                        kv_quant.dequantize_pages(kq, k_s),
                        kv_quant.dequantize_pages(vq, v_s), bt, lens, scale))
        qc = jax.random.normal(ks[4], (B, C, H, hd), jnp.float32).astype(bf)
        own_k = jax.random.normal(ks[5], (B, C, KVH, hd)).astype(bf)
        own_v = jax.random.normal(ks[6], (B, C, KVH, hd)).astype(bf)
        prefix = jnp.array([0, 5, 64, bp * page - C], jnp.int32)
        nvalid = jnp.array([C, 3, C, 9], jnp.int32)
        f32 = [x.astype(jnp.float32) for x in (qc, kb, vb, own_k, own_v)]
        check_close("prefill_bf16",
                    kops.paged_attention_prefill(qc, kb, vb, bt, prefix,
                                                 nvalid, own_k, own_v,
                                                 scale=scale),
                    ref.paged_attention_prefill_ref(
                        f32[0], f32[1], f32[2], bt, prefix, nvalid, f32[3],
                        f32[4], scale))
        sp = init_scorer(ks[7], cfg.d_model)
        h = jax.random.normal(ks[0], (MAX_BATCH, cfg.d_model)).astype(bf)
        want = ref.step_score_ref(h, sp["w1"], sp["b1"], sp["w2"], sp["b2"])
        check_close("step_score", kops.step_score_params(h, sp), want)
    # the mesh engine's dense scorer, at the chip's default precision
    check_close("step_score_dense", scorer_score(sp, h), want)


def build_engine(cfg, params, scorer, sampling=None, mesh=None,
                 num_blocks=None):
    from repro.core.pruning import make_policy
    from repro.serving import (Engine, EngineConfig, FIFOPolicy,
                               SamplingParams)
    num_blocks = num_blocks or NUM_BLOCKS
    ecfg = EngineConfig(
        max_batch=MAX_BATCH, num_blocks=num_blocks, capacity=CAPACITY,
        max_new_tokens=MAX_NEW,
        sampling=sampling or SamplingParams(max_new_tokens=MAX_NEW),
        use_kernel=True, prefix_cache=True, faults=None, kv_dtype="bf16",
        prefill_chunk_size=PREFILL_CHUNK, seed=0)
    eng = Engine(params, cfg, ecfg, make_policy("step"),
                 scorer_params=scorer, mesh=mesh, scheduler=FIFOPolicy())
    print(f"engine use_kernel={eng.use_kernel} "
          f"fused_scorer={eng.fused_scorer} mesh={mesh is not None} "
          f"num_blocks={num_blocks} block_bytes={eng.kv_block_bytes}")
    if not eng.use_kernel or (mesh is None and not eng.fused_scorer):
        raise AssertionError("the engine did not take the kernel path")
    return eng


def check_results(cfg, results, want_new):
    """Every request completed; every trace holds valid token ids with
    finite confidences and scores; no trace overran its limit."""
    for res in results:
        if res.status != "completed":
            raise AssertionError(f"request {res.request_id}: {res.status}")
        for t in res.traces:
            n = len(t.output_tokens)
            if not 0 < n <= want_new:
                raise AssertionError(f"trace {t.trace_id}: {n} tokens")
            if not all(0 <= x < cfg.vocab_size for x in t.output_tokens):
                raise AssertionError(f"trace {t.trace_id}: id out of vocab")
            vals = list(t.token_confidences) + list(t.step_scores)
            if not all(math.isfinite(v) and 0.0 <= v <= 1.0 for v in vals):
                raise AssertionError(f"trace {t.trace_id}: bad confidence "
                                     f"or score")


def peak_bytes():
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def run_phase(name, eng, cfg, requests, want_new):
    compiles0, compile_s0 = eng.counters.compiles, eng.counters.compile_s
    t0 = time.perf_counter()
    results = eng.serve_batch(requests)
    wall = time.perf_counter() - t0
    check_results(cfg, results, want_new)
    eng.check_integrity()
    if not eng.pool_drained():
        raise AssertionError(f"phase {name}: pool did not drain")
    if eng.fault_stats.degraded_to_dense:
        raise AssertionError(f"phase {name}: degraded to the dense path")
    tokens = sum(r.total_tokens for r in results)
    prompt = sum(len(r.traces[0].prompt_tokens) for r in results)
    cached = sum(r.metrics.cached_tokens for r in results)
    cached_blocks = (eng.prefix_cache.cached_blocks
                     if eng.prefix_cache is not None else 0)
    print(f"phase={name} requests={len(results)} "
          f"traces={sum(len(r.traces) for r in results)} "
          f"tokens_generated={tokens} "
          f"num_pruned={sum(r.num_pruned for r in results)} "
          f"num_preemptions={sum(r.num_preemptions for r in results)} "
          f"prefix_hit_rate={cached / prompt!r} wall_s={wall!r} "
          f"tokens_per_s={tokens / wall!r} "
          f"compilations={eng.counters.compiles - compiles0} "
          f"compile_s={eng.counters.compile_s - compile_s0!r} "
          f"degraded_to_dense={eng.fault_stats.degraded_to_dense} "
          f"peak_blocks={max(r.peak_blocks_used for r in results)} "
          f"free_blocks={eng.block_mgr.free_blocks} "
          f"cached_blocks={cached_blocks} "
          f"num_blocks={eng.block_mgr.num_blocks} drained=True "
          f"peak_bytes_in_use={peak_bytes()}")
    return results


def one_chip(cfg, params, scorer, seed):
    from repro.core.pruning import make_policy
    from repro.serving import Request

    check_kernels(cfg, seed)
    eng = build_engine(cfg, params, scorer)
    prompts = make_prompts(5, seed)

    def reqs(ids, start, policy, **kw):
        return [Request(request_id=start + i, prompt_tokens=p,
                        n_traces=N_TRACES, policy=make_policy(policy), **kw)
                for i, p in enumerate(ids)]

    run_phase("a", eng, cfg, reqs(prompts[0:2], 0, "sc"), MAX_NEW)
    res = run_phase("b", eng, cfg,
                    reqs(prompts[2:4], 10, "step",
                         max_new_tokens=PRESSURE_NEW), PRESSURE_NEW)
    if sum(r.num_pruned for r in res) == 0:
        raise AssertionError("phase b: STEP never pruned")
    if sum(r.num_preemptions for r in res):
        raise AssertionError("phase b: STEP preempted a trace")
    run_phase("c_cold", eng, cfg, reqs(prompts[4:5], 20, "step"), MAX_NEW)
    res = run_phase("c_warm", eng, cfg, reqs(prompts[4:5], 21, "step"),
                    MAX_NEW)
    if res[0].metrics.cached_tokens == 0:
        raise AssertionError("phase c: the repeated prompt missed the "
                             "prefix cache")


def four_chips(cfg, params, scorer, seed):
    """Greedy requests through a one-chip engine and a (data=2, model=2)
    mesh engine; reports the first diverging token, if any."""
    from repro.core.pruning import make_policy
    from repro.launch.mesh import make_host_mesh
    from repro.serving import Request, SamplingParams

    greedy = SamplingParams(temperature=0.0, top_k=0, top_p=1.0,
                            max_new_tokens=MAX_NEW)
    prompts = make_prompts(2, seed)
    outputs = {}
    for name, mesh in (("single", None), ("mesh", make_host_mesh(2, 2))):
        eng = build_engine(cfg, params, scorer, sampling=greedy, mesh=mesh,
                           num_blocks=128)
        reqs = [Request(request_id=i, prompt_tokens=p, n_traces=4,
                        policy=make_policy("step"))
                for i, p in enumerate(prompts)]
        res = run_phase(name, eng, cfg, reqs, MAX_NEW)
        outputs[name] = [(r.request_id, t.trace_id, t.output_tokens,
                          t.token_confidences, t.step_scores)
                         for r in res for t in r.traces]
        del eng
    diverged = None
    conf_err = score_err = 0.0
    n_scores = 0
    for (rid, tid, a, ca, sa), (_, _, b, cb, sb) in zip(outputs["single"],
                                                        outputs["mesh"]):
        if a != b and diverged is None:
            i = next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                     min(len(a), len(b)))
            diverged = (rid, tid, i, a[i:i + 1], b[i:i + 1])
        conf_err = max([conf_err] + [abs(x - y) for x, y in zip(ca, cb)])
        score_err = max([score_err] + [abs(x - y) for x, y in zip(sa, sb)])
        n_scores += min(len(sa), len(sb))
    n_tok = sum(len(o[2]) for o in outputs["single"])
    print(f"four_chips tokens_compared={n_tok} "
          f"identical={diverged is None} "
          f"confidence_max_abs_diff={conf_err!r} "
          f"step_scores_compared={n_scores} "
          f"step_score_max_abs_diff={score_err!r}")
    if diverged is not None:
        rid, tid, i, a, b = diverged
        raise AssertionError(f"request {rid} trace {tid} diverges at token "
                             f"{i}: single {a} vs mesh {b}")


def main(argv=None) -> int:
    args = parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (found {devices[0].platform}); "
              f"this script measures nothing without one", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    print(f"compile_cache_dir={enable_compile_cache()}")
    cache_events = count_cache_events()
    print(f"platform={devices[0].platform} "
          f"device_kind={devices[0].device_kind} count={len(devices)}")

    from repro.configs.qwen3_4b_thinking import full_config
    from repro.core.scorer import init_scorer
    from repro.models.init import count_params, init_params
    cfg = full_config()
    t0 = time.perf_counter()
    params = jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(args.seed))
    scorer = init_scorer(jax.random.PRNGKey(args.seed + 1), cfg.d_model)
    jax.block_until_ready(params)
    print(f"model={cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size} params={count_params(params)} "
          f"init_s={time.perf_counter() - t0!r}")

    if args.four_chips:
        four_chips(cfg, params, scorer, args.seed)
    else:
        one_chip(cfg, params, scorer, args.seed)
    print(" ".join(f"{k}={v}" for k, v in cache_events.items()))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
