"""Online-serving load benchmark: Poisson arrivals through the
continuous-batching scheduler.

Replays a seeded Poisson arrival trace against one engine (chunked
prefill + per-tick token budget on), records per-request TTFT / TPOT /
end-to-end latency, and writes the percentile summary to
``BENCH_serving.json`` — the artifact the CI benchmark-smoke job uploads
and regression-checks, starting the repo's perf trajectory.

``--multiturn`` runs the cross-request prefix-cache workload instead:
two multi-turn conversations over one shared system prompt, replayed
turn-by-turn with the cache off and on. Each turn's prompt extends the
previous one, so with the cache on every turn after the first forks the
parked blocks and prefills only the fresh suffix. Emits
``BENCH_prefix_cache.json`` (hit rate, prefill tokens saved, TTFT
on/off) and asserts the generated tokens are identical either way.

``--faults`` runs the fault-tolerance workload: the Poisson replay
served twice on identical requests — once fault-free, once under a
seeded transient fault plan (device-step failures retried with backoff,
a one-tick allocation stall) — asserting the faulted run recovers to
bit-identical outputs, then a chaos leg (NaN lane poisoning + an
impossible deadline) exercising quarantine and cancellation. Emits
``BENCH_faults.json`` (recovered steps, stalls, quarantines,
cancellations, and goodput under faults vs fault-free).

``--slo`` runs the multi-tenant SLO scenario suite: a 10x larger
workload (bursty arrival waves, heavy-tail prompt lengths, mixed
single-trace "chat" and 4-trace "reasoning" requests) served to a
premium tenant (weight 3, priority 1) and a batch tenant (weight 1,
priority 0, degradable SLO) through the weighted-fair TenantScheduler.
Emits ``BENCH_slo.json`` with the per-tenant TTFT/TPOT percentile and
SLO-attainment breakdown; the regression gate requires the premium
tenant's p99 TTFT to stay >= 2x better than the batch tenant's.

Uses randomly-initialised weights (perf numbers don't need a trained
model) so it runs in seconds on the CI CPU runners:

    PYTHONPATH=src python -m benchmarks.serving_load [--multiturn|--slo]
        [--out path.json]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.configs.registry import serving_config
from repro.core.pruning import make_policy
from repro.core.trace import TraceStatus
from repro.data.tokenizer import get_tokenizer
from repro.data.arithmetic import make_prompt
from repro.launch.compile_cache import enable_compile_cache
from repro.models.init import init_params
from repro.serving import (SLO, CacheStats, Engine, EngineConfig, FaultPlan,
                           Request, SamplingParams, TenantScheduler,
                           make_problems, poisson_arrivals, summarize,
                           summarize_by_tenant)

N_REQUESTS = 6
N_TRACES = 4
MAX_NEW = 24
NUM_BLOCKS = 96
CAPACITY = 128
ARRIVAL_RATE = 4.0      # requests / second (open-loop Poisson)
PREFILL_CHUNK = 16
MAX_TOKENS_PER_STEP = 64
SEED = 1234


def build_requests(tok):
    problems = make_problems(N_REQUESTS, seed=SEED, n_steps=(8, 12))
    arrivals = poisson_arrivals(N_REQUESTS, ARRIVAL_RATE, seed=SEED)
    return [
        Request(request_id=i,
                prompt_tokens=tok.encode(make_prompt(p), add_bos=True),
                n_traces=N_TRACES, policy=make_policy("sc"),
                arrival_time=at)
        for i, (p, at) in enumerate(zip(problems, arrivals))
    ]


def run(verbose: bool = False) -> dict:
    cfg = serving_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer()
    ecfg = EngineConfig(
        max_batch=N_REQUESTS * N_TRACES, num_blocks=NUM_BLOCKS,
        capacity=CAPACITY, max_new_tokens=MAX_NEW,
        sampling=SamplingParams(temperature=0.0, top_k=0, top_p=1.0,
                                max_new_tokens=MAX_NEW),
        prefill_chunk_size=PREFILL_CHUNK,
        max_tokens_per_step=MAX_TOKENS_PER_STEP,
        # cache off: the warmup replays request 0's prompt — a warm hit
        # would skip its prefill and shift the blessed latency numbers.
        # The cache gets its own workload (run_multiturn) below.
        prefix_cache=False)
    engine = Engine(params, cfg, ecfg, make_policy("sc"))

    # warm the jit caches (prefill, chunk prefill, decode) so the timed
    # replay measures scheduling, not compilation
    warm = build_requests(tok)[0]
    warm.arrival_time = 0.0
    engine.serve_batch([warm])

    requests = build_requests(tok)
    t0 = time.perf_counter()
    completions = []
    results = engine.serve_batch(
        requests, on_complete=lambda r: completions.append(r.request_id))
    wall = time.perf_counter() - t0

    assert len(completions) == len(requests), "streaming callback missed"
    for r in results:
        assert all(t.status == TraceStatus.FINISHED for t in r.traces)
        assert r.metrics is not None and r.metrics.ttft_s is not None
        assert r.metrics.first_token_s >= r.metrics.arrival_s
    assert engine.pool_drained()
    engine.block_mgr.check_invariants()

    summary = summarize([r.metrics for r in results])
    payload = {
        "benchmark": "serving_load",
        "config": {
            "n_requests": N_REQUESTS, "n_traces": N_TRACES,
            "max_new_tokens": MAX_NEW, "num_blocks": NUM_BLOCKS,
            "capacity": CAPACITY, "arrival_rate_per_s": ARRIVAL_RATE,
            "prefill_chunk_size": PREFILL_CHUNK,
            "max_tokens_per_step": MAX_TOKENS_PER_STEP, "seed": SEED,
        },
        "wall_s": wall,
        **summary,
    }
    if verbose:
        print(f"serving_load: {summary['num_completed']}/{N_REQUESTS} "
              f"requests, {summary['total_output_tokens']} tokens "
              f"in {wall:.2f}s "
              f"({summary['throughput_tok_per_s']:.1f} tok/s)")
        print(f"  ttft  p50={summary['ttft_s']['p50']:.3f}s "
              f"p99={summary['ttft_s']['p99']:.3f}s")
        print(f"  tpot  p50={summary['tpot_s']['p50'] * 1e3:.1f}ms "
              f"p99={summary['tpot_s']['p99'] * 1e3:.1f}ms")
        print(f"  e2e   p50={summary['e2e_s']['p50']:.3f}s "
              f"p99={summary['e2e_s']['p99']:.3f}s")
    return payload


# ---------------------------------------------------------------------------
# multi-turn / shared-template workload (cross-request prefix cache)
# ---------------------------------------------------------------------------

MT_TURNS = 10
MT_CONVS = 2
MT_MAX_NEW = 8
MT_NUM_BLOCKS = 128
MT_CAPACITY = 320
# the shared "system prompt": ~169 tokens of template every conversation
# starts from (10+ full KV blocks reusable across every turn)
SYS_TEXT = "".join(f"{i % 10}+{(i + 3) % 10}-{(i + 7) % 10}= "
                   for i in range(24))


def _mt_engine(params, cfg, prefix_cache: bool) -> Engine:
    ecfg = EngineConfig(
        max_batch=2 * MT_CONVS, num_blocks=MT_NUM_BLOCKS,
        capacity=MT_CAPACITY, max_new_tokens=MT_MAX_NEW,
        sampling=SamplingParams(temperature=0.0, top_k=0, top_p=1.0,
                                max_new_tokens=MT_MAX_NEW),
        prefill_chunk_size=PREFILL_CHUNK,
        prefix_cache=prefix_cache)
    return Engine(params, cfg, ecfg, make_policy("sc"))


def _mt_replay(engine: Engine, tok):
    """Drive the conversations turn-by-turn; each turn's prompt is the
    full history (system prompt + prior turns + responses)."""
    sys_ids = tok.encode(SYS_TEXT, add_bos=True)
    histories = [list(sys_ids) for _ in range(MT_CONVS)]
    responses = [[] for _ in range(MT_CONVS)]
    metrics = []
    t0 = time.perf_counter()
    for turn in range(MT_TURNS):
        reqs = []
        for c in range(MT_CONVS):
            user = tok.encode(
                f"{(2 * turn + c) % 10}+{(turn + 3 * c) % 10}=",
                add_bos=False)
            histories[c] = histories[c] + user
            reqs.append(Request(request_id=turn * MT_CONVS + c,
                                prompt_tokens=list(histories[c]),
                                n_traces=1, policy=make_policy("sc")))
        results = engine.serve_batch(reqs)
        for c, r in enumerate(results):
            out = [t for t in r.traces[0].output_tokens
                   if t != tok.eos_id]
            histories[c] = histories[c] + out
            responses[c].append(out)
            metrics.append(r.metrics)
    wall = time.perf_counter() - t0
    assert all(m.first_token_s is not None for m in metrics)
    assert engine.pool_drained()
    engine.block_mgr.check_invariants()
    return responses, metrics, wall


def run_multiturn(verbose: bool = False) -> dict:
    cfg = serving_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer()
    sides = {}
    for mode in ("off", "on"):
        engine = _mt_engine(params, cfg, prefix_cache=(mode == "on"))
        # jit warmup on an unrelated prompt, then forget its KV so the
        # timed replay starts from a cold cache
        engine.serve_batch([Request(
            request_id=0, n_traces=1, policy=make_policy("sc"),
            prompt_tokens=tok.encode("9*9-8+7-6+5= " * 4, add_bos=True))])
        if engine.prefix_cache is not None:
            engine.prefix_cache.clear()
            engine.prefix_cache.stats = CacheStats()
        responses, metrics, wall = _mt_replay(engine, tok)
        sides[mode] = (responses, summarize(metrics), wall)
    identical = sides["on"][0] == sides["off"][0]
    assert identical, "prefix cache changed the generated tokens"
    (_, on, wall_on), (_, off, wall_off) = sides["on"], sides["off"]
    payload = {
        "benchmark": "prefix_cache",
        "config": {
            "turns": MT_TURNS, "conversations": MT_CONVS,
            "max_new_tokens": MT_MAX_NEW, "num_blocks": MT_NUM_BLOCKS,
            "capacity": MT_CAPACITY, "prefill_chunk_size": PREFILL_CHUNK,
            "system_prompt_tokens": len(tok.encode(SYS_TEXT,
                                                   add_bos=True)),
        },
        "outputs_identical": identical,
        "num_completed": on["num_completed"],
        "prefix_hit_rate": on["prefix_hit_rate"],
        "total_prompt_tokens": on["total_prompt_tokens"],
        "total_cached_tokens": on["total_cached_tokens"],
        "prefill_tokens_saved": on["total_cached_tokens"],
        "ttft_speedup_x": off["mean_ttft_s"] / on["mean_ttft_s"],
        "cache_on": {"mean_ttft_s": on["mean_ttft_s"],
                     "total_prefill_s": on["total_prefill_s"],
                     "wall_s": wall_on},
        "cache_off": {"mean_ttft_s": off["mean_ttft_s"],
                      "total_prefill_s": off["total_prefill_s"],
                      "wall_s": wall_off},
    }
    if verbose:
        print(f"prefix_cache: {on['num_completed']} turns, "
              f"hit_rate={payload['prefix_hit_rate']:.3f} "
              f"({on['total_cached_tokens']}/{on['total_prompt_tokens']} "
              f"prompt tokens from cache)")
        print(f"  ttft  on={on['mean_ttft_s'] * 1e3:.1f}ms "
              f"off={off['mean_ttft_s'] * 1e3:.1f}ms "
              f"speedup={payload['ttft_speedup_x']:.2f}x")
        print(f"  prefill  on={on['total_prefill_s']:.3f}s "
              f"off={off['total_prefill_s']:.3f}s")
    return payload


# ---------------------------------------------------------------------------
# multi-tenant SLO scenario suite (bursty waves, heavy tails, tenant mix)
# ---------------------------------------------------------------------------

SLO_WAVES = 6            # bursty arrivals: WAVES x WAVE_SIZE requests
SLO_WAVE_SIZE = 10       # (10x the Poisson replay's request count)
SLO_PERIOD_S = 1.2       # wave spacing — each wave lands as a burst
SLO_SPREAD_S = 0.25      # intra-wave arrival jitter
SLO_MAX_BATCH = 16       # decode slots: each wave oversubscribes them
SLO_NUM_BLOCKS = 192
SLO_CAPACITY = 256
SLO_CHAT_MAX_NEW = 8     # per-request max_new_tokens overrides
SLO_REASON_MAX_NEW = 16
SLO_TENANTS = {"premium": 3.0, "batch": 1.0}
# premium: interactive tier — strict-ish TTFT it should comfortably make
# because priority-1 admission jumps every burst's queue. batch: best
# effort — a tight TTFT objective it will miss under bursts, which is
# what drives SLO admission to degrade its reasoning fan-out.
SLO_PREMIUM = SLO(ttft_s=2.5, tpot_s=1.0)
SLO_BATCH = SLO(ttft_s=0.8, tpot_s=1.0, min_traces=1)


def bursty_arrivals(n: int, wave_size: int, period_s: float,
                    spread_s: float, seed: int) -> list:
    """Arrival offsets for bursty waves: request i lands in wave
    i // wave_size at the wave instant plus uniform jitter — the
    flash-crowd load shape (vs. the smooth Poisson trace)."""
    rng = np.random.default_rng(seed)
    return [(i // wave_size) * period_s + float(rng.uniform(0.0, spread_s))
            for i in range(n)]


def heavy_tail_lengths(n: int, seed: int, median: float = 24.0,
                       sigma: float = 0.9, cap: int = 120) -> list:
    """Log-normal filler-token counts: most prompts short, a heavy tail
    of long-context stragglers (capped so prompts fit ``SLO_CAPACITY``)."""
    rng = np.random.default_rng(seed)
    return [int(min(rng.lognormal(np.log(median), sigma), cap))
            for _ in range(n)]


def build_slo_requests(tok):
    n = SLO_WAVES * SLO_WAVE_SIZE
    problems = make_problems(n, seed=SEED, n_steps=(4, 10))
    arrivals = bursty_arrivals(n, SLO_WAVE_SIZE, SLO_PERIOD_S,
                               SLO_SPREAD_S, seed=SEED)
    fillers = heavy_tail_lengths(n, seed=SEED + 1)
    # ~digit soup a char-level tokenizer maps ~1:1 to tokens; sliced per
    # request to the sampled heavy-tail length
    filler_ids = tok.encode("".join(f"{i % 10}+{(i + 3) % 10}= "
                                    for i in range(64)), add_bos=False)
    reqs = []
    for i, (p, at, fill) in enumerate(zip(problems, arrivals, fillers)):
        chat = i % 2 == 0           # single-trace interactive request
        premium = i % 3 == 0        # 1/3 premium, 2/3 batch
        prompt = tok.encode(make_prompt(p), add_bos=True)
        prompt = prompt[:1] + filler_ids[:fill] + prompt[1:]
        reqs.append(Request(
            request_id=i, prompt_tokens=prompt,
            n_traces=1 if chat else 4,
            policy=make_policy("sc"),
            arrival_time=at,
            max_new_tokens=(SLO_CHAT_MAX_NEW if chat
                            else SLO_REASON_MAX_NEW),
            tenant="premium" if premium else "batch",
            priority=1 if premium else 0,
            slo=SLO_PREMIUM if premium else SLO_BATCH))
    return reqs


def run_slo(verbose: bool = False) -> dict:
    cfg = serving_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer()
    ecfg = EngineConfig(
        max_batch=SLO_MAX_BATCH, num_blocks=SLO_NUM_BLOCKS,
        capacity=SLO_CAPACITY, max_new_tokens=SLO_REASON_MAX_NEW,
        sampling=SamplingParams(temperature=0.0, top_k=0, top_p=1.0,
                                max_new_tokens=SLO_REASON_MAX_NEW),
        prefill_chunk_size=PREFILL_CHUNK,
        max_tokens_per_step=MAX_TOKENS_PER_STEP,
        prefix_cache=False)
    engine = Engine(params, cfg, ecfg, make_policy("sc"),
                    scheduler=TenantScheduler(weights=SLO_TENANTS))

    # jit warmup outside the timed replay
    warm = build_slo_requests(tok)[0]
    warm.arrival_time = 0.0
    engine.serve_batch([warm])

    requests = build_slo_requests(tok)
    t0 = time.perf_counter()
    results = engine.serve_batch(requests)
    wall = time.perf_counter() - t0

    assert engine.pool_drained()
    engine.block_mgr.check_invariants()
    metrics = [r.metrics for r in results]
    assert all(m is not None and m.finished_s is not None for m in metrics)

    overall = summarize(metrics)
    tenants = summarize_by_tenant(metrics)
    ratio = (tenants["batch"]["ttft_s"]["p99"]
             / max(tenants["premium"]["ttft_s"]["p99"], 1e-9))
    payload = {
        "benchmark": "slo_serving",
        "config": {
            "n_requests": len(requests), "waves": SLO_WAVES,
            "wave_size": SLO_WAVE_SIZE, "period_s": SLO_PERIOD_S,
            "max_batch": SLO_MAX_BATCH, "num_blocks": SLO_NUM_BLOCKS,
            "capacity": SLO_CAPACITY,
            "max_tokens_per_step": MAX_TOKENS_PER_STEP,
            "prefill_chunk_size": PREFILL_CHUNK,
            "tenant_weights": SLO_TENANTS,
            "premium_slo_ttft_s": SLO_PREMIUM.ttft_s,
            "batch_slo_ttft_s": SLO_BATCH.ttft_s, "seed": SEED,
        },
        "wall_s": wall,
        "num_requests": overall["num_requests"],
        "num_completed": overall["num_completed"],
        "total_output_tokens": overall["total_output_tokens"],
        "throughput_tok_per_s": overall["throughput_tok_per_s"],
        "degraded_traces": overall["degraded_traces"],
        "num_pruned": overall["num_pruned"],
        "ttft_p99_ratio_low_over_high": ratio,
        "tenants": tenants,
    }
    if verbose:
        print(f"slo_serving: {overall['num_completed']}"
              f"/{overall['num_requests']} requests, "
              f"{overall['total_output_tokens']} tokens in {wall:.2f}s "
              f"({overall['throughput_tok_per_s']:.1f} tok/s), "
              f"degraded_traces={overall['degraded_traces']}")
        for name, t in tenants.items():
            att = t["slo"]["ttft_attainment"]
            print(f"  [{name}] n={t['num_requests']} "
                  f"ttft p50={t['ttft_s']['p50']:.3f}s "
                  f"p99={t['ttft_s']['p99']:.3f}s "
                  f"ttft_slo={'n/a' if att is None else f'{att:.2f}'} "
                  f"degraded={t['degraded_traces']}")
        print(f"  ttft p99 batch/premium = {ratio:.2f}x")
    return payload


# ---------------------------------------------------------------------------
# fault-tolerance workload (retry/degrade recovery, quarantine, deadlines)
# ---------------------------------------------------------------------------

# transient-only plan: two consecutive device-step failures at tick 2
# (retried within the retry_limit=3 budget -> recovered, no degrade
# rung), a one-tick allocation stall at tick 5 (below shed_after), and
# one more step failure at tick 9. Recovery must be invisible: the
# faulted replay produces bit-identical tokens to the fault-free one.
FAULT_TRANSIENT_PLAN = "step@2x2,alloc@5,step@9"
# chaos leg adds a NaN burst poisoning decode lane 1 at tick 6 — that
# lane is quarantined (FAILED) while its siblings finish untouched.
FAULT_CHAOS_PLAN = FAULT_TRANSIENT_PLAN + ",nan@6:slot=1"


def _fault_engine(params, cfg, plan: str | None) -> Engine:
    # faults=None explicitly: the engine default reads REPRO_FAULTS, and
    # a CI chaos env leaking into the fault-free baseline would break
    # the identity comparison. The plan is attached after jit warmup.
    ecfg = EngineConfig(
        max_batch=N_REQUESTS * N_TRACES, num_blocks=NUM_BLOCKS,
        capacity=CAPACITY, max_new_tokens=MAX_NEW,
        sampling=SamplingParams(temperature=0.0, top_k=0, top_p=1.0,
                                max_new_tokens=MAX_NEW),
        prefill_chunk_size=PREFILL_CHUNK,
        max_tokens_per_step=MAX_TOKENS_PER_STEP,
        prefix_cache=False, faults=None)
    engine = Engine(params, cfg, ecfg, make_policy("sc"))
    tok = get_tokenizer()
    warm = build_requests(tok)[0]
    warm.arrival_time = 0.0
    engine.serve_batch([warm])
    if plan is not None:
        engine.fault_plan = FaultPlan.parse(plan, seed=ecfg.seed)
    return engine


def _fault_snapshot(results):
    return [[(list(t.output_tokens), t.status.name) for t in r.traces]
            for r in results]


def run_faults(verbose: bool = False) -> dict:
    cfg = serving_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer()

    # identity leg: fault-free vs transient-fault replay of the same
    # Poisson trace. Greedy decode + recovery that consumes no RNG means
    # the snapshots must match token-for-token.
    sides = {}
    for mode, plan in (("clean", None), ("faulted", FAULT_TRANSIENT_PLAN)):
        engine = _fault_engine(params, cfg, plan)
        requests = build_requests(tok)
        t0 = time.perf_counter()
        results = engine.serve_batch(requests)
        wall = time.perf_counter() - t0
        assert engine.pool_drained()
        engine.check_integrity()
        tokens = sum(r.metrics.output_tokens for r in results
                     if r.status == "completed")
        sides[mode] = (_fault_snapshot(results), tokens, wall,
                       engine.fault_stats)
    identical = sides["faulted"][0] == sides["clean"][0]
    assert identical, "fault recovery changed the generated tokens"
    stats = sides["faulted"][3]
    assert stats.recovered_steps == 2 and stats.degraded_horizon == 0, \
        "transient plan was expected to recover without degrading"
    goodput = {m: sides[m][1] / sides[m][2] for m in sides}
    ratio = goodput["faulted"] / goodput["clean"]

    # chaos leg: NaN lane poisoning + an unmeetable deadline on the last
    # request — quarantine and cancellation on top of the retry path.
    engine = _fault_engine(params, cfg, FAULT_CHAOS_PLAN)
    requests = build_requests(tok)
    requests[-1].deadline = 0.0
    t0 = time.perf_counter()
    results = engine.serve_batch(requests)
    chaos_wall = time.perf_counter() - t0
    assert engine.pool_drained()
    engine.check_integrity()
    chaos = summarize([r.metrics for r in results])
    cstats = engine.fault_stats
    assert cstats.nan_quarantined == 1, "NaN burst missed its lane"
    assert chaos["num_deadline_exceeded"] == 1

    payload = {
        "benchmark": "fault_serving",
        "config": {
            "n_requests": N_REQUESTS, "n_traces": N_TRACES,
            "max_new_tokens": MAX_NEW, "num_blocks": NUM_BLOCKS,
            "capacity": CAPACITY, "arrival_rate_per_s": ARRIVAL_RATE,
            "prefill_chunk_size": PREFILL_CHUNK,
            "max_tokens_per_step": MAX_TOKENS_PER_STEP, "seed": SEED,
            "transient_plan": FAULT_TRANSIENT_PLAN,
            "chaos_plan": FAULT_CHAOS_PLAN,
        },
        "outputs_identical": identical,
        "step_faults": stats.step_faults,
        "step_retries": stats.step_retries,
        "recovered_steps": stats.recovered_steps,
        "alloc_stalls": stats.alloc_faults,
        "degraded_to_dense": stats.degraded_to_dense,
        "degraded_horizon": stats.degraded_horizon,
        "goodput_ratio": ratio,
        "clean": {"wall_s": sides["clean"][2],
                  "goodput_tok_per_s": goodput["clean"]},
        "faulted": {"wall_s": sides["faulted"][2],
                    "goodput_tok_per_s": goodput["faulted"]},
        "chaos": {
            "wall_s": chaos_wall,
            "num_completed": chaos["num_completed"],
            "num_deadline_exceeded": chaos["num_deadline_exceeded"],
            "num_cancelled": chaos["num_cancelled"],
            "nan_quarantined": cstats.nan_quarantined,
            "failed_traces": chaos["failed_traces"],
        },
    }
    if verbose:
        print(f"fault_serving: outputs_identical={identical} "
              f"({stats.step_faults} step faults, "
              f"{stats.recovered_steps} recovered, "
              f"{stats.alloc_faults} alloc stalls)")
        print(f"  goodput  clean={goodput['clean']:.1f} tok/s "
              f"faulted={goodput['faulted']:.1f} tok/s "
              f"ratio={ratio:.2f}")
        print(f"  chaos    completed={chaos['num_completed']}"
              f"/{N_REQUESTS} "
              f"deadline_exceeded={chaos['num_deadline_exceeded']} "
              f"quarantined={cstats.nan_quarantined}")
    return payload


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--multiturn", action="store_true",
                    help="run the prefix-cache conversation workload "
                         "instead of the Poisson load replay")
    ap.add_argument("--slo", action="store_true",
                    help="run the multi-tenant SLO scenario suite "
                         "(bursty waves, heavy-tail prompts, tenant mix) "
                         "instead of the Poisson load replay")
    ap.add_argument("--faults", action="store_true",
                    help="run the fault-tolerance workload (seeded "
                         "transient faults vs fault-free identity, plus "
                         "a NaN-quarantine / deadline chaos leg) instead "
                         "of the Poisson load replay")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.multiturn:
        payload, default_out = run_multiturn(verbose=True), \
            "BENCH_prefix_cache.json"
    elif args.slo:
        payload, default_out = run_slo(verbose=True), "BENCH_slo.json"
    elif args.faults:
        payload, default_out = run_faults(verbose=True), "BENCH_faults.json"
    else:
        payload, default_out = run(verbose=True), "BENCH_serving.json"
    out = os.path.abspath(args.out or os.path.join(
        os.path.dirname(__file__), "..", default_out))
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out}")
    return payload


if __name__ == "__main__":
    main()
