"""Fused multi-token decode horizon (`EngineConfig.decode_horizon`).

Pins the two equivalences the tentpole rests on:

  * model level — `multi_decode_step` (one jitted lax.scan over K
    iterations) emits exactly what K successive `decode_step` +
    `sample_logits` calls emit for the same key stream, including
    per-lane limits, EOS deactivation and step boundaries sitting at
    horizon edges;
  * engine level — `decode_horizon=K` generates token-identical traces
    (and step-score-identical, to float tolerance) to `decode_horizon=1`
    under a fixed RNG, for greedy and temperature sampling, with traces
    hitting EOS mid-horizon.

Plus the scheduling semantics around the horizon: the pressure-triggered
fallback to single-token ticks, STEP pruning in tight pools, chunked
prefill interleaving, and the per-tick decode-burst policy hook.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.registry import serving_config
from repro.core.pruning import make_policy
from repro.core.scorer import init_scorer, scorer_score
from repro.core.trace import TraceStatus
from repro.data.tokenizer import get_tokenizer
from repro.models.init import init_params
from repro.models.model import (decode_step, init_decode_cache,
                                multi_decode_step)
from repro.serving import Engine, EngineConfig, Request, SamplingParams
from repro.serving.sampling import sample_logits

MAX_NEW = 32
BATCH = 8
HORIZONS = (2, 4, 8)


# module-level caches instead of fixtures: the property test below runs
# under @given, which cannot receive pytest fixtures (neither with real
# hypothesis nor with the tests/_hypothesis_stub fallback)
_STATE: dict = {}


def _setup():
    if "cfg" not in _STATE:
        cfg = serving_config()
        _STATE["cfg"] = cfg
        _STATE["params"] = init_params(cfg, jax.random.PRNGKey(0))
        _STATE["scorer"] = init_scorer(jax.random.PRNGKey(1), cfg.d_model)
        tok = get_tokenizer()
        _STATE["tok"] = tok
        _STATE["prompts"] = [tok.encode(p, add_bos=True)
                             for p in ("3+5-2=", "7*2+1=", "9-4+6=")]
    return (_STATE["cfg"], _STATE["params"], _STATE["scorer"],
            _STATE["tok"], _STATE["prompts"])


@pytest.fixture(scope="module")
def setup():
    return _setup()


def _ecfg(K, temperature=0.8, num_blocks=64, max_new=MAX_NEW, batch=BATCH):
    return EngineConfig(
        max_batch=batch, num_blocks=num_blocks, capacity=128,
        max_new_tokens=max_new,
        sampling=SamplingParams(temperature=temperature,
                                top_k=0 if temperature == 0.0 else 20,
                                top_p=1.0 if temperature == 0.0 else 0.95,
                                max_new_tokens=max_new),
        decode_horizon=K)


def _engines():
    """One engine per (horizon, sampling mode), compiled once and reused
    across property examples (the per-example reset is the RNG key)."""
    if "engines" not in _STATE:
        cfg, params, scorer, _, _ = _setup()
        out = {}
        for temp in (0.0, 0.8):
            for K in (1,) + HORIZONS:
                eng = Engine(params, cfg, _ecfg(K, temperature=temp),
                             make_policy("step"), scorer_params=scorer)
                out[(K, temp)] = eng
        _STATE["engines"] = out
    return _STATE["engines"]


def _serve(eng, prompt, n_traces, rng_seed):
    eng._rng = jax.random.PRNGKey(rng_seed)  # align key streams
    res = eng.serve_batch([Request(request_id=0, prompt_tokens=prompt,
                                   n_traces=n_traces,
                                   policy=make_policy("step"))])[0]
    assert eng.pool_drained()
    eng.block_mgr.check_invariants()
    return res


@settings(max_examples=6, deadline=None)
@given(st.sampled_from(HORIZONS), st.integers(0, 2), st.integers(2, 6),
       st.booleans(), st.integers(0, 10**6))
def test_horizon_token_identical_to_single_step(K, prompt_idx, n_traces,
                                                greedy, rng_seed):
    """decode_horizon=K must generate exactly what decode_horizon=1
    generates under a fixed RNG: same tokens, same step scores (traces
    hit EOS mid-horizon under temperature sampling; greedy runs to the
    token cap, placing step boundaries anywhere incl. horizon edges)."""
    engines = _engines()
    _, _, _, _, prompts = _setup()
    temp = 0.0 if greedy else 0.8
    prompt = prompts[prompt_idx]
    ref = _serve(engines[(1, temp)], prompt, n_traces, rng_seed)
    got = _serve(engines[(K, temp)], prompt, n_traces, rng_seed)
    assert [t.output_tokens for t in got.traces] \
        == [t.output_tokens for t in ref.traces]
    for a, b in zip(ref.traces, got.traces):
        assert a.status == b.status
        assert len(a.step_scores) == len(b.step_scores)
        assert np.allclose(a.step_scores, b.step_scores,
                           rtol=1e-4, atol=1e-5)
        assert np.allclose(a.token_confidences, b.token_confidences,
                           rtol=1e-4, atol=1e-5)


def test_eos_mid_horizon(setup):
    """Temperature sampling on the random-init model ends traces at
    scattered lengths — EOS landing inside a fused horizon — and the
    K=8 run must still match K=1 exactly."""
    _, _, _, _, prompts = setup
    engines = _engines()
    ref = _serve(engines[(1, 0.8)], prompts[0], 6, rng_seed=7)
    got = _serve(engines[(8, 0.8)], prompts[0], 6, rng_seed=7)
    lens = [t.num_tokens for t in ref.traces]
    assert min(lens) < MAX_NEW, lens  # at least one early EOS
    assert len(set(lens)) > 1, lens
    assert [t.output_tokens for t in got.traces] \
        == [t.output_tokens for t in ref.traces]


def test_multi_decode_step_matches_decode_step_loop(setup):
    """Model-level pin: the fused scan == a Python loop of single
    decode_step + sample_logits calls over the same key stream, with
    per-lane limits and step boundaries at the horizon edge (lane input
    tokens chosen == step_id at iteration 0)."""
    cfg, params, scorer, tok, _ = setup
    B, K, capacity = 4, 3, 64
    bs = cfg.kv_block_size
    bp = -(-capacity // bs)
    cache = init_decode_cache(cfg, B, capacity, num_blocks=1 + B * bp)
    bt = np.arange(1, 1 + B * bp, dtype=np.int32).reshape(B, bp)
    cache["block_tables"] = jnp.asarray(bt)
    # iteration-0 inputs: two lanes sit exactly on a step boundary
    tokens = jnp.asarray([tok.step_id, 7, tok.step_id, 9], jnp.int32)
    positions = jnp.zeros((B,), jnp.int32)
    limits = jnp.asarray([3, 3, 2, 1], jnp.int32)
    keys, rng = [], jax.random.PRNGKey(42)
    for _ in range(K):
        rng, k = jax.random.split(rng)
        keys.append(k)

    def sample_fn(key, logits):
        logits = logits.at[:, cfg.vocab_size:].set(-jnp.inf)
        return sample_logits(key, logits, temperature=0.8, top_k=20,
                             top_p=0.95)

    out = multi_decode_step(
        params, cfg, tokens, positions, limits, dict(cache),
        window_len=capacity, horizon=K, rng_keys=jnp.stack(keys),
        sample_fn=sample_fn, eos_id=tok.eos_id, step_id=tok.step_id,
        score_fn=lambda h: scorer_score(scorer, h))

    # reference: K sequential single-token decode steps (the old engine
    # inner loop), tracking per-lane active state on the host
    ref_cache = dict(cache)
    ct = np.asarray(tokens).copy()
    pos = np.zeros((B,), np.int32)
    active = np.asarray(limits) > 0
    ref_toks = np.zeros((B, K), np.int32)
    ref_valid = np.zeros((B, K), bool)
    ref_svalid = np.zeros((B, K), bool)
    ref_scores = np.zeros((B, K), np.float32)
    for k in range(K):
        step = decode_step(params, cfg, jnp.asarray(ct[:, None]),
                           jnp.asarray(pos), ref_cache, window_len=capacity)
        ref_cache = step["cache"]
        nt, _ = sample_fn(keys[k], step["logits"])
        nt = np.asarray(nt)
        sc = np.asarray(scorer_score(scorer, step["hidden"]))
        for i in range(B):
            if not active[i]:
                continue
            ref_valid[i, k] = True
            ref_svalid[i, k] = ct[i] == tok.step_id
            ref_scores[i, k] = sc[i]
            ref_toks[i, k] = nt[i]
            pos[i] += 1
            ct[i] = nt[i]
            if nt[i] == tok.eos_id or k + 1 >= int(limits[i]):
                active[i] = False

    got_valid = np.asarray(out["token_valid"])
    assert (got_valid == ref_valid).all()
    assert (np.asarray(out["score_valid"]) == ref_svalid).all()
    assert (np.asarray(out["tokens"])[ref_valid]
            == ref_toks[ref_valid]).all()
    assert np.allclose(np.asarray(out["scores"])[ref_svalid],
                       ref_scores[ref_svalid], rtol=1e-4, atol=1e-5)
    assert (np.asarray(out["positions"]) == pos).all()
    assert (np.asarray(out["final_tokens"]) == ct).all()
    # step boundaries at the horizon edge were actually exercised
    assert ref_svalid[0, 0] and ref_svalid[2, 0]


def test_horizon_pressure_fallback(setup):
    """Waiting traces + a short free list force single-token ticks so
    frontier pre-allocation never starves waiting admissions."""
    cfg, params, scorer, _, prompts = setup
    policy = make_policy("step")
    eng = Engine(params, cfg,
                 _ecfg(8, temperature=0.0, num_blocks=12, max_new=48,
                       batch=4),
                 policy, scorer_params=scorer)
    res = eng.serve_batch([Request(request_id=0, prompt_tokens=prompts[0],
                                   n_traces=8, policy=policy)])[0]
    assert eng.counters.horizon_fallbacks > 0
    assert res.wait_s == 0.0 and res.num_preemptions == 0
    assert eng.pool_drained()
    eng.block_mgr.check_invariants()


def test_step_prunes_in_tight_pool_with_horizon(setup):
    """Memory-triggered STEP pruning still fires with a fused horizon
    (greedy runs to the cap, so the pool must fill)."""
    cfg, params, scorer, _, prompts = setup
    eng = Engine(params, cfg,
                 _ecfg(8, temperature=0.0, num_blocks=12, max_new=100),
                 make_policy("step"), scorer_params=scorer)
    res = eng.serve(prompts[0], 8)
    assert res.num_pruned > 0
    assert res.wait_s == 0.0 and res.num_preemptions == 0
    assert all(t.status in (TraceStatus.FINISHED, TraceStatus.PRUNED)
               for t in res.traces)
    assert eng.pool_drained()
    eng.block_mgr.check_invariants()


def test_sc_preemption_in_tight_pool_with_horizon(setup):
    """Baseline preemption (discard-and-recompute) composes with the
    horizon: every trace still finishes and the pool drains clean."""
    cfg, params, _, _, prompts = setup
    eng = Engine(params, cfg,
                 _ecfg(4, temperature=0.0, num_blocks=12, max_new=64),
                 make_policy("sc"))
    res = eng.serve(prompts[0], 8)
    assert res.num_preemptions > 0
    assert all(t.status == TraceStatus.FINISHED for t in res.traces)
    assert eng.pool_drained()
    eng.block_mgr.check_invariants()


def test_horizon_with_chunked_prefill_multi_request(setup):
    """Chunked prefill + online arrival + horizon>1 interleave; outputs
    match the horizon=1 run of the identical scenario."""
    cfg, params, _, _, prompts = setup
    outs = []
    for K in (1, 4):
        ecfg = dataclasses.replace(_ecfg(K, temperature=0.0, max_new=16),
                                   prefill_chunk_size=4)
        eng = Engine(params, cfg, ecfg, make_policy("sc"))
        reqs = [Request(request_id=i, prompt_tokens=p, n_traces=2,
                        policy=make_policy("sc"))
                for i, p in enumerate(prompts)]
        results = eng.serve_batch(reqs)
        for r in results:
            assert all(t.status == TraceStatus.FINISHED for t in r.traces)
        outs.append({r.request_id: [t.output_tokens for t in r.traces]
                     for r in results})
        assert eng.pool_drained()
        eng.block_mgr.check_invariants()
    assert outs[0] == outs[1]


def test_horizon_respects_token_budget(setup):
    """max_tokens_per_step charges a full horizon per running/admitted
    trace (pessimistic), so a tick can never exceed the budget; every
    trace still completes under a tight budget."""
    cfg, params, _, _, prompts = setup
    ecfg = dataclasses.replace(
        _ecfg(4, temperature=0.0, max_new=16),
        prefill_chunk_size=4, max_tokens_per_step=8)
    eng = Engine(params, cfg, ecfg, make_policy("sc"))
    reqs = [Request(request_id=i, prompt_tokens=p, n_traces=2,
                    policy=make_policy("sc"))
            for i, p in enumerate(prompts)]
    results = eng.serve_batch(reqs)
    for r in results:
        assert all(t.status == TraceStatus.FINISHED for t in r.traces)
    assert eng.pool_drained()
    eng.block_mgr.check_invariants()


def test_policy_observes_decode_bursts(setup):
    """The engine hands each trace's per-tick burst (tokens, confs, step
    scores) to the policy in one call, never longer than the horizon."""
    cfg, params, _, _, prompts = setup
    bursts = []

    class Spy(type(make_policy("sc"))):
        def observe_decode_burst(self, trace, tokens, confidences,
                                 step_scores):
            bursts.append((trace.trace_id, list(tokens),
                           list(confidences)))

    policy = Spy()
    eng = Engine(params, cfg, _ecfg(4, temperature=0.0, max_new=16),
                 policy)
    res = eng.serve_batch([Request(request_id=0,
                                   prompt_tokens=prompts[0],
                                   n_traces=2, policy=policy)])[0]
    assert bursts
    assert all(1 <= len(toks) <= 4 for _, toks, _ in bursts)
    assert all(len(toks) == len(confs) for _, toks, confs in bursts)
    for t in res.traces:
        got = [tk for tid, toks, _ in bursts if tid == t.trace_id
               for tk in toks]
        # bursts reconstruct the decoded suffix (first token comes from
        # the prefill-logit sampling, not from a decode burst)
        assert got == t.output_tokens[1:]


def test_decode_horizon_default_is_one():
    assert EngineConfig().decode_horizon == 1
