"""Serving-engine system tests: scheduling, preemption, pruning, accounting."""
import jax
import pytest

from repro.configs.registry import serving_config
from repro.core.pruning import make_policy
from repro.core.scorer import init_scorer
from repro.core.trace import TraceStatus
from repro.data.tokenizer import get_tokenizer
from repro.models.init import init_params
from repro.serving import Engine, EngineConfig, Request, SamplingParams


@pytest.fixture(scope="module")
def setup():
    cfg = serving_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    scorer = init_scorer(jax.random.PRNGKey(1), cfg.d_model)
    tok = get_tokenizer()
    prompt = tok.encode("3+5-2=", add_bos=True)
    return cfg, params, scorer, prompt


def _ecfg(num_blocks=40, max_new=48, batch=8):
    return EngineConfig(
        max_batch=batch, num_blocks=num_blocks, capacity=128,
        max_new_tokens=max_new,
        sampling=SamplingParams(max_new_tokens=max_new))


def _run(setup, method, num_blocks=40, n=8, max_new=48, **pkw):
    cfg, params, scorer, prompt = setup
    policy = make_policy(method, **pkw)
    eng = Engine(params, cfg, _ecfg(num_blocks, max_new), policy,
                 scorer_params=scorer if policy.uses_scorer else None)
    res = eng.serve(prompt, n)
    return eng, res


def test_sc_completes_all_traces(setup):
    eng, res = _run(setup, "sc")
    assert all(t.status == TraceStatus.FINISHED for t in res.traces)
    assert res.num_pruned == 0
    # allocator clean: every block returned
    assert eng.pool_drained()
    eng.block_mgr.check_invariants()


@pytest.fixture(scope="module")
def tight_blocks(setup):
    """A pool about half the size the 8 traces hold at their peak when
    nothing stops them, plus the allocator's scratch block: pressure
    follows from what the traces need, whatever lengths the weights
    give them."""
    _, res = _run(setup, "sc", num_blocks=40, max_new=100)
    assert all(t.status == TraceStatus.FINISHED for t in res.traces)
    assert res.num_preemptions == 0
    return res.peak_blocks_used // 2 + 1


def test_sc_preempts_under_memory_pressure(setup, tight_blocks):
    """The paper's Fig. 2c bottleneck: tight pool => preemption + waiting."""
    eng, res = _run(setup, "sc", num_blocks=tight_blocks, max_new=100)
    assert res.num_preemptions > 0
    assert res.wait_s > 0
    # discard-and-recompute: preempted traces prefill more than once
    assert any(t.prefill_count > 1 for t in res.traces)
    # SC never prunes: every trace eventually finishes
    assert all(t.status == TraceStatus.FINISHED for t in res.traces)
    assert eng.pool_drained()


def test_step_never_waits(setup, tight_blocks):
    """STEP's claim (Table 3): memory-aware pruning => zero waiting."""
    eng, res = _run(setup, "step", num_blocks=tight_blocks, max_new=100)
    assert res.wait_s == 0.0
    assert res.num_preemptions == 0
    assert res.num_pruned > 0
    # pruned + finished covers every trace
    assert all(t.status in (TraceStatus.FINISHED, TraceStatus.PRUNED)
               for t in res.traces)
    assert eng.pool_drained()


def test_step_prunes_lowest_scored(setup, tight_blocks):
    eng, res = _run(setup, "step", num_blocks=tight_blocks, max_new=100)
    pruned = [t for t in res.traces if t.status == TraceStatus.PRUNED]
    assert pruned
    # every pruned trace recorded step scores or was at the uninformative
    # prior; the engine must have consulted the scorer
    for t in pruned:
        assert 0.0 <= t.score <= 1.0


def test_step_faster_than_sc_under_pressure(setup, tight_blocks):
    _, res_sc = _run(setup, "sc", num_blocks=tight_blocks, max_new=100)
    _, res_step = _run(setup, "step", num_blocks=tight_blocks, max_new=100)
    assert res_step.latency_s < res_sc.latency_s
    # STEP does zero recompute; SC's preemptions force re-prefills
    assert res_step.num_preemptions == 0 and res_sc.num_preemptions > 0


def test_deepconf_warmup_then_prune(setup):
    eng, res = _run(setup, "deepconf", warmup=4, keep_pct=0.25)
    # the warmup traces must all finish (no early termination before the
    # threshold exists); later traces may be terminated
    assert all(t.status in (TraceStatus.FINISHED, TraceStatus.PRUNED)
               for t in res.traces)
    assert eng.pool_drained()


def test_cot_single_trace(setup):
    _, res = _run(setup, "cot", n=1)
    assert len(res.traces) == 1
    assert res.wait_s == 0.0


def test_weighted_vote_used_by_step(setup):
    _, res = _run(setup, "step")
    finished = [t for t in res.traces if t.status == TraceStatus.FINISHED]
    answered = [t for t in finished if t.answer is not None]
    if answered:
        assert res.answer in {t.answer for t in answered}


def test_trace_budget_respected(setup):
    _, res = _run(setup, "sc", n=4)
    assert len(res.traces) == 4
    assert res.total_tokens <= 4 * 48


# ---------------------------------------------------------------------------
# prefix sharing (COW) + multi-request scheduling
# ---------------------------------------------------------------------------

def _greedy_ecfg(share, num_blocks=64, max_new=32, batch=8):
    return EngineConfig(
        max_batch=batch, num_blocks=num_blocks, capacity=128,
        max_new_tokens=max_new,
        sampling=SamplingParams(temperature=0.0, top_k=0, top_p=1.0,
                                max_new_tokens=max_new),
        share_prompt_prefix=share)


def test_shared_prefix_matches_per_trace_greedy(setup):
    """The COW fork must be invisible to the model: under greedy sampling
    both prefill modes generate token-identical traces."""
    cfg, params, _, prompt = setup
    outs = []
    for share in (True, False):
        eng = Engine(params, cfg, _greedy_ecfg(share), make_policy("sc"))
        res = eng.serve(prompt, 6)
        assert all(t.status == TraceStatus.FINISHED for t in res.traces)
        outs.append([t.output_tokens for t in res.traces])
        assert eng.pool_drained()
        eng.block_mgr.check_invariants()
    assert outs[0] == outs[1]


def test_shared_prefix_prefills_once(setup):
    """N traces of one request => exactly one prompt prefill (vs N)."""
    cfg, params, _, prompt = setup
    for share, expected in ((True, 1), (False, 6)):
        eng = Engine(params, cfg, _greedy_ecfg(share), make_policy("sc"))
        calls = []
        orig = eng._prefill
        eng._prefill = lambda p, t: (calls.append(t.shape) or orig(p, t))
        eng.serve(prompt, 6)
        assert len(calls) == expected


def test_serve_batch_multi_request(setup):
    """Traces of different requests co-exist in the decode batch; results
    aggregate per request and the pool drains clean."""
    cfg, params, _, prompt = setup
    tok = get_tokenizer()
    eng = Engine(params, cfg, _greedy_ecfg(True, max_new=24),
                 make_policy("sc"))
    reqs = [
        Request(request_id=7, prompt_tokens=prompt, n_traces=4,
                policy=make_policy("sc")),
        Request(request_id=9,
                prompt_tokens=tok.encode("7*2+1=", add_bos=True),
                n_traces=4, policy=make_policy("sc")),
    ]
    results = eng.serve_batch(reqs)
    assert [r.request_id for r in results] == [7, 9]
    for r in results:
        assert len(r.traces) == 4
        assert all(t.request_id == r.request_id for t in r.traces)
        assert all(t.status == TraceStatus.FINISHED for t in r.traces)
    assert eng.pool_drained()
    eng.block_mgr.check_invariants()


def test_serve_batch_queues_beyond_max_batch(setup):
    """More total traces than decode slots: surplus waits for a slot and
    still completes (slot waiting is not memory WAIT)."""
    cfg, params, _, prompt = setup
    eng = Engine(params, cfg, _greedy_ecfg(True, max_new=16, batch=4),
                 make_policy("sc"))
    reqs = [Request(request_id=i, prompt_tokens=prompt, n_traces=3,
                    policy=make_policy("sc")) for i in range(3)]
    results = eng.serve_batch(reqs)
    for r in results:
        assert all(t.status == TraceStatus.FINISHED for t in r.traces)
    assert eng.pool_drained()


def test_prefix_cache_on_off_identity(setup):
    """The cross-request prefix cache must be invisible to generation:
    tokens, step scores and prune decisions are identical with the cache
    on vs off under fixed RNG (a hit serves bit-identical KV and the
    engine evicts parked blocks before any pruning decision)."""
    from repro.models import kv_quant
    if kv_quant.is_quantized(EngineConfig().kv_dtype):
        # Exact on/off identity is a float-pool contract: a cache HIT
        # recomputes the suffix reading the quantized prefix KV from the
        # pool, while a MISS one-shot-prefills the whole prompt with
        # exact hidden states — inherently divergent under a lossy
        # dtype. tests/test_kv_quant.py covers prefix-cache correctness
        # (hits occur, drains, bounded drift) for int8/fp8 pools.
        pytest.skip("prefix-cache on/off identity pinned for float "
                    "pools only (lossy kv_dtype hits re-read quantized "
                    "prefix KV)")
    cfg, params, scorer, _ = setup
    tok = get_tokenizer()
    prompt = tok.encode("1+2-3+4-5+6-7+8=" * 2, add_bos=True)  # 33 toks
    runs = []
    for on in (True, False):
        ecfg = EngineConfig(
            max_batch=8, num_blocks=24, capacity=128, max_new_tokens=64,
            sampling=SamplingParams(temperature=0.0, top_k=0, top_p=1.0,
                                    max_new_tokens=64),
            share_prompt_prefix=True, prefix_cache=on)
        eng = Engine(params, cfg, ecfg, make_policy("step"),
                     scorer_params=scorer)
        rounds = []
        for _ in range(2):  # round 2 replays into a warm cache
            res = eng.serve(prompt, 6)
            rounds.append([(t.output_tokens, t.step_scores, t.status)
                           for t in res.traces])
        runs.append(rounds)
        assert eng.pool_drained()
        eng.block_mgr.check_invariants()
    assert runs[0] == runs[1]


def test_serve_batch_step_cross_request_contention(setup):
    """Two STEP requests contending for one tight pool: each request's
    policy prunes its own traces, no request ever waits."""
    cfg, params, scorer, prompt = setup
    ecfg = EngineConfig(max_batch=8, num_blocks=12, capacity=128,
                        max_new_tokens=100,
                        sampling=SamplingParams(max_new_tokens=100))
    eng = Engine(params, cfg, ecfg, make_policy("step"),
                 scorer_params=scorer)
    reqs = [Request(request_id=i, prompt_tokens=prompt, n_traces=4,
                    policy=make_policy("step")) for i in range(2)]
    results = eng.serve_batch(reqs)
    assert sum(r.num_pruned for r in results) > 0
    for r in results:
        assert r.wait_s == 0.0
        assert r.num_preemptions == 0
        assert all(t.status in (TraceStatus.FINISHED, TraceStatus.PRUNED)
                   for t in r.traces)
    assert eng.pool_drained()
    eng.block_mgr.check_invariants()
