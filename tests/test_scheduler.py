"""Scheduler-core tests: the FIFO reduction pin (TenantScheduler with a
single tenant must be op-identical to the default FIFO policy, fixed
RNG), weighted-fair DRR budgets, SLO admission control, per-request
sampling / max_new_tokens overrides, EngineConfig.from_env, and the
event-stream contract."""
import functools

import jax
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.registry import serving_config
from repro.core.pruning import make_policy
from repro.core.trace import TraceStatus
from repro.data.tokenizer import get_tokenizer
from repro.models.init import init_params
from repro.serving import (SLO, Arrival, BudgetReplenish, BurstDone,
                           Completion, DeficitRoundRobin, Engine,
                           EngineConfig, FIFOPolicy, Request, SamplingParams,
                           SchedulingPolicy, TenantScheduler, TokenBudget,
                           WeightedTokenBudget, default_scheduler,
                           parse_tenant_weights)


@functools.lru_cache(maxsize=1)
def _setup():
    """Module-level cache instead of a fixture: the hypothesis property
    tests can't receive pytest fixtures under the dependency-free stub
    runner (tests/_hypothesis_stub.py)."""
    cfg = serving_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer()
    prompts = [tok.encode("3+5-2=", add_bos=True),
               tok.encode("7*2+1=", add_bos=True),
               tok.encode("9-4+6=", add_bos=True)]
    return cfg, params, prompts


@pytest.fixture(scope="module")
def setup():
    return _setup()


def _ecfg(num_blocks=64, max_new=12, batch=8, chunk=None, budget=None,
          temperature=0.0, seed=1234):
    return EngineConfig(
        max_batch=batch, num_blocks=num_blocks, capacity=128,
        max_new_tokens=max_new, seed=seed,
        sampling=SamplingParams(temperature=temperature, top_k=0,
                                top_p=1.0, max_new_tokens=max_new),
        prefill_chunk_size=chunk, max_tokens_per_step=budget)


def _reqs(prompts, n=2, arrivals=None, **extra):
    arrivals = arrivals or [0.0] * len(prompts)
    return [Request(request_id=i, prompt_tokens=p, n_traces=n,
                    policy=make_policy("sc"), arrival_time=a, **extra)
            for i, (p, a) in enumerate(zip(prompts, arrivals))]


def _snapshot(results):
    """Everything the reduction pin compares: tokens, statuses, scores
    and prune counts per request."""
    return {r.request_id: ([(t.output_tokens, t.status, t.score)
                            for t in r.traces], r.num_pruned)
            for r in results}


# ---------------------------------------------------------------------------
# policy plumbing units
# ---------------------------------------------------------------------------

def test_parse_tenant_weights():
    assert parse_tenant_weights("premium:3,batch:1") == \
        {"premium": 3.0, "batch": 1.0}
    assert parse_tenant_weights(" a : 2.5 ") == {"a": 2.5}
    with pytest.raises(ValueError):
        parse_tenant_weights("premium=3")
    with pytest.raises(ValueError):
        parse_tenant_weights("a:0")


def test_default_scheduler_env(monkeypatch):
    # unset / "fifo" -> None: the engine builds a FIFOPolicy per run
    monkeypatch.delenv("REPRO_SCHED", raising=False)
    assert default_scheduler() is None
    monkeypatch.setenv("REPRO_SCHED", "fifo")
    assert default_scheduler() is None
    monkeypatch.setenv("REPRO_SCHED", "tenant")
    sched = default_scheduler()
    assert isinstance(sched, TenantScheduler)
    assert isinstance(sched, SchedulingPolicy)
    monkeypatch.setenv("REPRO_SCHED", "bogus")
    with pytest.raises(ValueError):
        default_scheduler()


def test_engine_config_from_env(monkeypatch):
    monkeypatch.setenv("REPRO_MAX_BATCH", "7")
    monkeypatch.setenv("REPRO_DECODE_HORIZON", "3")
    monkeypatch.setenv("REPRO_MAX_TOKENS_PER_STEP", "48")
    ecfg = EngineConfig.from_env()
    assert ecfg.max_batch == 7
    assert ecfg.decode_horizon == 3
    assert ecfg.max_tokens_per_step == 48
    # explicit overrides beat the environment
    ecfg = EngineConfig.from_env(max_batch=3)
    assert ecfg.max_batch == 3 and ecfg.decode_horizon == 3
    monkeypatch.delenv("REPRO_MAX_BATCH")
    monkeypatch.delenv("REPRO_DECODE_HORIZON")
    monkeypatch.delenv("REPRO_MAX_TOKENS_PER_STEP")
    assert EngineConfig.from_env().max_batch == EngineConfig().max_batch


def test_engine_config_from_env_rejects_malformed_values(monkeypatch):
    """Malformed REPRO_* values fail loudly with the variable name and
    the accepted range in the message — not a bare int() traceback."""
    monkeypatch.setenv("REPRO_MAX_BATCH", "eight")
    with pytest.raises(ValueError,
                       match=r"REPRO_MAX_BATCH='eight'.*integer >= 1"):
        EngineConfig.from_env()
    monkeypatch.setenv("REPRO_MAX_BATCH", "0")  # parses, below the floor
    with pytest.raises(ValueError,
                       match=r"REPRO_MAX_BATCH='0'.*integer >= 1"):
        EngineConfig.from_env()
    monkeypatch.delenv("REPRO_MAX_BATCH")
    monkeypatch.setenv("REPRO_NUM_BLOCKS", "1")  # block 0 is scratch
    with pytest.raises(ValueError,
                       match=r"REPRO_NUM_BLOCKS='1'.*integer >= 2"):
        EngineConfig.from_env()
    monkeypatch.setenv("REPRO_NUM_BLOCKS", "-3")
    with pytest.raises(ValueError, match="REPRO_NUM_BLOCKS"):
        EngineConfig.from_env()
    monkeypatch.delenv("REPRO_NUM_BLOCKS")
    monkeypatch.setenv("REPRO_SEED", "0")  # seed floor is 0, not 1
    assert EngineConfig.from_env().seed == 0
    monkeypatch.delenv("REPRO_SEED")


def test_token_budget_semantics():
    assert TokenBudget(None).can(10**9)          # unlimited
    b = TokenBudget(5)
    assert b.can(5) and not b.can(6)
    assert b.can(6, force=True)                  # first-prefill escape hatch
    b.spend(5)
    assert not b.can(1)


def test_drr_weighted_split_two_to_one():
    """2:1 weights -> 2:1 token split when both tenants stay backlogged
    (the weighted-fairness acceptance criterion, engine-free)."""
    drr = DeficitRoundRobin(weights={"a": 2.0, "b": 1.0})
    drr.reset()
    got = {"a": 0, "b": 0}
    for _ in range(20):
        drr.replenish(["a", "b"], 30)
        budget = WeightedTokenBudget(30, drr)
        progressed = True
        while progressed:
            progressed = False
            for tenant in ("a", "b"):
                if budget.can(1, tenant=tenant):
                    budget.spend(1, tenant=tenant)
                    got[tenant] += 1
                    progressed = True
    assert got["a"] + got["b"] == 20 * 30
    assert got["a"] == pytest.approx(2 * got["b"], rel=0.05)


def test_weighted_budget_requires_both_pool_and_deficit():
    drr = DeficitRoundRobin(weights={"a": 1.0, "b": 1.0})
    drr.reset()
    drr.replenish(["a", "b"], 10)                # 5 deficit each
    budget = WeightedTokenBudget(10, drr)
    # force admits past the deficit only while nothing has been spent
    # (the first-prefill escape hatch; it drives the deficit negative)
    assert budget.can(10**6, tenant="a", force=True)
    assert budget.can(5, tenant="a") and not budget.can(6, tenant="a")
    budget.spend(5, tenant="a")
    assert not budget.can(6, tenant="b")         # global pool: 5 left
    assert budget.can(5, tenant="b")
    assert not budget.can(10**6, tenant="a", force=True)
    assert drr.balance("a") == 0.0


# ---------------------------------------------------------------------------
# the reduction pin: single tenant == FIFO, fixed RNG
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _pinned_engines():
    """One FIFO engine and one TenantScheduler engine, identical seeds:
    reused across property examples (jit caches are per-engine)."""
    cfg, params, _ = _setup()
    ecfg = _ecfg(chunk=4, budget=16, temperature=0.8, max_new=10)
    fifo = Engine(params, cfg, ecfg, make_policy("sc"),
                  scheduler=FIFOPolicy())
    tenant = Engine(params, cfg, ecfg, make_policy("sc"),
                    scheduler=TenantScheduler(weights={}))
    return fifo, tenant


@settings(max_examples=5, deadline=None)
@given(st.integers(1, 3), st.integers(1, 3),
       st.lists(st.integers(0, 2), min_size=1, max_size=3))
def test_tenant_scheduler_reduces_to_fifo(n_reqs, n_traces, order):
    """Property: for single-tenant workloads the TenantScheduler must be
    operation-identical to the FIFO policy — same tokens, same trace
    scores, same prune counts — under stochastic sampling with the same
    engine seed (i.e. the schedulers consume the RNG stream in the same
    order). This pins the redesign contract: the event core with default
    policies reproduces the old tick loop exactly."""
    cfg, params, prompts = _setup()
    fifo, tenant = _pinned_engines()
    chosen = [prompts[i] for i in order][:n_reqs] or [prompts[0]]
    snaps = []
    for eng in (fifo, tenant):
        eng._rng = jax.random.PRNGKey(eng.ecfg.seed)   # fixed RNG
        results = eng.serve_batch(_reqs(chosen, n=n_traces))
        assert eng.pool_drained()
        eng.block_mgr.check_invariants()
        snaps.append(_snapshot(results))
    assert snaps[0] == snaps[1]


def test_reduction_holds_with_staggered_arrivals(setup):
    """Greedy + roomy pool: the reduction also holds for online arrivals
    (timing jitter moves tick boundaries, never the argmax tokens)."""
    cfg, params, prompts = setup
    snaps = []
    for sched in (None, TenantScheduler(weights={})):
        eng = Engine(params, cfg, _ecfg(chunk=4), make_policy("sc"),
                     scheduler=sched)
        results = eng.serve_batch(
            _reqs(prompts, n=2, arrivals=[0.0, 0.05, 0.1]))
        assert eng.pool_drained()
        snaps.append({rid: [t for t, _, _ in traces]
                      for rid, (traces, _) in _snapshot(results).items()})
    assert snaps[0] == snaps[1]


# ---------------------------------------------------------------------------
# multi-tenant behaviour
# ---------------------------------------------------------------------------

def test_priority_admission_order(setup):
    """With one decode slot pair, the priority-1 tenant's request jumps
    the queue even though it was submitted second."""
    cfg, params, prompts = setup
    eng = Engine(params, cfg, _ecfg(batch=2), make_policy("sc"),
                 scheduler=TenantScheduler(
                     weights={"premium": 3.0, "batch": 1.0}))
    reqs = [
        Request(request_id=0, prompt_tokens=prompts[0], n_traces=2,
                policy=make_policy("sc"), tenant="batch", priority=0),
        Request(request_id=1, prompt_tokens=prompts[1], n_traces=2,
                policy=make_policy("sc"), tenant="premium", priority=1),
    ]
    results = eng.serve_batch(reqs)
    assert eng.pool_drained()
    m_batch, m_premium = results[0].metrics, results[1].metrics
    assert m_premium.first_token_s <= m_batch.first_token_s
    assert m_premium.tenant == "premium" and m_premium.priority == 1
    for r in results:
        assert all(t.status == TraceStatus.FINISHED for t in r.traces)


def test_tenant_pressure_published_to_policies(setup):
    """Under a TenantScheduler, AdmissionPressure carries the per-tenant
    demand/deficit views (None under FIFO)."""
    cfg, params, prompts = setup
    seen = []

    class Spy(type(make_policy("sc"))):
        def observe_pressure(self, pressure):
            super().observe_pressure(pressure)
            seen.append(pressure)

    eng = Engine(params, cfg, _ecfg(batch=2, budget=16),
                 make_policy("sc"),
                 scheduler=TenantScheduler(weights={"t0": 1.0}))
    eng.serve_batch([Request(request_id=0, prompt_tokens=prompts[0],
                             n_traces=4, policy=Spy(), tenant="t0")])
    assert seen
    assert any(p.demand_by_tenant is not None for p in seen)
    assert any("t0" in (p.deficit_by_tenant or {}) for p in seen)


def test_slo_degrades_trace_fanout(setup):
    """An unmeetable TTFT objective degrades the request's fan-out to
    min_traces at admission (quality-for-latency, the paper's dial)."""
    cfg, params, prompts = setup
    eng = Engine(params, cfg, _ecfg(), make_policy("sc"),
                 scheduler=TenantScheduler(weights={}))
    res = eng.serve_batch(_reqs(prompts[:1], n=4,
                                slo=SLO(ttft_s=0.0, min_traces=1)))[0]
    assert eng.pool_drained()
    assert res.metrics.degraded_traces == 3
    assert sum(t.status == TraceStatus.FINISHED for t in res.traces) == 1
    assert sum(t.status == TraceStatus.PRUNED for t in res.traces) == 3
    survivor = next(t for t in res.traces
                    if t.status == TraceStatus.FINISHED)
    assert survivor.num_tokens > 0


def test_slo_meetable_keeps_all_traces(setup):
    """A generous objective must not degrade anything."""
    cfg, params, prompts = setup
    eng = Engine(params, cfg, _ecfg(), make_policy("sc"),
                 scheduler=TenantScheduler(weights={}))
    res = eng.serve_batch(_reqs(prompts[:1], n=4,
                                slo=SLO(ttft_s=60.0)))[0]
    assert res.metrics.degraded_traces == 0
    assert all(t.status == TraceStatus.FINISHED for t in res.traces)
    assert res.metrics.ttft_attained is True


def test_slo_shed_rejects_request(setup):
    """shed=True + a hopeless projection rejects the request outright:
    every trace is pruned at admission, answer None."""
    cfg, params, prompts = setup
    eng = Engine(params, cfg, _ecfg(), make_policy("sc"),
                 scheduler=TenantScheduler(weights={}))
    res = eng.serve_batch(_reqs(
        prompts[:1], n=4,
        slo=SLO(ttft_s=1e-9, shed=True, shed_factor=1.0)))[0]
    assert eng.pool_drained()
    assert res.answer is None
    assert all(t.status == TraceStatus.PRUNED for t in res.traces)
    assert res.metrics.degraded_traces == 4
    assert res.metrics.ttft_attained is False  # shed counts as a miss


def test_slo_ignored_under_fifo(setup):
    """The default FIFO policy never degrades: SLOs are reported, not
    enforced (back-compat for existing callers)."""
    cfg, params, prompts = setup
    eng = Engine(params, cfg, _ecfg(), make_policy("sc"))
    res = eng.serve_batch(_reqs(prompts[:1], n=4,
                                slo=SLO(ttft_s=0.0)))[0]
    assert res.metrics.degraded_traces == 0
    assert all(t.status == TraceStatus.FINISHED for t in res.traces)


# ---------------------------------------------------------------------------
# per-request overrides
# ---------------------------------------------------------------------------

def test_per_request_max_new_tokens_override(setup):
    """A request-level max_new_tokens caps only that request; greedy
    sampling makes the capped output a prefix of the uncapped one."""
    cfg, params, prompts = setup
    eng = Engine(params, cfg, _ecfg(max_new=12), make_policy("sc"))
    reqs = [Request(request_id=0, prompt_tokens=prompts[0], n_traces=1,
                    policy=make_policy("sc"), max_new_tokens=4),
            Request(request_id=1, prompt_tokens=prompts[0], n_traces=1,
                    policy=make_policy("sc"))]
    results = eng.serve_batch(reqs)
    assert eng.pool_drained()
    short = results[0].traces[0].output_tokens
    long = results[1].traces[0].output_tokens
    assert len(short) <= 4 and len(long) <= 12
    assert long[:len(short)] == short


def test_per_request_sampling_override_lanewise(setup):
    """A mixed batch (one request overrides SamplingParams) runs the
    lane-wise sampling path; a greedy-override lane must produce exactly
    the scalar greedy engine's tokens (argmax ignores the RNG lane)."""
    cfg, params, prompts = setup
    greedy = SamplingParams(temperature=0.0, top_k=0, top_p=1.0,
                            max_new_tokens=10)
    # reference: engine whose global sampling is greedy (scalar path)
    ref = Engine(params, cfg, _ecfg(max_new=10), make_policy("sc"))
    want = [t.output_tokens
            for t in ref.serve_batch(_reqs(prompts[:1], n=1))[0].traces]

    # mixed batch: request 0 overrides to greedy, request 1 inherits the
    # stochastic engine default -> lane-wise decode for the whole batch
    eng = Engine(params, cfg, _ecfg(max_new=10, temperature=0.8),
                 make_policy("sc"))
    reqs = [Request(request_id=0, prompt_tokens=prompts[0], n_traces=1,
                    policy=make_policy("sc"), sampling=greedy),
            Request(request_id=1, prompt_tokens=prompts[1], n_traces=2,
                    policy=make_policy("sc"))]
    results = eng.serve_batch(reqs)
    assert eng.pool_drained()
    eng.block_mgr.check_invariants()
    assert [t.output_tokens for t in results[0].traces] == want
    for r in results:
        assert all(t.status == TraceStatus.FINISHED for t in r.traces)


def test_uniform_override_matches_engine_default(setup):
    """Every request overriding to the engine's own params is NOT a
    mixed batch: outputs are identical to no-override submission."""
    cfg, params, prompts = setup
    outs = []
    for extra in ({}, {"sampling": SamplingParams(
            temperature=0.0, top_k=0, top_p=1.0, max_new_tokens=12)}):
        eng = Engine(params, cfg, _ecfg(max_new=12), make_policy("sc"))
        results = eng.serve_batch(_reqs(prompts, n=2, **extra))
        outs.append({r.request_id: [t.output_tokens for t in r.traces]
                     for r in results})
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# event stream
# ---------------------------------------------------------------------------

class _Recorder(FIFOPolicy):
    """A FIFO policy that records every event the core dispatches."""

    def __init__(self):
        self.log = []

    def on_event(self, event):
        self.log.append(event)


def test_event_stream_contract(setup):
    """A policy's ``on_event`` sees the dispatched event stream:
    arrivals precede everything for their request, one Completion per
    request, timestamps non-decreasing."""
    cfg, params, prompts = setup
    rec = _Recorder()
    eng = Engine(params, cfg, _ecfg(chunk=4, budget=16),
                 make_policy("sc"), scheduler=rec)
    results = eng.serve_batch(_reqs(prompts, n=2))
    log = rec.log
    assert log and isinstance(log[0], Arrival)
    times = [ev.t for ev in log]
    assert times == sorted(times)
    completions = [ev for ev in log if isinstance(ev, Completion)]
    assert sorted(ev.request_id for ev in completions) == [0, 1, 2]
    assert any(isinstance(ev, BurstDone) for ev in log)
    assert any(isinstance(ev, BudgetReplenish) for ev in log)
    arrival_at = {ev.request_id: i for i, ev in enumerate(log)
                  if isinstance(ev, Arrival)}
    for i, ev in enumerate(log):
        if isinstance(ev, Completion):
            assert arrival_at[ev.request_id] < i
    assert len(results) == 3
