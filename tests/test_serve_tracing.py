"""The program's own measurement on the tiny cell, on the CPU: the
scheduler's ``sched.*`` spans in a profiler trace, nested as the call
graph nests; ``Engine.counters`` against the benchmark's Observer over
one window under pool pressure; a compile charged to the span it fell
in; the span reduction on a hand-made trace; and the program names that
the trace reduction keys on."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from bench import harness, spans, trace_reduce, traffic
from bench.observer import Observer
from bench.tests import tiny
from bench.tests.test_trace_reduce import HAND
from bench.weights import make_weights
from repro.core.pruning import make_policy
from repro.data.tokenizer import get_tokenizer
from repro.serving import Engine, EngineConfig, Request, SamplingParams

SEED = 3_000_000_019          # above 2**31, as the benchmark's seeds may be
SPANS = ("sched.round", "sched.admit", "sched.prefill", "sched.first_tokens",
         "sched.memory_full", "sched.assure", "sched.decode", "sched.sync",
         "sched.fold")


@functools.lru_cache(maxsize=1)
def _model():
    spec = tiny.files()
    cfg = harness.model_config(spec["config"])
    m = spec["config"]["model"]
    tok = get_tokenizer()
    params, scorer = jax.jit(make_weights, static_argnums=(0, 2, 3))(
        tuple(sorted(m.items())), jax.random.PRNGKey(7), tok.step_id, 2.5)
    return spec, cfg, params, scorer


def engine(scheduler=None, sampling=None, **overrides):
    """The tiny cell's engine (its mix's settings and pool)."""
    spec, cfg, params, scorer = _model()
    mix = spec["mix"]
    eng_cfg = dict(mix["engine"], **overrides)
    num_blocks = eng_cfg.pop("num_blocks", spec["config"]["num_blocks"])
    samp = SamplingParams(**(sampling or mix["sampling"]),
                          max_new_tokens=eng_cfg["capacity"])
    ecfg = EngineConfig(**eng_cfg, num_blocks=num_blocks,
                        max_new_tokens=eng_cfg["capacity"], sampling=samp,
                        seed=SEED & 0x7FFFFFFF, faults=None)
    return Engine(params, cfg, ecfg, make_policy(mix["policy"]),
                  scorer_params=scorer, scheduler=scheduler)


def requests(n=None, max_new=None, n_traces=None, wrap=lambda p: p):
    spec = _model()[0]
    mix = spec["mix"]
    items = traffic.generate(mix, SEED, tiny.TINY_MODEL["vocab_size"])
    return [Request(request_id=it.index, prompt_tokens=it.prompt,
                    n_traces=n_traces or mix["n_traces"],
                    policy=wrap(make_policy(mix["policy"])),
                    max_new_tokens=max_new or it.max_new_tokens)
            for it in items[:n]]


# ---------------------------------------------------------------------------
# (a) spans
# ---------------------------------------------------------------------------

def test_spans_nest_in_a_profiler_trace(tmp_path):
    eng = engine(num_blocks=24)
    reqs = requests(n=4, max_new=40)
    assert {len(r.prompt_tokens) > 32 for r in reqs} == {True, False}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.serve_batch(reqs)
    finally:
        jax.profiler.stop_trace()
    got = spans.load_spans(trace_reduce.find_xplane(str(tmp_path)))
    names = [s[0] for s in got]
    assert set(SPANS) <= set(names), sorted(set(names))
    c = eng.counters
    assert c.memory_full > 0 and c.prefill_chunks > 0
    # admissions refused blocks: those rounds' empty lanes are memory's
    assert c.memory_waits > 0 and c.lanes_empty_memory > 0

    def parents(sp):
        return [p[0] for p in got
                if p is not sp and p[1] <= sp[1] and sp[2] <= p[2]]

    for sp in got:
        name, _, _, stats = sp
        up = parents(sp)
        if name == "sched.round":
            assert "tick" in stats and not up
        elif name == "sched.fold":
            assert not up           # a BurstDone of its own
        elif name in ("sched.admit", "sched.assure", "sched.decode",
                      "sched.sync"):
            assert up == ["sched.round"], (name, up)
        elif name in ("sched.prefill", "sched.first_tokens"):
            assert set(up) == {"sched.round", "sched.admit"}, (name, up)
        elif name == "sched.memory_full":
            assert "sched.round" in up and (
                {"sched.admit", "sched.assure"} & set(up)), up
        if name in ("sched.prefill", "sched.memory_full"):
            assert stats["request_id"] in range(4)
        if name == "sched.prefill":
            assert stats["tokens"] > 0
        if name == "sched.decode":
            assert stats["lanes"] > 0 and stats["horizon"] >= 1
        if name == "sched.first_tokens":
            assert stats["wave"] >= 1
    # one sync per decode call, one fold per sync, no span per lane
    assert names.count("sched.sync") == names.count("sched.decode") \
        == names.count("sched.fold") == eng.counters.rounds
    assert len(got) < 12 * eng.counters.rounds + 2 * (
        eng.counters.memory_full + eng.counters.prefill_chunks)


# ---------------------------------------------------------------------------
# (b) counters against the Observer
# ---------------------------------------------------------------------------

def test_counters_agree_with_the_observer():
    step_id = get_tokenizer().step_id
    marks = {}
    obs = Observer(1.5, step_id,
                   on_open=lambda: marks.setdefault(
                       "open", eng.counters.snapshot()),
                   on_close=lambda: marks.setdefault(
                       "close", eng.counters.snapshot()))
    eng = engine(scheduler=obs, num_blocks=20)
    eng.serve_batch(requests(wrap=obs.watch))
    obs.finish()
    assert obs.closed
    d = {k: v - marks["open"][k] for k, v in marks["close"].items()
         if isinstance(v, (int, float))}
    B = eng.ecfg.max_batch
    assert obs.memory_full > 0 and obs.bursts
    # the Observer sees the policy's decisions: those past an eviction
    assert d["memory_full"] - d["evictions"] == obs.memory_full
    assert d["memory_full"] == sum(d[k] for k in (
        "evictions", "prunes", "truncations", "reclaims", "preemptions",
        "memory_waits"))
    assert d["rounds"] == len(obs.bursts)
    assert d["lane_rounds"] == B * d["rounds"]
    assert d["lanes_running"] == sum(b.n_lanes for b in obs.bursts)
    assert d["lanes_running"] + d["lanes_empty_memory"] + \
        d["lanes_empty_prefill"] + d["lanes_empty_other"] == d["lane_rounds"]
    assert d["decode_tokens"] == obs.window_tokens()
    occupancy = harness.load_reader("lane_occupancy")(
        harness.Context(model=None, engine={"max_batch": B}, peaks=None,
                        seconds=obs.seconds, setup_s=0.0, obs=obs,
                        weight_bytes=0, page=16, trace=None))
    assert occupancy == pytest.approx(
        100.0 * d["lanes_running"] / d["lane_rounds"])
    snap = eng.counters.snapshot()
    assert snap["lanes_running"] + snap["lanes_empty_memory"] + \
        snap["lanes_empty_prefill"] + snap["lanes_empty_other"] == \
        snap["lane_rounds"]


# ---------------------------------------------------------------------------
# (c) compiles by phase
# ---------------------------------------------------------------------------

def test_a_new_wave_size_compiles_under_first_tokens():
    # sampling parameters no other test uses: the first-token sampler of
    # this wave is a program the process has not compiled yet
    eng = engine(max_batch=6, num_blocks=64,
                 sampling={"temperature": 0.55, "top_k": 11, "top_p": 0.9})
    eng.serve_batch(requests(n=1, max_new=4, n_traces=5))
    c = eng.counters
    count, secs = c.compiles_by_phase["sched.first_tokens"]
    assert count >= 1 and secs > 0
    assert c.compiles == sum(v[0] for v in c.compiles_by_phase.values())
    assert c.compile_s == pytest.approx(
        sum(v[1] for v in c.compiles_by_phase.values()))


@pytest.mark.parametrize("mode, path", [
    ("top_k", "select"), ("top_p_only", "sort"), ("lanewise", "sort")])
def test_sampled_rows_count_by_filter(mode, path):
    """Every sampled row (a decode token or a first token) counts under
    the filter its sampler ran: the mix's top-k sampling selects; top-p
    alone, and a batch that samples lane-wise because one request
    overrides the engine's sampling, sort."""
    sampling = ({"temperature": 0.6, "top_k": 0, "top_p": 0.95}
                if mode == "top_p_only" else None)
    eng = engine(num_blocks=64, sampling=sampling)
    reqs = requests(n=3, max_new=12)
    if mode == "lanewise":
        reqs[0] = dataclasses.replace(reqs[0], sampling=SamplingParams(
            temperature=0.7, top_k=5, top_p=0.9, max_new_tokens=12))
    results = eng.serve_batch(reqs)
    c = eng.counters
    traces = [t for r in results for t in r.traces]
    rows = sum(len(t.output_tokens) for t in traces)
    first_rows = sum(bool(t.output_tokens) for t in traces)
    assert c.preemptions == 0 and c.decode_tokens > 0
    assert getattr(c, f"sampled_{path}") == rows \
        == c.decode_tokens + first_rows
    assert c.sampled_select + c.sampled_sort == rows


# ---------------------------------------------------------------------------
# (d) the span reduction on a hand-made trace
# ---------------------------------------------------------------------------

def _ev(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {int(start_us * 1e6)} "
            f"duration_ps: {int(dur_us * 1e6)} }}")


# the hand trace of test_trace_reduce (device busy 1-9.5 and 16-20 us of
# its 0-30 us extent) with a serve loop on a host line of its own: round
# A 0.5-17 us holds admit 0.5-2, decode 2-3 and sync 3-15.5; fold 17-19;
# round B 19-30 holds admit 19-21 (with a memory-full 20-20.5) and no
# decode. Idle 0-1 us: 0.5 outside every span, 0.5 in A's admit; 9.5-16:
# 6 in the sync, 0.5 in round A; 20-30: 0.5 each in the memory-full and
# B's admit, 9 in round B alone.
SERVE = """
planes {
  id: 3
  name: "/host:CPU"
  lines {
    id: 2
    name: "serve"
    timestamp_ns: 0
    %s
  }
  event_metadata { key: 11 value { id: 11 name: "sched.round" } }
  event_metadata { key: 12 value { id: 12 name: "sched.admit" } }
  event_metadata { key: 13 value { id: 13 name: "sched.decode" } }
  event_metadata { key: 14 value { id: 14 name: "sched.sync" } }
  event_metadata { key: 15 value { id: 15 name: "sched.fold" } }
  event_metadata { key: 16 value { id: 16 name: "sched.memory_full" } }
}
""" % "\n    ".join([_ev(11, 0.5, 16.5), _ev(12, 0.5, 1.5), _ev(13, 2, 1),
                    _ev(14, 3, 12.5), _ev(15, 17, 2), _ev(11, 19, 11),
                    _ev(12, 19, 2), _ev(16, 20, 0.5)])


def test_reduce_spans_on_a_hand_trace():
    plain = trace_reduce.load(ProfileData.from_text_proto(HAND))
    pd = ProfileData.from_text_proto(HAND + SERVE)
    loaded = trace_reduce.load(pd)
    got = spans.reduce_spans(loaded, spans.load_spans(pd))
    # round A: 16.5 - 12.5 (its sync) + 2 (its fold) us; round B: 11 us
    assert got["rounds"] == 2
    assert got["round_host_s"] == pytest.approx((6e-6 + 11e-6) / 2)
    assert got["extent_s"] == pytest.approx(30e-6)
    idle = got["idle_by_phase"]
    assert idle == {"sched.sync": pytest.approx(6e-6),
                    "sched.round": pytest.approx(9.5e-6),
                    "sched.admit": pytest.approx(1e-6),
                    "sched.memory_full": pytest.approx(0.5e-6),
                    spans.OUTSIDE: pytest.approx(0.5e-6)}
    red = trace_reduce.reduce(loaded, 30e-6)
    assert sum(idle.values()) == pytest.approx(30e-6 - red["busy_s"])
    # the spans leave the device reduction as it was
    assert red["breakdown"] == trace_reduce.reduce(plain, 30e-6)["breakdown"]
    assert red["busy_s"] == trace_reduce.reduce(plain, 30e-6)["busy_s"]


def test_reduce_spans_without_spans_is_none():
    pd = ProfileData.from_text_proto(HAND)
    assert spans.load_spans(pd) == []
    assert spans.reduce_spans(trace_reduce.load(pd), []) is None


def test_a_round_whose_fold_left_the_trace_is_not_counted():
    # round 0-10 with a decode and a sync, its fold past the trace's end
    got = spans.round_host_times([
        ("sched.round", 0, 10, {}), ("sched.decode", 1, 2, {}),
        ("sched.sync", 2, 9, {}), ("sched.round", 12, 14, {})])
    assert got == [pytest.approx(2e-9)]


# ---------------------------------------------------------------------------
# the program names the trace reduction keys on
# ---------------------------------------------------------------------------

def test_step_program_names():
    """The decode and chunked-prefill steps lower to the module names
    that ``bench/trace_reduce.py`` and the decode readers match; a rename
    fails here instead of making a metric absent."""
    from bench.metrics import decode_hbm_share, paged_decode_roofline
    eng = engine()
    B, bps = eng.ecfg.max_batch, eng.blocks_per_seq
    cache = eng._init_cache()
    i32 = lambda *s: jnp.zeros(s, jnp.int32)          # noqa: E731
    decode = eng.decode_fn(eng.ecfg.decode_horizon).lower(
        eng.params, cache, i32(B), i32(B), i32(B), i32(B, bps),
        eng._rng, eng.scorer_params)
    C = eng.ecfg.prefill_chunk_size
    chunk = eng._chunk_prefill.lower(
        eng.params, cache, i32(1, C), i32(1, C), jnp.ones((1, C), bool),
        i32(1, bps))
    names = {lw.as_text().split("module @", 1)[1].split(" ", 1)[0]
             for lw in (decode, chunk)}
    assert names == {decode_hbm_share.PROGRAM, "jit_chunk_prefill"}
    assert paged_decode_roofline.KEY.split("/")[0] in names
