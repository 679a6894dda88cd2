"""The harness: one run of one cell of ``BENCHMARK.json``.

Everything that belongs to a configuration, a traffic mix, a metric or a
cell's limits is found by name in its own file:

    bench/configs/<config>.json   sizes, engine pool, step-row scale, and
                                  the architecture family it belongs to
    bench/traffic/<traffic>.json  the mix and the engine settings it pins
    bench/metrics/<metric>.py     read(ctx) -> number or None
    bench/limits/<cell>.json      the limits of the correctness check
    bench/families/<family>/      what is particular to an architecture:
        weights.py    make_weights(m, key, step_id, step_row_scale)
                      -> (params, scorer), jitted with all but ``key``
                      static
        reference.py  forward(params, scorer, tokens, targets, *, m,
                      low=False) -> {"lse", "tgt", "score"}, and BLOCK,
                      the length ``tokens`` is a multiple of
        cost.py       the FLOP and byte counts the metric readers take
                      from ``ctx.cost``, and KERNELS, the kernel op names
                      the trace reduction times per program

A run: check the device, make the configuration's weights on the device,
build the engine through its public API, warm the shapes the cell's
traffic uses, drive the traffic through ``Engine.serve_batch`` with the
``Observer`` as its scheduling policy, measure the window, free the
engine, check the served tokens against the plain reference, and print
one JSON line.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import types
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
# a --trace 1 run traces the first TRACE_S seconds of its window: some
# tens of decode calls, a trace small enough to reduce within the run
TRACE_S = 4.0
WARM_THREADS = 8


class Refused(Exception):
    """The run cannot measure anything here (no chip, a REPRO_* variable,
    a missing file); it exits non-zero and prints no result."""


# ---------------------------------------------------------------------------
# files
# ---------------------------------------------------------------------------

def load_json(path: Path) -> dict:
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_cell(name: str) -> dict:
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[cell["config"]]["file"])
    mix = load_json(ROOT / "bench" / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(ROOT / "bench" / "limits" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    layer = [m for m in bench["per_layer"]
             if "workloads" not in m or name in m["workloads"]]
    return {"cell": cell, "config": config, "mix": mix, "limits": limits,
            "end_to_end": e2e, "per_layer": layer}


def _load_module(name: str, path: Path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    path = ROOT / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise Refused(f"no reader bench/metrics/{metric}.py")
    return _load_module(f"bench_metric_{metric}", path).read


FAMILY_MODULES = ("weights", "reference", "cost")


def load_family(config: dict) -> types.SimpleNamespace:
    """The three modules of the configuration's architecture family,
    ``bench/families/<family>/``, as ``.weights``, ``.reference`` and
    ``.cost``."""
    if "family" not in config:
        raise Refused(f"configuration {config['name']!r} names no family: "
                      f"its file needs \"family\", a directory under "
                      f"bench/families/")
    rel = Path("bench") / "families" / config["family"]
    if not (ROOT / rel).is_dir():
        raise Refused(f"no family directory {rel}/")
    mods = {}
    for part in FAMILY_MODULES:
        path = ROOT / rel / f"{part}.py"
        if not path.is_file():
            raise Refused(f"missing {rel / part}.py")
        mods[part] = _load_module(f"bench_family_{config['family']}_{part}",
                                  path)
    return types.SimpleNamespace(**mods)


# ---------------------------------------------------------------------------
# device and set-up
# ---------------------------------------------------------------------------

def check_environment() -> None:
    bad = sorted(k for k in os.environ if k.startswith("REPRO_"))
    if bad:
        raise Refused(f"{', '.join(bad)} set: these change the engine's "
                      f"defaults under the benchmark")


def check_devices(chips: int) -> dict:
    import jax
    from bench.peaks import peaks_for
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU (found {devices[0].platform})")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, found {len(devices)}")
    try:
        return peaks_for(devices[0].device_kind)
    except KeyError as e:
        raise Refused(str(e)) from None


class CompileCounter:
    """Backend compilations and persistent-cache hits, through JAX's
    monitoring events (listeners cannot be removed, so one per process)."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(name, _secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)


def model_config(config: dict):
    """The program's ModelConfig for the file: its own ``full_config`` with
    the file's departures applied, checked against every size the file
    states."""
    mod = importlib.import_module(config["program_config"])
    cfg = dataclasses.replace(mod.full_config(),
                              **config.get("program_overrides", {}))
    for k, v in config["model"].items():
        if getattr(cfg, k) != v:
            raise Refused(f"{config['name']}: the program's {k}="
                          f"{getattr(cfg, k)!r}, the file states {v!r}")
    return cfg


def derive_seeds(seed: int, n: int) -> list:
    return [int(x) for x in
            np.random.SeedSequence(seed).generate_state(n, np.uint32)]


def warm_host_shapes(eng, mix: dict, samp) -> None:
    """Compile, before the window, the small programs the scheduler runs
    eagerly with shapes that vary with the traffic: the first-token
    sampling of an admission wave, and the concatenation of the wave's
    prefill logits before it, one of each per wave size. A wave holds
    the traces of one request, or of several whose prefills finished in
    the same round, and under a full pool traces are admitted one by one
    as memory frees: every size up to four requests' traces (waves of
    two or three requests and part of another, 36 to 56 traces, come in
    a window, and a size not warmed compiles there, about 8 s on a TPU
    v5e), and every multiple of one request's traces up to the decode
    slots. (All 128 sizes took over 300 s to compile on a TPU v5e,
    beyond a run's time.) A TPU compile of even a small program takes
    seconds, so they compile side by side in threads."""
    import jax
    import jax.numpy as jnp
    from repro.serving.sampling import sample_tokens
    vp = eng.params["embed"].shape[0]
    row = jnp.zeros((1, vp), jnp.bfloat16)
    key = jax.random.PRNGKey(0)

    def wave(m):
        cat = jnp.concatenate([row] * m, axis=0)
        return sample_tokens(key, cat, temperature=samp.temperature,
                             top_k=samp.top_k, top_p=samp.top_p)

    n, top = mix["n_traces"], mix["engine"]["max_batch"]
    sizes = sorted(set(range(1, 4 * n + 1)) | set(range(n, top + 1, n)))
    with ThreadPoolExecutor(WARM_THREADS) as pool:
        outs = list(pool.map(wave, sizes))
    jax.block_until_ready(outs)


def warm_up(eng, items, mix: dict, samp, phases: dict,
            t_process: float) -> None:
    """Compile every shape the window will run, from the compile cache
    after a cell's first run:

    - the first-token samplers of every wave size (``warm_host_shapes``);
    - a prompt no longer than a prefill chunk takes the engine's one-shot
      prefill, which compiles once per prompt length: one request of each
      such length the traffic holds is served, and one a token longer
      than a chunk for the chunked path, on their own random tokens,
      dropped from the prefix cache after. Their decode steps compile
      the decode program the window runs.
    """
    from bench import traffic
    from repro.core.pruning import make_policy
    from repro.serving import Request
    warm_host_shapes(eng, mix, samp)
    phases["warm_waves_s"] = time.perf_counter() - t_process
    chunk = mix["engine"]["prefill_chunk_size"]
    lengths = sorted({len(it.prompt) for it in items
                      if len(it.prompt) <= chunk})
    if any(len(it.prompt) > chunk for it in items):
        lengths.append(chunk + 1)      # the chunked-prefill program
    rng = np.random.default_rng(len(lengths))
    vocab = eng.cfg.vocab_size
    obs, eng.scheduler = eng.scheduler, None
    try:
        eng.serve_batch([
            Request(request_id=-1 - i, n_traces=1,
                    prompt_tokens=rng.integers(
                        traffic.FIRST_PLAIN_ID, vocab, n).tolist(),
                    policy=make_policy(mix["policy"]), max_new_tokens=2)
            for i, n in enumerate(lengths)])
        if eng.prefix_cache is not None:
            eng.prefix_cache.clear()
    finally:
        eng.scheduler = obs
    phases["warm_serve_s"] = time.perf_counter() - t_process


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a metric reader reads."""
    model: dict
    engine: dict
    peaks: dict
    seconds: float
    setup_s: float
    obs: object
    weight_bytes: int
    page: int
    trace: Optional[dict]
    cost: Optional[types.ModuleType] = None   # the family's cost module


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, require_chip: bool = True, control: bool = False,
        files: Optional[dict] = None) -> dict:
    """One run; returns the result line's object. ``files`` (tests) gives
    the cell's files directly instead of reading them by name, and may
    give the family's modules under ``"family"`` (``load_family``'s
    namespace) in place of the configuration's own. With
    ``control`` the correctness check's control (the reference below the
    stated precision in the engine's place) is judged as well, on the same
    served tokens and limits, and its verdict printed on standard error."""
    check_environment()
    spec = files if files is not None else load_cell(workload)
    cell, config, mix = spec["cell"], spec["config"], spec["mix"]
    family = spec["family"] if "family" in spec else load_family(config)
    peaks = check_devices(cell["chips"]) if require_chip else {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()

    from bench import check, traffic
    from bench.observer import Observer
    from repro.core.pruning import make_policy
    from repro.data.tokenizer import get_tokenizer
    from repro.serving import Engine, EngineConfig, Request, SamplingParams

    phases = {"start_s": time.perf_counter() - t_process}
    cfg = model_config(config)
    m = config["model"]
    tok = get_tokenizer()
    # the weights stand for the configuration's checkpoint: one fixed set,
    # made from the file's ``weights_seed``, so that every seed serves the
    # same model and does the same work. The seed draws the traffic (its
    # prompts and their order), the engine's sampling and the check's sample.
    s_engine, s_traffic, s_check = derive_seeds(seed, 3)
    params, scorer = jax.jit(family.weights.make_weights,
                             static_argnums=(0, 2, 3))(
        tuple(sorted(m.items())), jax.random.PRNGKey(config["weights_seed"]),
        tok.step_id, config["step_row_scale"])
    jax.block_until_ready((params, scorer))
    phases["weights_s"] = time.perf_counter() - t_process

    eng_cfg = mix["engine"]
    samp = SamplingParams(**mix["sampling"],
                          max_new_tokens=eng_cfg["capacity"])
    # the mix's engine settings are EngineConfig fields; what it leaves
    # out stays at the engine's default
    ecfg = EngineConfig(**eng_cfg, num_blocks=config["num_blocks"],
                        max_new_tokens=eng_cfg["capacity"], sampling=samp,
                        seed=s_engine & 0x7FFFFFFF, faults=None)

    prof_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    prof = {}

    def on_open():
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(prof_dir, profiler_options=opts)
        # a compile inside the window names its program on standard error
        jax.config.update("jax_log_compiles", True)
        prof["c0"] = counter.compiles
        prof["hits0"] = counter.cache_hits
        prof["window_start"] = time.perf_counter()

    def on_trace_end():
        prof["trace_end"] = time.perf_counter()
        jax.profiler.stop_trace()

    def on_close():
        prof["c1"] = counter.compiles
        jax.config.update("jax_log_compiles", False)

    obs = Observer(seconds, tok.step_id, on_open=on_open, on_close=on_close,
                   trace_s=min(TRACE_S, seconds) if trace else None,
                   on_trace_end=on_trace_end)
    eng = Engine(params, cfg, ecfg, make_policy(mix["policy"]),
                 scorer_params=scorer, scheduler=obs)
    if require_chip and not (eng.use_kernel and eng.fused_scorer):
        raise Refused("the engine did not take the kernel path")
    phases["engine_s"] = time.perf_counter() - t_process

    items = traffic.generate(mix, s_traffic, m["vocab_size"])
    warm_up(eng, items, mix, samp, phases, t_process)
    reqs = []
    for it in items:
        reqs.append(Request(
            request_id=it.index, prompt_tokens=it.prompt,
            n_traces=mix["n_traces"],
            policy=obs.watch(make_policy(mix["policy"])),
            arrival_time=it.arrival_s, max_new_tokens=it.max_new_tokens))
    results = eng.serve_batch(reqs)
    obs.finish()
    if obs.t_open is None or not obs.closed:
        raise RuntimeError("the window never opened or never closed: the "
                           "traffic ran out before the pool filled or "
                           "before the window's end")
    t_served = time.perf_counter()
    setup_s = prof["window_start"] - t_process
    phases["window_open_s"] = setup_s
    devices = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[: cell["chips"]])

    # the window's requests
    t_open, t_close = obs.t_open, obs.t_close
    due = [r.metrics for r in results
           if r.metrics.admitted_s is not None
           and r.metrics.admitted_s < t_close]
    attempted = len(due)
    failed = sum(1 for md in due if md.status == "failed")
    served = [t for r in results for t in r.traces if t.num_tokens > 0]
    wb = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    eng_state = {"requests": len(results),
                 "requests_left": sum(1 for r in results
                                      if r.metrics.admitted_s is None),
                 "num_pruned": sum(r.num_pruned for r in results),
                 "preemptions": sum(r.num_preemptions for r in results)}
    del eng, results, reqs
    gc.collect()

    # correctness, with the engine freed
    t_chk = time.perf_counter()
    rng = np.random.default_rng(s_check)
    sample = check.pick(served, rng, mix["check"]["traces"])
    scores_expected = make_policy(mix["policy"]).uses_scorer
    numbers = check.compare(family.reference, params, scorer, m, sample,
                            tok.step_id, scores_expected)
    limits = spec["limits"]["limits"]
    correct = check.judge(numbers, limits)
    ctrl = None
    if control:
        ctrl = check.compare(family.reference, params, scorer, m, sample,
                             tok.step_id, scores_expected, control=True)
        ctrl["correct"] = check.judge(ctrl, limits)
    check_s = time.perf_counter() - t_chk

    summary = None
    if trace:
        from bench import trace_reduce
        summary = trace_reduce.reduce_dir(
            prof_dir, prof["trace_end"] - prof["window_start"],
            family.cost.KERNELS)
        shutil.rmtree(prof_dir, ignore_errors=True)

    ctx = Context(model=m, engine=eng_cfg, peaks=peaks,
                  seconds=seconds, setup_s=setup_s, obs=obs,
                  weight_bytes=wb, page=cfg.kv_block_size, trace=summary,
                  cost=family.cost)
    wanted = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for mdef in wanted:
        v = load_reader(mdef["name"])(ctx)
        if v is not None:
            metrics[mdef["name"]] = {"value": float(v), "unit": mdef["unit"]}

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = summary["breakdown"]

    window_tokens = obs.window_tokens()
    stats = {
        "window_tokens": window_tokens,
        "window_bursts": len(obs.bursts),
        "window_chunks": len(obs.chunks),
        "memory_full_decisions": obs.memory_full,
        "memory_full_victims": obs.victims,
        "step_tokens": sum(b.step_tokens for b in obs.bursts),
        "step_scores": sum(b.step_scores for b in obs.bursts),
        "compiles_in_window": prof.get("c1", 0) - prof.get("c0", 0),
        "setup_compiles": prof["c0"], "setup_cache_hits": prof["hits0"],
        "drain_s": t_served - prof["window_start"] - seconds,
        "check_s": check_s, "t_open_s": t_open, **eng_state,
        "setup_phases": {k: round(v, 3) for k, v in phases.items()},
        "sample": numbers["counts"],
    }
    for k, v in stats.items():
        print(f"stat {k}={v!r}", file=sys.stderr)
    if ctrl is not None:
        print(f"control correct={ctrl['correct']} counts={ctrl['counts']}",
              file=sys.stderr)
        for k, lim in limits.items():
            print(f"control {k}={_num(ctrl[k])!r} limit={lim!r}",
                  file=sys.stderr)
    checked = {k: {"value": _num(numbers[k]), "limit": lim}
               for k, lim in limits.items()}
    for k, v in checked.items():
        print(f"check {k}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr)
    out["check"] = checked
    return out


def _num(v):
    if v is None or math.isfinite(v):
        return v
    return "inf"

