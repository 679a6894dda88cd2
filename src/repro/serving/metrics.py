"""Per-request serving metrics for the continuous-batching engine.

The paper's Table 3 accounts engine seconds (prefill / decode / wait);
online serving additionally needs the request-facing latencies every
serving system reports:

  * TTFT — time to first token: first generated token of ANY of the
    request's traces, measured from the request's *arrival* (not from
    batch start). Queueing before admission, shared-prompt prefill and
    chunked-prefill interleaving all land in TTFT.
  * TPOT — time per output token after the first: steady-state decode
    pace as the request experienced it, including scheduler stalls,
    preemption-induced recompute and cross-request contention. For a
    request fanning into N traces the denominator is the total new
    tokens across traces minus one (the batch generates N tokens per
    engine step, so TPOT is a *request-level* pace, not a per-trace one).
  * e2e latency — arrival to completion (all traces finished/pruned).

``summarize`` folds a set of ``RequestMetrics`` into the percentile
table the load benchmark writes to ``BENCH_serving.json``.

The serve loop's own measurement lives here too:

  * ``span(name, **stats)`` — a region of the scheduler (``sched.*``),
    written into the JAX profiler's trace when one is active, so that it
    shares the clock of the device's ops; about a microsecond otherwise.
  * ``ServeCounters`` — cumulative counters over an engine's life
    (``Engine.counters``): decode dispatches and their empty lanes by
    cause, memory-full outcomes, prefill and decode work, and the
    compiles the serve loop waited for, by the span they fell in.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Iterator, List, Optional, Sequence

import jax.monitoring
import numpy as np
from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class RequestMetrics:
    """Wall-clock serving metrics for one request (times in seconds,
    relative to the engine's serve-loop start)."""

    request_id: int
    arrival_s: float                 # when the request entered the queue
    admitted_s: Optional[float]      # first trace admitted to a slot
    first_token_s: Optional[float]   # first generated token (any trace)
    finished_s: Optional[float]      # all traces finished/pruned
    prompt_tokens: int = 0
    output_tokens: int = 0           # total new tokens across traces
    n_traces: int = 0
    num_pruned: int = 0
    num_preemptions: int = 0
    wait_s: float = 0.0              # memory-induced waiting (Table 3)
    prefill_s: float = 0.0
    decode_s: float = 0.0
    cached_tokens: int = 0           # prompt tokens served from the
    #                                  prefix cache (no prefill compute)
    # multi-tenant serving (scheduler.TenantScheduler)
    tenant: str = "default"
    priority: int = 0
    degraded_traces: int = 0         # traces shed by SLO admission
    slo_ttft_s: Optional[float] = None   # the request's SLO targets
    slo_tpot_s: Optional[float] = None   # (None = no objective attached)
    # fault-tolerant serving: how the request ended ("completed" |
    # "cancelled" | "deadline_exceeded" | "failed") and how many of its
    # traces were quarantined/aborted by fault recovery.
    status: str = "completed"
    failed_traces: int = 0

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_s is None:
            return None
        return self.first_token_s - self.arrival_s

    @property
    def tpot_s(self) -> Optional[float]:
        if self.first_token_s is None or self.finished_s is None:
            return None
        n_after_first = max(self.output_tokens - 1, 1)
        return (self.finished_s - self.first_token_s) / n_after_first

    @property
    def e2e_s(self) -> Optional[float]:
        if self.finished_s is None:
            return None
        return self.finished_s - self.arrival_s

    @property
    def ttft_attained(self) -> Optional[bool]:
        """Whether the request met its TTFT objective (None = no SLO)."""
        if self.slo_ttft_s is None:
            return None
        return self.ttft_s is not None and self.ttft_s <= self.slo_ttft_s

    @property
    def tpot_attained(self) -> Optional[bool]:
        if self.slo_tpot_s is None:
            return None
        return self.tpot_s is not None and self.tpot_s <= self.slo_tpot_s

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["ttft_s"] = self.ttft_s
        d["tpot_s"] = self.tpot_s
        d["e2e_s"] = self.e2e_s
        return d


def percentiles(xs: Sequence[float],
                ps: Sequence[float] = (50, 90, 99)
                ) -> Dict[str, Optional[float]]:
    """Linear-interpolated percentiles as {"p50": ..., "p90": ...}.

    An empty input yields ``None`` values (JSON ``null``), never NaN:
    NaN survives a round-trip through ``json`` as the non-standard token
    ``NaN`` and — worse — compares unequal to itself, so a regression
    gate diffing two NaN-bearing payloads would silently pass. ``None``
    fails loudly instead."""
    if not xs:
        return {f"p{_fmt(p)}": None for p in ps}
    vals = np.percentile([float(x) for x in xs], list(ps))
    return {f"p{_fmt(p)}": float(v) for p, v in zip(ps, vals)}


def _fmt(p: float) -> str:
    return str(int(p)) if float(p).is_integer() else str(p)


def summarize(metrics: Sequence[RequestMetrics],
              ps: Sequence[float] = (50, 90, 99)) -> dict:
    """Aggregate request metrics into the BENCH_serving.json payload."""
    done = [m for m in metrics if m.finished_s is not None]
    ttfts = [m.ttft_s for m in done if m.ttft_s is not None]
    tpots = [m.tpot_s for m in done if m.tpot_s is not None]
    e2es = [m.e2e_s for m in done]
    span = (max((m.finished_s for m in done), default=0.0)
            - min((m.arrival_s for m in metrics), default=0.0))
    total_tokens = sum(m.output_tokens for m in done)
    total_prompt = sum(m.prompt_tokens for m in metrics)
    total_cached = sum(m.cached_tokens for m in metrics)
    return {
        "num_requests": len(metrics),
        "num_completed": len(done),
        "total_output_tokens": total_tokens,
        "makespan_s": span,
        "throughput_tok_per_s": total_tokens / span if span > 0 else 0.0,
        "throughput_req_per_s": len(done) / span if span > 0 else 0.0,
        "ttft_s": percentiles(ttfts, ps),
        "tpot_s": percentiles(tpots, ps),
        "e2e_s": percentiles(e2es, ps),
        "mean_ttft_s": _mean(ttfts),
        "mean_tpot_s": _mean(tpots),
        "mean_e2e_s": _mean(e2es),
        "total_wait_s": sum(m.wait_s for m in metrics),
        "total_prefill_s": sum(m.prefill_s for m in metrics),
        "total_decode_s": sum(m.decode_s for m in metrics),
        "num_pruned": sum(m.num_pruned for m in metrics),
        "num_preemptions": sum(m.num_preemptions for m in metrics),
        "total_prompt_tokens": total_prompt,
        "total_cached_tokens": total_cached,
        "prefix_hit_rate": (total_cached / total_prompt
                            if total_prompt > 0 else 0.0),
        "requests_with_prefix_hit": sum(
            m.cached_tokens > 0 for m in metrics),
        "degraded_traces": sum(m.degraded_traces for m in metrics),
        "num_cancelled": sum(m.status == "cancelled" for m in metrics),
        "num_deadline_exceeded": sum(
            m.status == "deadline_exceeded" for m in metrics),
        "num_failed": sum(m.status == "failed" for m in metrics),
        "failed_traces": sum(m.failed_traces for m in metrics),
        "slo": _slo_attainment(metrics),
    }


def _slo_attainment(metrics: Sequence[RequestMetrics]) -> dict:
    """SLO attainment over the requests that carry an objective. A shed
    request (every trace dropped by admission control, so it never
    produced a first token) counts as a TTFT miss — shedding is a
    capacity decision, not an excuse."""
    ttft_j = [m.ttft_attained for m in metrics
              if m.ttft_attained is not None]
    tpot_j = [m.tpot_attained for m in metrics
              if m.tpot_attained is not None]
    return {
        "requests_with_slo": sum(
            m.slo_ttft_s is not None or m.slo_tpot_s is not None
            for m in metrics),
        "ttft_attainment": (sum(ttft_j) / len(ttft_j)
                            if ttft_j else None),
        "tpot_attainment": (sum(tpot_j) / len(tpot_j)
                            if tpot_j else None),
    }


def summarize_by_tenant(metrics: Sequence[RequestMetrics],
                        ps: Sequence[float] = (50, 90, 99)) -> dict:
    """Per-tenant breakdown of ``summarize`` (the BENCH_slo.json
    payload): tenants are compared on the same percentile table, plus
    their SLO attainment."""
    tenants: Dict[str, list] = {}
    for m in metrics:
        tenants.setdefault(m.tenant, []).append(m)
    return {name: summarize(ms, ps) for name, ms in sorted(tenants.items())}


def _mean(xs: Sequence[float]) -> Optional[float]:
    """Mean, or ``None`` for an empty input (same NaN-avoidance
    rationale as ``percentiles``)."""
    return sum(xs) / len(xs) if xs else None


# ---------------------------------------------------------------------------
# scheduler spans and serve counters
# ---------------------------------------------------------------------------

OUTSIDE = "(outside)"     # the phase of a compile outside every span
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_open_spans: List[str] = []   # names of the open spans, innermost last
_counting: Optional["ServeCounters"] = None   # the serve loop running now
_listening = False


class _Span:
    __slots__ = ("name", "_annotation")

    def __init__(self, name: str, stats: dict):
        self.name = name
        self._annotation = TraceAnnotation(name, **stats)

    def __enter__(self) -> "_Span":
        _open_spans.append(self.name)
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._annotation.__exit__(*exc)
        _open_spans.pop()


def span(name: str, **stats) -> _Span:
    """Context manager for one region of the serve loop. With a profiler
    trace active it lands on the host plane as an event ``name`` whose
    event stats are ``stats``; the innermost open span also names the
    phase that a compile inside it is charged to. The serve loop is
    single-threaded, so one module-level stack holds the open spans."""
    return _Span(name, stats)


@dataclasses.dataclass
class ServeCounters:
    """Serve-loop counters, cumulative over an engine's life.

    Per decode dispatch (``rounds``): the decode slots (``lane_rounds``)
    are either running a trace (``lanes_running``) or empty, and a
    round's empty slots all go to one cause — ``lanes_empty_memory`` when
    in that round an admission, a prefill chunk or write-block assurance
    was refused blocks; else ``lanes_empty_prefill`` when a prompt was
    still in prefill chunks; else ``lanes_empty_other`` (nothing waiting,
    or the token budget). So ``lanes_running`` plus the three causes is
    ``lane_rounds``.

    ``memory_full`` counts the scheduler's memory-full decisions, each
    with one outcome: a prefix-cache block evicted, a trace pruned, a
    lone trace truncated, idle prefixes or other prefill jobs reclaimed,
    a trace preempted, or nothing freed (``memory_waits``).

    ``prefill_tokens`` counts every prompt token a prefill program
    computed, chunked (``prefill_chunks`` calls) or one-shot;
    ``decode_tokens`` every token a decode call emitted to a trace.

    ``sampled_select`` / ``sampled_sort`` count the sampled rows (every
    emitted decode token and first-token row) whose top-k/top-p filter
    ran the exact selection or a full sort of the row
    (``sampling.sampler_path``); greedy and unfiltered rows count in
    neither.

    ``compiles``/``compile_s`` are the backend compiles that ran while
    the engine served, and ``compiles_by_phase`` maps the innermost span
    open at each to ``[count, seconds]`` (``OUTSIDE`` for none).

    MoE configurations only (they stay 0 otherwise), from the per-held-
    expert count each decode call returns: ``moe_pairs_routed``, every
    routed (token, expert) pair of the decode tokens, all MoE layers (the
    tokens times top-k times the MoE layers); ``moe_pairs_held``, those
    that fell on the experts this chip holds; ``moe_load_max``, summed
    over decode calls, the pairs of the call's busiest held expert. So
    ``moe_pairs_held / moe_pairs_routed`` is the held share (the held
    experts' share of all, under uniform routing), and ``moe_load_max``
    over ``moe_pairs_held / experts held`` the max/mean load."""

    rounds: int = 0
    lane_rounds: int = 0
    lanes_running: int = 0
    lanes_empty_memory: int = 0
    lanes_empty_prefill: int = 0
    lanes_empty_other: int = 0
    memory_full: int = 0
    evictions: int = 0
    prunes: int = 0
    truncations: int = 0
    reclaims: int = 0
    preemptions: int = 0
    memory_waits: int = 0
    horizon_fallbacks: int = 0
    prefill_chunks: int = 0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    sampled_select: int = 0
    sampled_sort: int = 0
    moe_pairs_routed: int = 0
    moe_pairs_held: int = 0
    moe_load_max: int = 0
    compiles: int = 0
    compile_s: float = 0.0
    compiles_by_phase: Dict[str, list] = dataclasses.field(
        default_factory=dict)

    def note_dispatch(self, lanes: int, running: int, cause: str) -> None:
        """One decode call over ``lanes`` slots, ``running`` of them in
        use; ``cause`` ("memory" | "prefill" | "other") takes the rest."""
        self.rounds += 1
        self.lane_rounds += lanes
        self.lanes_running += running
        name = f"lanes_empty_{cause}"
        setattr(self, name, getattr(self, name) + lanes - running)

    def note_memory_full(self, outcome: str) -> None:
        self.memory_full += 1
        setattr(self, outcome, getattr(self, outcome) + 1)

    def note_sampled(self, path: str, rows: int) -> None:
        """``rows`` sampled by a filter of ``path`` ("select" | "sort" |
        "none", as ``sampling.sampler_path`` names it)."""
        if path != "none":
            name = f"sampled_{path}"
            setattr(self, name, getattr(self, name) + rows)

    def note_moe(self, load, routed: int) -> None:
        """One decode call's routed pairs per held expert (``load``), and
        all its routed pairs (``routed``)."""
        self.moe_pairs_routed += routed
        self.moe_pairs_held += sum(load)
        self.moe_load_max += max(load)

    def note_compile(self, phase: str, secs: float) -> None:
        self.compiles += 1
        self.compile_s += secs
        entry = self.compiles_by_phase.setdefault(phase, [0, 0.0])
        entry[0] += 1
        entry[1] += secs

    def snapshot(self) -> dict:
        return dataclasses.asdict(self)


def _on_duration(event: str, secs: float, **_) -> None:
    if event == COMPILE_EVENT and _counting is not None:
        _counting.note_compile(_open_spans[-1] if _open_spans else OUTSIDE,
                               secs)


@contextlib.contextmanager
def counting(counters: ServeCounters) -> Iterator[ServeCounters]:
    """Charge the backend compiles that run inside the block to
    ``counters``. The JAX monitoring listener behind it is process-wide,
    registered on first use and never again."""
    global _counting, _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    prev, _counting = _counting, counters
    try:
        yield counters
    finally:
        _counting = prev
