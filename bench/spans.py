"""The scheduler's ``sched.*`` spans in one profiler trace, reduced to what
the scheduler's per-layer readings need.

The program writes its phases into the JAX profiler's trace as host events
named ``sched.*`` (``repro.serving.metrics.span``), on the clock of the
device's ops: ``sched.round`` (one scheduling round) holds ``sched.admit``,
``sched.assure``, ``sched.decode`` and ``sched.sync`` (the host blocked on
the decode call's results); ``sched.fold`` (the burst folded into the
traces) follows its round. Nothing here is read by the harness yet: a
trace of a program without the spans reduces to ``None``.

- A round's host time is its ``sched.round`` less its ``sched.sync``
  children, plus the ``sched.fold`` that follows it before the next round
  starts. A round that dispatched a decode counts once its fold is in the
  trace; a round with no decode counts as it is.
- Each idle stretch of a device (before its first op, between ops, after
  its last op, within the traced extent) is split over the innermost
  ``sched.*`` spans open during it, by overlap, and what no span covers
  goes to ``(outside)``; like busy time, it is averaged over the devices
  traced. (A stretch usually runs from the decode call's end, inside
  ``sched.sync``, through the fold and the next round to the next
  dispatch: charged whole to the span at its midpoint, it flipped
  between ``sched.sync`` and ``sched.fold`` from seed to seed.)
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import trace_reduce

PREFIX = "sched."
OUTSIDE = "(outside)"


def load_spans(source) -> List[tuple]:
    """The ``sched.*`` host events of a trace (a path or a
    ``jax.profiler.ProfileData``) as (name, start_ns, end_ns, stats),
    sorted by start."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(source) if isinstance(source, str) else source
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda x: (x[1], -x[2]))


def phase_segments(spans: List[tuple]) -> List[Tuple[float, float, str]]:
    """The timeline cut where the innermost open span changes:
    (start_ns, end_ns, name) pieces, in order; where no span is open
    there is no piece. Spans of one thread nest, so a stack suffices."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []      # (end, name), innermost last
    cur = 0.0

    def close_until(t: float) -> None:
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            segs.append((cur, end, name))
            cur = end

    for name, s, e, _ in spans:
        close_until(s)
        if stack:
            segs.append((cur, s, stack[-1][1]))
        stack.append((e, name))
        cur = s
    close_until(float("inf"))
    return [x for x in segs if x[1] > x[0]]


def split_by_phase(segs: List[tuple], starts: List[float], g0: float,
                   g1: float) -> Dict[str, float]:
    """The nanoseconds of [g0, g1) under each innermost phase."""
    out: Dict[str, float] = defaultdict(float)
    i = max(bisect.bisect_right(starts, g0) - 1, 0)
    covered = 0.0
    while i < len(segs) and segs[i][0] < g1:
        s, e, name = segs[i]
        overlap = min(e, g1) - max(s, g0)
        if overlap > 0:
            out[name] += overlap
            covered += overlap
        i += 1
    if g1 - g0 > covered:
        out[OUTSIDE] += g1 - g0 - covered
    return out


def round_host_times(spans: List[tuple]) -> List[float]:
    """Host seconds of each complete round (see the module docstring)."""
    by = defaultdict(list)
    for sp in spans:
        by[sp[0]].append(sp)
    rounds = by["sched.round"]
    syncs, folds = by["sched.sync"], by["sched.fold"]
    fold_starts = [f[1] for f in folds]
    out = []
    for i, (_, s, e, _) in enumerate(rounds):
        nxt = rounds[i + 1][1] if i + 1 < len(rounds) else float("inf")
        dispatched = any(s <= d[1] and d[2] <= e
                         for d in by["sched.decode"])
        host = (e - s) - sum(y[2] - y[1] for y in syncs
                             if s <= y[1] and y[2] <= e)
        j = bisect.bisect_left(fold_starts, e)
        if j < len(folds) and folds[j][1] < nxt:
            host += folds[j][2] - folds[j][1]
        elif dispatched:
            continue          # its fold fell outside the trace
        out.append(host / 1e9)
    return out


def reduce_spans(trace: dict, spans: List[tuple]) -> Optional[dict]:
    """``trace`` as ``trace_reduce.load`` gives it, ``spans`` as
    ``load_spans`` does: the complete rounds, their mean host seconds,
    the traced extent, and the devices' idle seconds by the phase the
    host was in. ``None`` when the trace holds no ``sched.*`` span."""
    if not spans:
        return None
    times = round_host_times(spans)
    segs = phase_segments(spans)
    starts = [x[0] for x in segs]
    events = [(s, e) for _, s, e in trace["host"]]
    events += [(s, e) for dev in trace["devices"].values()
               for _, s, e in dev["ops"] + dev["modules"]]
    events += [(s, e) for _, s, e, _ in spans]
    lo = min(s for s, _ in events)
    hi = max(e for _, e in events)
    idle = defaultdict(float)
    devs = trace["devices"]
    for dev in devs.values():
        merged = trace_reduce.union([(s, e) for _, s, e in dev["ops"]])
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                for name, ns in split_by_phase(segs, starts, g0,
                                               g1).items():
                    idle[name] += ns / len(devs) / 1e9
    return {"rounds": len(times),
            "round_host_s": sum(times) / len(times) if times else None,
            "extent_s": (hi - lo) / 1e9,
            "idle_by_phase": dict(sorted(idle.items(),
                                         key=lambda x: -x[1]))}
