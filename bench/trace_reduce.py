"""From one profiler trace (``.xplane.pb``) to the numbers the device-trace
metrics read.

The reduction keys on names, listed here so that a rename shows as a
missing (null) metric and never as a silent zero:

- device planes: ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
  event per operation run on the chip, their ``XLA Modules`` line one per
  program run (``jit_<function>(<id>)``);
- a program's operations are the op events that lie inside one of its
  module events;
- an op event's name is the HLO instruction (``%name.N = shape op(...)``);
  its base name is ``name`` without the ``.N``. A Pallas kernel is the
  custom call named after its jitted wrapper (``paged_attention`` for the
  decode face of ``_mq_paged_kernel``, ``paged_attention_prefill`` for
  the chunked-prefill face); a run times per program the kernels its
  configuration's family lists in ``cost.KERNELS``;
- control-flow ops (``while``, ``conditional``, ``call``) span the ops
  they run and are left out of the ops breakdown.

Busy time is the union of the op intervals of a device, averaged over the
devices traced. Each idle gap is put down to the host event that ended
it: the innermost host event running at the instant the device resumed
(a program's dispatch, a transfer, a compile), or else the last host event
that ended inside the gap.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence

OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_ID = re.compile(r"\(\d+\)$")


def find_xplane(directory: str) -> Optional[str]:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _events(line) -> List[tuple]:
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def load(source) -> dict:
    """Planes of the trace (a path, or a ``jax.profiler.ProfileData``) as
    plain tuples: {"devices": {plane: {"ops", "modules"}}, "host":
    [events]}; an event is (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(source) if isinstance(source, str) else source
    devices, host = {}, []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if DEVICE_PLANE.match(plane.name) and OPS_LINE in lines:
            devices[plane.name] = {
                "ops": _events(lines[OPS_LINE]),
                "modules": (_events(lines[MODULES_LINE])
                            if MODULES_LINE in lines else [])}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += _events(ln)
    return {"devices": devices, "host": host}


def union(intervals: List[tuple]) -> List[tuple]:
    """Merged [start, end) intervals, sorted."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def module_name(name: str) -> str:
    return MODULE_ID.sub("", name)


def op_base(name: str) -> str:
    """``%paged_attention.9 = bf16[...] custom-call(...)`` ->
    ``paged_attention``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def op_label(name: str) -> str:
    """Instruction name and result shape, without layouts."""
    head, _, rest = name.partition(" = ")
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0])
    return f"{head.lstrip('%')} {shape}".strip()[:120]


def reduce(trace: dict, window_s: float,
           kernels=("paged_attention", "paged_attention_prefill"),
           top: int = 10) -> dict:
    """Busy time, per-program time and count, per-(program, kernel) time
    and count, and the breakdown of device ops and idle gaps."""
    devs = trace["devices"]
    if not devs:
        return None
    busy, op_time = [], defaultdict(float)
    mod_time, mod_count = defaultdict(float), defaultdict(int)
    kern_time, kern_count = defaultdict(float), defaultdict(int)
    gaps: List[tuple] = []
    for dev in devs.values():
        ops = dev["ops"]
        merged = union([(s, e) for _, s, e in ops])
        busy.append(sum(e - s for s, e in merged))
        gaps += [(merged[i - 1][1], merged[i][0])
                 for i in range(1, len(merged))]
        for name, s, e in ops:
            if op_base(name) not in CONTAINERS:
                op_time[op_label(name)] += (e - s) / len(devs)
        mods = sorted((s, e, module_name(n)) for n, s, e in dev["modules"])
        starts = [s for s, _, _ in mods]
        for s, e, n in mods:
            mod_time[n] += (e - s) / len(devs)
            mod_count[n] += 1
        for ev in ops:
            k = op_base(ev[0])
            if k in kernels:
                i = bisect.bisect_right(starts, ev[1]) - 1
                owner = mods[i][2] if i >= 0 and ev[2] <= mods[i][1] else ""
                kern_time[(owner, k)] += (ev[2] - ev[1]) / len(devs)
                kern_count[(owner, k)] += 1
    busy_s = sum(busy) / len(busy) / 1e9
    idle = attribute_gaps(gaps, trace["host"])
    scale = 1.0 / len(devs) / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "modules": {n: {"s": t / 1e9, "count": mod_count[n]}
                    for n, t in mod_time.items()},
        "kernels": {f"{o}/{k}": {"s": t / 1e9, "count": kern_count[(o, k)]}
                    for (o, k), t in kern_time.items()},
        "breakdown": {
            "device_ops": [[n, t / 1e9] for n, t in
                           sorted(op_time.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": [[n, t * scale] for n, t in
                          sorted(idle.items(), key=lambda x: -x[1])[:top]],
        },
    }


def attribute_gaps(gaps: List[tuple], host: List[tuple]) -> Dict[str, float]:
    """Idle nanoseconds per host event name (see the module docstring)."""
    host = sorted((s, e, n) for n, s, e in host if e > s)
    starts = [s for s, _, _ in host]
    by_end = sorted(host, key=lambda x: x[1])
    ends = [e for _, e, _ in by_end]
    out: Dict[str, float] = defaultdict(float)
    for g0, g1 in gaps:
        name = None
        i = bisect.bisect_right(starts, g1)
        best = None
        # innermost host event running when the device resumed; a short
        # look-back bounds the scan (host events rarely nest deeper)
        for s, e, n in host[max(0, i - 256):i]:
            if s <= g1 <= e and (best is None or e - s < best[0]):
                best = (e - s, n)
        if best is not None:
            name = best[1]
        else:
            j = bisect.bisect_right(ends, g1) - 1
            if j >= 0 and by_end[j][1] >= g0:
                name = by_end[j][2]
        out[name or "(no host event)"] += g1 - g0
    return out


def reduce_dir(directory: str, window_s: float,
               kernels: Sequence[str]) -> Optional[dict]:
    """``reduce`` of the newest trace under ``directory``, timing the op
    base names in ``kernels`` per program."""
    path = find_xplane(directory)
    if path is None:
        return None
    return reduce(load(path), window_s, kernels)
