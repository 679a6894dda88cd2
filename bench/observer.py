"""The benchmark's clock inside ``Engine.serve_batch``.

``Observer`` is a scheduling policy that schedules exactly as the engine's
FIFO default (it overrides no decision) and reads the scheduler's events,
which ``SchedulerCore`` hands every policy: ``BurstDone`` (one decode
call synced back to the host) and ``ChunkDone`` (one prefill chunk).
From them it keeps, for the measured window only:

- per decode call: its time, its lanes, the decode iterations it ran
  (the most tokens any lane emitted), and each emitted token's context
  length (for FLOPs and bytes);
- per emitted token: the gap since the same lane's previous token (the
  first token of a burst carries the gap, further tokens of the same
  burst carry 0);
- per prefill chunk: its start position and length.

The window opens at the first memory-full decision of any request's
pruning policy (the pool has run full) and closes ``seconds`` later. At
the close every request gets a deadline of now, so the scheduler
releases them all and the serve returns.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.serving.scheduler import BurstDone, ChunkDone, FIFOPolicy


@dataclasses.dataclass
class Burst:
    t: float
    n_lanes: int
    tokens: int
    steps: int               # decode iterations: most tokens of one lane
    gaps: List[float]        # one per emitted token
    lens: List[int]          # keys attended, one per emitted token
    step_tokens: int         # emitted tokens that close a step
    step_scores: int         # step scores recorded by this burst


class Observer(FIFOPolicy):
    def __init__(self, seconds: float, step_id: int,
                 on_open: Optional[Callable[[], None]] = None,
                 on_close: Optional[Callable[[], None]] = None,
                 trace_s: Optional[float] = None,
                 on_trace_end: Optional[Callable[[], None]] = None):
        super().__init__()
        self.seconds = seconds
        # the profiler traces the first trace_s seconds of the window
        self.trace_s = trace_s
        self.on_trace_end = on_trace_end
        self.t_trace_end: Optional[float] = None
        self.step_id = step_id
        self.on_open, self.on_close = on_open, on_close
        self.core = None
        self.t_open: Optional[float] = None     # serve-relative seconds
        self.t_close: Optional[float] = None
        self.closed = False
        self.bursts: List[Burst] = []
        self.chunks: List[tuple] = []           # (t, start, n_tokens)
        self.memory_full = 0                    # decisions in the window
        self.victims = 0                        # of which pruned/preempted
        self._last_tok: Dict[int, float] = {}   # id(trace) -> time
        self._pending = None

    # -- policy hooks (decisions stay FIFO's) ------------------------------
    def reset(self) -> None:
        super().reset()
        self.core = None

    def tick_budget(self, core):
        self.core = core
        return super().tick_budget(core)

    def watch(self, policy):
        """Wrap a request's pruning policy so that its memory-full
        decisions are seen (the first one opens the window)."""
        inner = policy.on_memory_full

        def on_memory_full(running, pressure=None):
            if self.t_open is None:
                self._open(time.perf_counter() - self.core.t_start)
            victim = inner(running, pressure=pressure)
            if self.in_window():
                self.memory_full += 1
                self.victims += victim is not None
            return victim

        policy.on_memory_full = on_memory_full
        return policy

    # -- window --------------------------------------------------------------
    def in_window(self) -> bool:
        return self.t_open is not None and not self.closed

    def _open(self, t: float) -> None:
        self.t_open, self.t_close = t, t + self.seconds
        if self.on_open is not None:
            self.on_open()

    def _end_trace(self, t: float) -> None:
        self.t_trace_end = t
        if self.on_trace_end is not None:
            self.on_trace_end()

    def _close(self) -> None:
        if self.trace_s is not None and self.t_trace_end is None:
            self._end_trace(self.t_close)
        self.closed = True
        if self.on_close is not None:
            self.on_close()
        now = time.perf_counter() - self.core.t_start
        for st in self.core.states:
            st.req.deadline = now

    # -- events --------------------------------------------------------------
    def on_event(self, ev) -> None:
        self._settle()
        if self.core is None:
            return
        if (self.in_window() and self.trace_s is not None
                and self.t_trace_end is None
                and ev.t >= self.t_open + self.trace_s):
            self._end_trace(ev.t)
        if self.in_window() and ev.t >= self.t_close:
            self._close()
        if isinstance(ev, BurstDone):
            core = self.core
            self._pending = (ev, [(t, id(t), t.num_tokens,
                                   len(t.step_scores),
                                   int(core.positions[t.batch_slot]))
                                  for t in core.running])
        elif isinstance(ev, ChunkDone) and self.in_window():
            self.chunks.append((ev.t, ev.pos - ev.chunk_tokens,
                                ev.chunk_tokens))

    def finish(self) -> None:
        """Fold the last burst in once the serve has returned, and let go
        of the scheduler (and the KV pool it holds)."""
        self._settle()
        self.core = None

    def _settle(self) -> None:
        """Fold in the burst seen at the previous event: its handler has
        appended the emitted tokens to the traces since."""
        if self._pending is None:
            return
        ev, lanes = self._pending
        self._pending = None
        keep = self.t_open is not None and self.t_open <= ev.t <= self.t_close
        gaps, lens = [], []
        steps = boundaries = scores = 0
        for trace, key, n0, s0, pos in lanes:
            n = trace.num_tokens - n0
            if n <= 0:
                continue
            prev = self._last_tok.get(key)
            self._last_tok[key] = ev.t
            if not keep:
                continue
            steps = max(steps, n)
            if prev is not None:
                gaps.append(ev.t - prev)
                gaps.extend([0.0] * (n - 1))
            lens.extend(range(pos - n + 1, pos + 1))
            out = trace.output_tokens
            boundaries += sum(1 for x in out[len(out) - n:]
                              if x == self.step_id)
            scores += len(trace.step_scores) - s0
        if keep:
            self.bursts.append(Burst(ev.t, ev.n_lanes, len(lens), steps,
                                     gaps, lens, boundaries, scores))

    # -- summaries -----------------------------------------------------------
    def traced(self) -> tuple:
        """The decode calls and prefill chunks inside the traced part of
        the window."""
        end = self.t_trace_end if self.t_trace_end is not None else -1.0
        return ([b for b in self.bursts if b.t <= end],
                [c for c in self.chunks if c[0] <= end])

    def window_tokens(self) -> int:
        return sum(b.tokens for b in self.bursts)

    def gaps(self) -> np.ndarray:
        return np.asarray([g for b in self.bursts for g in b.gaps])
