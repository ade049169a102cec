"""The serving program's own spans in a profiler trace, laid over the
device's busy time.

The program marks every scheduler step with a ``BatchScheduler.step``
annotation and each phase of it (preempt, admit and each admission,
ensure_blocks, decode_inputs, decode_dispatch, readback, bookkeeping)
with a child span inside it; the caller's ``BatchScheduler.submit`` runs
on another thread and is left out of the attribution.  A parent that
lacks these spans yields nothing here.  Device idle time is laid to the
innermost program span open at each instant, so a host read that stalls
the device shows under ``BatchScheduler.readback`` and not under the
runtime's own ``np.asarray`` span nested inside it (which is what
``trace._host_activity`` names).

Like ``trace.py`` this works on plain ``(name, start_ns, end_ns)``
tuples, so the arithmetic can be checked on a synthesised trace.
"""
from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from harness.trace import WINDOW, Event, Interval, covered, gaps, union

PREFIX = "BatchScheduler."
STEP = "BatchScheduler.step"
CALLER = "BatchScheduler.submit"
OUTSIDE = "(no program span)"

Segment = Tuple[float, float, str]


@dataclasses.dataclass
class ProgramSpans:
    window: Interval                    # ns, the harness's traced window
    steps: List[Interval]               # BatchScheduler.step spans in it
    step_busy_ns: List[float]           # device busy inside each, mean
    idle_ns: Dict[str, float]           # window's device idle by span

    @property
    def step_idle_ns(self) -> List[float]:
        """Each step span's length less the device's busy time in it."""
        return [(e - s) - b for (s, e), b in zip(self.steps, self.step_busy_ns)]


def innermost(spans: Sequence[Event]) -> List[Segment]:
    """Disjoint, sorted ``(start, end, name)`` segments: at each instant
    the narrowest span open then.  Spans of one thread nest; a child
    that outlives its parent is cut at the parent's end."""
    out: List[Segment] = []
    stack: List[Tuple[float, str]] = []        # (end, name), outermost first
    t = 0.0

    def close_until(s: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= s:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        close_until(s)
        if stack:
            if s > t:
                out.append((t, s, stack[-1][1]))
            e = min(e, stack[-1][0])
        stack.append((e, name))
        t = s
    close_until(float("inf"))
    return out


def attribute(idle: Sequence[Interval], segments: Sequence[Segment]
              ) -> Dict[str, float]:
    """Length of the ``idle`` intervals under each segment's name; what
    no segment covers goes to ``OUTSIDE``."""
    out: Dict[str, float] = defaultdict(float)
    starts = [s for s, _, _ in segments]
    for lo, hi in idle:
        under = 0.0
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        for s, e, name in segments[i:]:
            if s >= hi:
                break
            d = min(e, hi) - max(s, lo)
            if d > 0:
                out[name] += d
                under += d
        if hi - lo > under:
            out[OUTSIDE] += (hi - lo) - under
    return dict(out)


def summarize(device: Dict[str, Tuple[List[Event], List[Event]]],
              host: List[Event]) -> Optional[ProgramSpans]:
    """``device`` and ``host`` as ``trace.read_xspace`` gives them.  None
    without the harness's window, a device, or a program step span."""
    win = [(s, e) for n, s, e in host if n == WINDOW]
    if not win or not device:
        return None
    lo, hi = win[0]
    program = [(n, max(s, lo), min(e, hi)) for n, s, e in host
               if n.startswith(PREFIX) and n != CALLER and s < hi and e > lo]
    steps = sorted((s, e) for n, s, e in host
                   if n == STEP and s >= lo and e <= hi)
    if not steps:
        return None
    merged = [union([(s, e) for _, s, e in ops])
              for ops, _ in device.values()]
    n = len(merged)
    step_busy = [sum(covered(m, s, e) for m in merged) / n for s, e in steps]
    segments = innermost(program)
    idle: Dict[str, float] = defaultdict(float)
    for m in merged:
        for name, ns in attribute(gaps(m, lo, hi), segments).items():
            idle[name] += ns / n
    return ProgramSpans(window=(lo, hi), steps=steps,
                        step_busy_ns=step_busy, idle_ns=dict(idle))


def under_a_child(p: ProgramSpans) -> float:
    """Share of the device idle time inside step spans that lies under a
    named child span rather than in the step span alone."""
    inside = sum(v for k, v in p.idle_ns.items() if k != OUTSIDE)
    return (inside - p.idle_ns.get(STEP, 0.0)) / inside if inside else 0.0
