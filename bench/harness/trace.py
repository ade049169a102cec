"""From a profiler trace to device busy time, idle share and step times.

The traced run marks its traced window with a host annotation named
``bench_window``, every scheduler step with ``sched_step:<i>`` and every
wait for new arrivals with ``wait_for_arrivals``.  The device planes
(``/device:...``) hold the operations that ran on each chip: their
``XLA Ops`` line gives the intervals in which an operation ran (a loop
op spans the ops of its body), and ``XLA Modules`` the jitted program
each belongs to.  Busy time is the union of the operation intervals, so
nested and overlapping operations count once.  The breakdown ranks
operations by self time (their own interval less the ops nested in it),
named ``<program>:<op>``.

Everything here works on plain ``(name, start_ns, end_ns)`` tuples, so
the arithmetic can be checked on a synthesised trace.
"""
from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

WINDOW = "bench_window"
STEP = "sched_step:"
WAIT = "wait_for_arrivals"


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the disjoint sorted ``merged`` covers."""
    if hi <= lo or not merged:
        return 0.0
    i = max(0, bisect.bisect_right([s for s, _ in merged], lo) - 1)
    total = 0.0
    for s, e in merged[i:]:
        if s >= hi:
            break
        total += max(0.0, min(e, hi) - max(s, lo))
    return total


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` between busy intervals."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class TraceSummary:
    window: Interval                       # ns, host clock of the trace
    busy_ns: float                         # mean over chips, inside window
    steps: Dict[int, Interval]             # step index -> host interval
    step_busy_ns: Dict[int, float]         # busy ns inside each step, mean
    top_ops: List[Tuple[str, float]]       # (op name, seconds), inside window
    idle_gaps: List[Tuple[str, float]]     # (host activity, seconds)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9


def self_times(ops: Sequence[Event]) -> List[Tuple[str, float, float, float]]:
    """(name, start, end, self time) of each op: its interval less the
    union of the ops nested inside it on the same line."""
    order = sorted(ops, key=lambda e: (e[1], -e[2]))
    out = []
    stack: List[list] = []          # [name, start, end, child_cover_end, covered]
    for name, s, e in order:
        while stack and s >= stack[-1][2]:
            top = stack.pop()
            out.append((top[0], top[1], top[2], top[2] - top[1] - top[4]))
        if stack:
            par = stack[-1]
            lo = max(s, par[3])
            if e > lo:
                par[4] += min(e, par[2]) - lo
                par[3] = max(par[3], min(e, par[2]))
        stack.append([name, s, e, s, 0.0])
    while stack:
        top = stack.pop()
        out.append((top[0], top[1], top[2], top[2] - top[1] - top[4]))
    return out


def _short(op: str) -> str:
    """``%fusion.7 = bf16[8,24576]{...} fusion(...)`` -> ``%fusion.7
    bf16[8,24576]``: the op and the shape it makes."""
    name, _, rest = op.partition(" = ")
    return f"{name} {rest.split('{', 1)[0]}".strip()


def _program(name: str) -> str:
    return name.split("(", 1)[0]


def summarize(device: Dict[str, Tuple[List[Event], List[Event]]],
              host: List[Event], n_top: int = 10) -> Optional[TraceSummary]:
    """``device``: per chip, its operation events and its program
    (module) events; ``host``: every host event (annotations and runtime
    spans).  None without a window or without a device."""
    win = [(s, e) for n, s, e in host if n == WINDOW]
    if not win or not device:
        return None
    lo, hi = win[0]
    steps: Dict[int, Interval] = {}
    for n, s, e in host:
        if n.startswith(STEP) and s >= lo and e <= hi:
            steps[int(n[len(STEP):])] = (s, e)
    merged = {c: union([(s, e) for _, s, e in ops])
              for c, (ops, _) in device.items()}
    n = len(merged)
    busy = sum(covered(m, lo, hi) for m in merged.values()) / n
    step_busy = {i: sum(covered(m, s, e) for m in merged.values()) / n
                 for i, (s, e) in steps.items()}
    per_op: Dict[str, float] = defaultdict(float)
    for ops, modules in device.values():
        mods = sorted((s, e, _program(name)) for name, s, e in modules)
        starts = [m[0] for m in mods]
        for name, s, e, own in self_times(ops):
            if s < lo or s >= hi or own <= 0:
                continue
            j = bisect.bisect_right(starts, s) - 1
            prog = mods[j][2] if j >= 0 and mods[j][1] >= s else "?"
            per_op[f"{prog}:{_short(name)}"] += own / n
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:n_top]
    first = next(iter(merged.values()))
    idle = sorted(gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:n_top]
    named = [(_host_activity(host, g), (g[1] - g[0]) / 1e9) for g in idle]
    return TraceSummary(window=(lo, hi), busy_ns=busy,
                        steps=steps, step_busy_ns=step_busy,
                        top_ops=[(k, v / 1e9) for k, v in top],
                        idle_gaps=named)


def _host_activity(host: List[Event], gap: Interval) -> str:
    """What the host was doing in the middle of an idle gap: the
    benchmark's own mark there (a scheduler step, a wait for arrivals),
    then the narrowest runtime span inside it."""
    mid = (gap[0] + gap[1]) / 2
    mark, inner, width = "", "", float("inf")
    for n, s, e in host:
        if not s <= mid <= e or n == WINDOW:
            continue
        if n.startswith(STEP):
            mark = "scheduler step"
        elif n == WAIT:
            mark = "waiting for arrivals"
        elif e - s < width:
            inner, width = n, e - s
    if mark and inner:
        return f"{mark} > {inner}"
    return mark or inner or "host idle"


def read_xspace(path: str
                ) -> Tuple[Dict[str, Tuple[List[Event], List[Event]]],
                           List[Event]]:
    """Per chip, the operation and program events, and all host events
    of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: Dict[str, Tuple[List[Event], List[Event]]] = {}
    host: List[Event] = []

    def events(line):
        return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in line.events] if line is not None else []

    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops = events(lines.get("XLA Ops"))
            if ops:
                device[plane.name] = (ops, events(lines.get("XLA Modules")))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(events(ln))
    return device, host
