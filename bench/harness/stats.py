"""Tail arithmetic shared by the metric readers."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (``q`` in 0..100): the smallest value with
    at least ``q`` percent of the sample at or below it.  None when
    empty."""
    if not values:
        return None
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def request_latencies(run, until: str) -> list:
    """Seconds from each due request's due time to its first token
    (``until="first"``) or its last (``"done"``).  A request that never
    got there by the drain limit counts at the limit: it misses every
    bound a tail could set."""
    out = []
    for r in run.due_requests():
        t = getattr(r, until)
        out.append((t if t is not None else run.drain_deadline) - r.due)
    return out


def mean_step_ms(run) -> Optional[float]:
    """Mean host duration of the scheduler steps that ended in the window."""
    steps = [s for s in run.steps if s.end <= run.seconds]
    if not steps:
        return None
    return 1e3 * sum(s.end - s.start for s in steps) / len(steps)
