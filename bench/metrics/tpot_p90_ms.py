"""90th percentile over requests of each one's mean gap between output
tokens after the first (host clock).  A request unfinished at the drain
limit enters at its whole wait, so it misses any bound on the tail."""
from harness.stats import percentile


def read(run):
    gaps = []
    for r in run.due_requests():
        if r.done is None or r.first is None:
            gaps.append(run.drain_deadline - r.due)
        elif r.n_out > 1:
            gaps.append((r.done - r.first) / (r.n_out - 1))
    v = percentile(gaps, 90)
    return None if v is None else v * 1e3
