"""99th percentile of how late the open-loop client submitted a request
after its due time (host clock): a starved generator shows here, and is
not read as a fast server."""
from harness.stats import percentile


def read(run):
    late = [r.submit - r.due for r in run.due_requests()
            if r.submit is not None]
    v = percentile(late, 99)
    return None if v is None else v * 1e3
