"""90th percentile of time to first token, from each request's due time,
over every request due in the window (host clock)."""
from harness.stats import percentile, request_latencies


def read(run):
    v = percentile(request_latencies(run, "first"), 90)
    return None if v is None else v * 1e3
