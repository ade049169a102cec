"""90th percentile of completion latency, due time to last token, over
every request due in the window (host clock)."""
from harness.stats import percentile, request_latencies


def read(run):
    v = percentile(request_latencies(run, "done"), 90)
    return None if v is None else v * 1e3
