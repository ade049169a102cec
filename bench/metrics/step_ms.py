"""Mean host time of one scheduler step in the window (host stamps
around each step; idle waits for arrivals are not steps)."""
from harness.stats import mean_step_ms


def read(run):
    return mean_step_ms(run)
