"""90th percentile over the requests due in the window of each one's
wait in the scheduler's queue: the program's admission stamp
(``Request.t_admit``) less the submit stamp.  A request never admitted
waits until the drain limit.  Nothing to read where the run carries no
admission stamps."""
from harness.stats import percentile


def read(run):
    due = [r for r in run.due_requests() if r.submit is not None]
    if not due or not any(hasattr(r, "admit") for r in due):
        return None
    waits = [(r.admit if getattr(r, "admit", None) is not None
              else run.drain_deadline) - r.submit for r in due]
    return percentile(waits, 90) * 1e3
