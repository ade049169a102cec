"""Mean share of the decode slots that were live, over the steps of the
window (the scheduler's per-step event)."""


def read(run):
    steps = [s for s in run.steps if s.end <= run.seconds]
    if not steps:
        return None
    return 100.0 * sum(s.counters["live"] for s in steps) / (
        len(steps) * run.n_slots)
