"""Share of the prompt tokens admitted in the window that the prefix
cache served from shared blocks (the scheduler's counters)."""


def read(run):
    admitted = [r for r in run.requests if r.admit_step is not None]
    total = sum(len(r.prompt) for r in admitted)
    if not total:
        return None
    return 100.0 * sum(s.reused for s in run.steps) / total
