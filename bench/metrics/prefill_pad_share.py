"""Share of the prefill rows the window's steps computed that were
padding: 100 x (1 - real tokens / rows computed), from each step's
``EngineStepped.prefilled`` and ``prefill_rows`` (the program's
counters).  Nothing to read where a step lacks the row count, or no
step prefilled."""


def read(run):
    steps = [s for s in run.steps if s.end <= run.seconds]
    rows = [s.counters.get("prefill_rows") for s in steps]
    if not steps or None in rows or not sum(rows):
        return None
    return 100.0 * (1.0 - sum(s.counters["prefilled"] for s in steps)
                    / sum(rows))
