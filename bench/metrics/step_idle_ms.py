"""Mean device idle time inside one scheduler step: over the traced
window's ``BatchScheduler.step`` spans (the program's own), each span's
length less the device's busy time inside it (profiler trace).  Nothing
to read where the run carries no program spans."""


def read(run):
    p = getattr(run, "spans", None)
    if p is None or not p.steps:
        return None
    idle = p.step_idle_ns
    return sum(idle) / len(idle) / 1e6
