"""Decode's share of its roofline: the least bytes the traced window's
pure-decode steps had to move (weights once, K/V at the live positions
of live slots) at the chip's HBM bandwidth, over the device's busy time
inside those steps (profiler trace).  Steps that admitted or prefilled
anything are left out.  Nothing to read without such a step."""


def read(run):
    t = run.trace
    if t is None or run.peaks is None:
        return None
    work = run.work
    need_bytes, busy_ns = 0, 0.0
    for i, ns in t.step_busy_ns.items():
        w = work.get(i)
        if w is None or not w.pure_decode or not w.decode_ctx:
            continue
        need_bytes += run.counts.decode_step_bytes(w.decode_ctx)
        busy_ns += ns
    if busy_ns <= 0:
        return None
    return 100.0 * need_bytes / run.peaks["hbm_bytes_per_s"] / (busy_ns / 1e9)
