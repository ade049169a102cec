"""The whole step's share of the chip's peak: operations the real tokens
of the traced window's steps needed (prompt tokens that were not reused,
and decoded tokens, each at its own context) over the traced window's
length times the bf16 peak."""
from harness.account import step_flops


def read(run):
    t = run.trace
    if t is None or run.peaks is None or t.window_s <= 0:
        return None
    work = run.work
    flops = sum(step_flops(work[i], run.counts) for i in t.steps if i in work)
    if flops <= 0:
        return None
    return 100.0 * flops / (t.window_s * run.peaks["flops_bf16"])
