"""What each scheduler step of a run computed: which prompt positions it
prefilled and at which contexts its live slots decoded.

Rebuilt after the window from the run's records alone: each request's
prompt length, output count and stamps, and the per-step counters the
scheduler reports (its prefix cache's admissions and reused tokens).
Admissions are first in, first out, so the k-th admission of the window
is the k-th request submitted in it.  A chunked admission prefills one
chunk per step from its admission step on; a request's first output
token comes from prefill, and output token j >= 1 from the decode of
step ``first_step + j - 1``, at position ``prompt + j - 1``.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List


@dataclasses.dataclass
class StepWork:
    prefill_positions: List[range]
    decode_ctx: List[int]       # context (positions attended) of each decode
    first_tokens: int

    @property
    def pure_decode(self) -> bool:
        return not self.prefill_positions and not self.first_tokens


def step_work(run) -> Dict[int, StepWork]:
    steps = run.steps
    work = {s.idx: StepWork([], [], 0) for s in steps}
    ends = [s.end for s in steps]
    idx_of = [s.idx for s in steps]
    admitted = [r for r in sorted(run.requests, key=lambda r: r.rid)
                if r.admit_step is not None]
    chunk = run.chunk
    for r in admitted:
        P = len(r.prompt)
        lo = r.reused
        if P > chunk:
            k = 0
            while lo < P:
                hi = min(P, lo + chunk)
                s = r.admit_step + k
                if s in work:
                    work[s].prefill_positions.append(range(lo, hi))
                lo, k = hi, k + 1
        elif r.admit_step in work:
            work[r.admit_step].prefill_positions.append(range(lo, P))
        if r.first is None:
            continue
        f = idx_of[min(bisect.bisect_left(ends, r.first), len(ends) - 1)]
        work[f].first_tokens += 1
        for j in range(1, r.n_out):
            s = f + j - 1
            if s in work:
                work[s].decode_ctx.append(P + j)
    return work


def step_flops(w: StepWork, counts) -> int:
    total = 0
    for rng in w.prefill_positions:
        n = len(rng)
        if n:
            # token_flops is affine in ctx: sum it at the mean context
            total += n * counts.token_flops(0) + (
                counts.token_flops(1) - counts.token_flops(0)
            ) * (rng.start + 1 + rng.stop) * n // 2
    for ctx in w.decode_ctx:
        total += counts.token_flops(ctx)
    return total
