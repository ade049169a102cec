"""Tail and rate arithmetic of the metric readers on a synthesised run:
an unfinished request enters the tails, the rate is over the whole
window, and the per-step accounting counts what each step computed."""
import dataclasses

import pytest

from bench_tiny import ROOT
from harness import account, spec, stats
from harness.serve import Step
from harness.traffic import Req


def read(name, run):
    return spec.metric_reader(name, ROOT)(run)


@dataclasses.dataclass
class FakeRun:
    requests: list
    steps: list
    seconds: float = 10.0
    drain_deadline: float = 70.0
    kind: str = "agent"
    n_slots: int = 4
    chunk: int = 4
    trace: object = None
    peaks: object = None
    counts: object = None

    def due_requests(self):
        return [r for r in self.requests if r.due < self.seconds]

    def window_output_tokens(self):
        firsts = sum(1 for r in self.requests
                     if r.first is not None and r.first <= self.seconds)
        return firsts + sum(s.counters["generated"] for s in self.steps
                            if s.end <= self.seconds)

    @property
    def work(self):
        return account.step_work(self)


def _req(due, first, done, n_out, rid, prompt=3, submit=None):
    r = Req(due=due, prompt=[1] * prompt, max_new=n_out, rid=rid)
    r.first, r.done, r.submit = first, done, submit
    r.out = [5] * n_out if done is not None else None
    return r


def _step(i, start, end, live=0, prefilled=0, generated=0, admissions=0,
          reused=0):
    return Step(i, start, end, admissions, reused,
                {"live": live, "prefilled": prefilled,
                 "generated": generated, "preempted": 0})


def test_percentile_is_nearest_rank():
    xs = list(range(1, 11))
    assert stats.percentile(xs, 90) == 9
    assert stats.percentile(xs, 91) == 10
    assert stats.percentile([3.0], 99) == 3.0
    assert stats.percentile([], 90) is None


def test_unfinished_request_enters_the_tails_at_the_drain_limit():
    reqs = [_req(i, i + 0.5, i + 2.0, 5, i) for i in range(9)]
    reqs.append(_req(9.0, None, None, 5, 9))       # never served
    run = FakeRun(reqs, [])
    # nine TTFTs of 0.5 s and one of 70 - 9 = 61 s: p90 is 0.5, p99 the 61
    assert read("ttft_p90_ms", run) == pytest.approx(500.0)
    assert stats.percentile(stats.request_latencies(run, "first"), 99) == 61.0
    assert read("e2e_p90_ms", run) == pytest.approx(2000.0)
    # tpot: (2.0 - 0.5) / 4 for the served ones; the unfinished one at 61 s
    assert read("tpot_p90_ms", run) == pytest.approx(375.0)
    reqs[0].done = None                 # a second unfinished one: the tail
    assert read("tpot_p90_ms", run) == pytest.approx(61000.0)


def test_rate_is_over_the_whole_window():
    reqs = [_req(0, 0.1, 9.0, 10, 0), _req(0, 0.2, 12.0, 10, 1)]
    steps = [_step(i, i * 0.5, i * 0.5 + 0.4, live=2, generated=2)
             for i in range(24)]                    # 20 end inside 10 s
    run = FakeRun(reqs, steps, kind="batch")
    # 2 first tokens + 20 steps x 2 decoded, over 10 s even though the
    # last token came at 12 s
    assert read("output_tok_s", run) == pytest.approx(42 / 10.0)
    assert read("slot_occupancy", run) == pytest.approx(50.0)
    assert read("step_ms.batch", run) == pytest.approx(400.0)
    assert read("step_ms.agent", run) == pytest.approx(400.0)
    assert read("step_ms", run) == pytest.approx(400.0)


def test_lateness_and_prefix_share():
    reqs = [_req(1.0, 2.0, 3.0, 2, 0, prompt=100, submit=1.002),
            _req(2.0, 3.0, 4.0, 2, 1, prompt=300, submit=2.010)]
    reqs[0].admit_step, reqs[1].admit_step = 0, 1
    steps = [_step(0, 0, 1, prefilled=100, admissions=1),
             _step(1, 1, 2, live=1, prefilled=200, generated=1,
                   admissions=1, reused=96)]
    run = FakeRun(reqs, steps)
    assert read("gen_lateness_p99_ms", run) == pytest.approx(10.0, abs=1e-6)
    assert read("prefix_reuse_share", run) == pytest.approx(96 / 400 * 100)


def test_step_accounting_chunks_and_decodes():
    # prompt of 10, 2 of them reused, chunk 4: chunks at steps 3, 4 of
    # positions 2-5 and 6-9; first token in step 5, then decodes at
    # steps 5 and 6 (contexts 11 and 12)
    r = _req(0, 5.5, 6.5, 3, 0, prompt=10)
    r.admit_step, r.reused = 3, 2
    steps = [_step(i, i, i + 0.9) for i in range(8)]
    work = account.step_work(FakeRun([r], steps))
    assert [list(x) for x in work[3].prefill_positions] == [[2, 3, 4, 5]]
    assert [list(x) for x in work[4].prefill_positions] == [[6, 7, 8, 9]]
    assert work[5].first_tokens == 1 and work[5].decode_ctx == [11]
    assert work[6].decode_ctx == [12] and work[6].pure_decode
    assert not work[5].pure_decode
    m = {"d_model": 8, "vocab_size": 16, "n_layers": 2, "head_dim": 2,
         "n_heads": 4, "n_kv_heads": 2, "d_ff": 8, "mlp_type": "gelu"}
    c = spec.arch("dense").counts(m, "bfloat16")
    assert account.step_flops(work[3], c) == sum(c.token_flops(p + 1)
                                                 for p in range(2, 6))
