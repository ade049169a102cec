"""The program's spans and counters as the benchmark reads them: the
reduction of ``BatchScheduler.*`` spans over device busy time on a
synthesised trace, the three readers of the program's fields, and, on
whole tiny runs, the program's admission stamps against the harness's
first-in-first-out rebuild."""
import dataclasses

import pytest

import bench_tiny
from harness import main as hm
from harness import spans, spec, trace
from harness.serve import Step
from harness.traffic import Req


def read(name, run):
    return spec.metric_reader(name, bench_tiny.ROOT)(run)


def _synth():
    """Window 0-200 ns.  The harness marks two steps; inside each the
    program's step span holds an admission (with its readback), the
    decode dispatch and the readback of the sampled tokens.  The submit
    span runs on the client thread across step 0."""
    host = [
        ("bench_window", 0, 200),
        ("sched_step:0", 10, 90), ("sched_step:1", 110, 190),
        ("BatchScheduler.step", 11, 89),
        ("BatchScheduler.admit", 12, 40),
        ("BatchScheduler.admit_bucket", 13, 39),
        ("BatchScheduler.readback", 30, 38),
        ("np.asarray(jax.Array)", 31, 37),      # runtime, not program
        ("BatchScheduler.decode_dispatch", 40, 50),
        ("BatchScheduler.readback", 50, 80),
        ("BatchScheduler.submit", 45, 46),
        ("BatchScheduler.step", 111, 189),
        ("BatchScheduler.decode_dispatch", 112, 120),
        ("BatchScheduler.readback", 120, 180),
        ("BatchScheduler.step", 300, 400),      # past the window
    ]
    ops = [("prefill", 14, 30), ("decode", 41, 55), ("decode", 113, 125)]
    mods = [("jit_prefill_fresh(1)", 14, 30), ("jit_decode_step(2)", 41, 55),
            ("jit_decode_step(2)", 113, 125)]
    return {"/device:TPU:0": (ops, mods)}, host


def test_innermost_lays_each_instant_to_the_narrowest_span():
    segs = spans.innermost([("a", 0, 100), ("b", 10, 40), ("c", 20, 30),
                            ("d", 40, 120), ("e", 200, 210)])
    assert segs == [(0, 10, "a"), (10, 20, "b"), (20, 30, "c"),
                    (30, 40, "b"), (40, 100, "d"), (200, 210, "e")]


def test_program_spans_over_device_busy_time():
    dev, host = _synth()
    p = spans.summarize(dev, host)
    assert p.window == (0, 200)
    assert p.steps == [(11, 89), (111, 189)]
    assert p.step_busy_ns == [16 + 14, 12]
    assert p.step_idle_ns == [78 - 30, 78 - 12]
    idle = p.idle_ns
    # idle 0-14, 30-41, 55-113, 125-200.  Step 0: 11-12 step, 12-13
    # admit, 13-14 and 38-39 admission, 30-38 its readback, 39-40 admit,
    # 40-41 dispatch, 55-80 readback, 80-89 step; step 1: 111-112 step,
    # 112-113 dispatch, 125-180 readback, 180-189 step
    assert idle["BatchScheduler.readback"] == 8 + 25 + 55
    assert idle["BatchScheduler.admit_bucket"] == 1 + 1
    assert idle["BatchScheduler.admit"] == 1 + 1
    assert idle["BatchScheduler.decode_dispatch"] == 1 + 1
    assert idle["BatchScheduler.step"] == 1 + 9 + 1 + 9
    assert idle[spans.OUTSIDE] == 11 + 22 + 11      # 0-11, 89-111, 189-200
    assert "BatchScheduler.submit" not in idle
    assert sum(idle.values()) == 200 - 16 - 14 - 12
    assert spans.under_a_child(p) == pytest.approx(
        1 - 20 / (200 - 42 - 44))
    # the harness's own reduction is unchanged: its steps are its marks,
    # and it names the readback's gap by the runtime span inside it
    t = trace.summarize(dev, host)
    assert t.steps == {0: (10, 90), 1: (110, 190)}
    assert dict(t.idle_gaps)["scheduler step > np.asarray(jax.Array)"] == 11e-9


def test_a_trace_without_program_spans_reads_nothing():
    dev, host = _synth()
    parent = [h for h in host if not h[0].startswith("BatchScheduler.")]
    assert spans.summarize(dev, parent) is None
    assert spans.summarize({}, host) is None
    assert read("step_idle_ms.agent", FakeRun([], [])) is None


@dataclasses.dataclass
class FakeRun:
    requests: list
    steps: list
    seconds: float = 10.0
    drain_deadline: float = 70.0
    spans: object = None

    def due_requests(self):
        return [r for r in self.requests if r.due < self.seconds]


def test_step_idle_reads_the_program_step_spans():
    dev, host = _synth()
    run = FakeRun([], [], spans=spans.summarize(dev, host))
    assert read("step_idle_ms.batch", run) == pytest.approx(
        (48 + 66) / 2 / 1e6)


def _step(i, prefilled, rows, end=1.0):
    counters = {"live": 1, "prefilled": prefilled, "generated": 1,
                "preempted": 0}
    if rows is not None:
        counters["prefill_rows"] = rows
    return Step(idx=i, start=end - 0.1, end=end, admissions=0, reused=0,
                counters=counters)


def test_prefill_pad_share_is_the_padded_rows_share():
    steps = [_step(0, 100, 8 * 128), _step(1, 0, 0), _step(2, 512, 512),
             _step(3, 7, 8, end=11.0)]                  # after the window
    assert read("prefill_pad_share.agent", FakeRun([], steps)) == (
        pytest.approx(100 * (1 - 612 / 1536)))
    assert read("prefill_pad_share.batch", FakeRun([], steps[1:2])) is None
    parent = [_step(0, 100, None)]
    assert read("prefill_pad_share.batch", FakeRun([], parent)) is None


def _req(due, submit, admit=(), rid=0):
    r = Req(due=due, prompt=[1, 2], max_new=2, rid=rid)
    r.submit = submit
    if admit != ():
        r.admit = admit
    return r


def test_queue_wait_counts_unadmitted_requests_at_the_drain_limit():
    reqs = [_req(float(i), i + 0.001, i + 0.001 + 0.01 * i, rid=i)
            for i in range(9)]
    reqs.append(_req(9.0, 9.001, None, rid=9))          # never admitted
    reqs.append(_req(12.0, 12.0, 12.5, rid=10))          # due past window
    run = FakeRun(reqs, [])
    # waits 0, 10, ..., 80 ms and 70 - 9.001 s: p90 is the 9th of 10
    assert read("queue_wait_p90_ms", run) == pytest.approx(80.0)
    reqs[0].admit = 60.0
    assert read("queue_wait_p90_ms", run) == pytest.approx(
        (60.0 - 0.001) * 1e3)
    parent = FakeRun([_req(0.0, 0.001)], [])
    assert read("queue_wait_p90_ms", parent) is None


@pytest.mark.parametrize("kind", ["agent", "batch"])
def test_program_stamps_agree_with_the_fifo_rebuild(tmp_path, kind):
    """The harness rebuilds admissions first in, first out
    (``serve._mark_admissions``); the program stamps its own.  Over whole
    tiny runs of both mixes they agree, and the readers read them."""
    root = bench_tiny.make_root(tmp_path, kind)
    cell, window, sched = bench_tiny.window(root, kind, 2 ** 31 + 5)
    sv = cell.serving
    run = hm.RunData(cell, window, cell.arch.counts(cell.model, sv["dtype"]),
                     None, None, int(sv["prefill_chunk"]))
    warm = sched._steps - len(window.steps)
    admitted = [r for r in window.requests if r.admit_step is not None]
    assert admitted
    for r in admitted:
        q = sched.requests[r.rid]
        assert q.admit_step - warm == r.admit_step, r.rid
        # the client stamps its submit just after the program's enqueue
        assert -0.01 < r.admit - r.submit < 2.0 + 30
        if window.steps[r.admit_step].admissions == 1:
            assert q.reused == r.reused, r.rid
    if kind == "agent":
        assert any(sched.requests[r.rid].reused for r in admitted)
        assert read("queue_wait_p90_ms", run) >= 0
    assert 0 < read(f"prefill_pad_share.{kind}", run) < 100
    assert read(f"step_idle_ms.{kind}", run) is None    # not traced
