"""The reduction from a profiler trace to busy time, idle share and the
device time of each scheduler step, on a synthesised trace."""
import bench_tiny  # noqa: F401  (puts bench/ on the path)
import pytest

from harness import trace


def test_union_merges_overlaps_and_drops_empty():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)]) == [
        (0, 3), (5, 9)]


def test_covered_and_gaps():
    merged = [(0, 3), (5, 9)]
    assert trace.covered(merged, 2, 6) == 2      # 2-3 and 5-6
    assert trace.covered(merged, 10, 12) == 0
    assert trace.gaps(merged, 1, 11) == [(3, 5), (9, 11)]


def _synth():
    """Window 0-100 ns on the host clock, two steps, two chips."""
    host = [("bench_window", 0, 100), ("sched_step:0", 10, 40),
            ("sched_step:1", 50, 90), ("PjitFunction(decode)", 42, 49),
            ("outside", 200, 300), ("sched_step:9", 150, 160)]
    chip0 = [("fusion.1", 12, 30), ("fusion.2", 20, 38),   # overlap: 12-38
             ("copy.3", 55, 85), ("late", 95, 120)]        # 95-100 counts
    chip1 = [("fusion.1", 10, 20), ("copy.3", 60, 70)]
    mods0 = [("jit_step(1)", 12, 38), ("jit_copy(2)", 55, 85)]
    mods1 = [("jit_step(1)", 10, 20), ("jit_copy(2)", 60, 70)]
    return {"/device:TPU:0": (chip0, mods0),
            "/device:TPU:1": (chip1, mods1)}, host


def test_summary_busy_idle_and_steps():
    dev, host = _synth()
    s = trace.summarize(dev, host)
    assert s.window == (0, 100)
    # chip 0: 26 + 30 + 5 = 61; chip 1: 10 + 10 = 20; mean over chips
    assert s.busy_ns == pytest.approx((61 + 20) / 2)
    assert s.busy_s == pytest.approx(40.5e-9)
    assert s.window_s == pytest.approx(100e-9)
    assert set(s.steps) == {0, 1}                # step 9 is outside
    assert s.step_busy_ns[0] == pytest.approx((26 + 10) / 2)
    assert s.step_busy_ns[1] == pytest.approx((30 + 10) / 2)
    names = dict(s.top_ops)
    assert names["jit_copy:copy.3"] == pytest.approx((30 + 10) / 2 / 1e9)
    assert names["?:late"] == pytest.approx(25 / 2 / 1e9)   # starts inside


def test_self_time_subtracts_nested_ops_once():
    ops = [("%while.1 = loop", 0, 100), ("%fusion.2 = a", 10, 40),
           ("%fusion.3 = b", 40, 50), ("%inner.4 = c", 12, 20),
           ("%after.5 = d", 120, 130)]
    own = {n: t for n, _, _, t in trace.self_times(ops)}
    assert own["%while.1 = loop"] == 100 - 40          # children 10-50
    assert own["%fusion.2 = a"] == 30 - 8              # inner 12-20
    assert own["%fusion.3 = b"] == 10 and own["%after.5 = d"] == 10


def test_idle_gaps_are_named_by_host_activity():
    dev, host = _synth()
    s = trace.summarize(dev, host)
    gaps = dict(s.idle_gaps)
    # chip 0 idle 0-12, 38-55, 85-95; 38-55's middle lies in the
    # decode dispatch, 0-12's in no span at all, 85-95's in step 1
    assert gaps["PjitFunction(decode)"] == pytest.approx(17e-9)
    assert gaps["host idle"] == pytest.approx(12e-9)
    assert gaps["scheduler step"] == pytest.approx(10e-9)


def test_idle_gap_inside_a_wait_names_both():
    dev, host = _synth()
    host = host + [("wait_for_arrivals", 40, 54), ("acquire", 45, 50)]
    gaps = dict(trace.summarize(dev, host).idle_gaps)
    assert gaps["waiting for arrivals > acquire"] == pytest.approx(17e-9)


def test_no_window_or_no_device_reads_nothing():
    dev, host = _synth()
    assert trace.summarize(dev, [h for h in host if h[0] != "bench_window"]) is None
    assert trace.summarize({}, host) is None
