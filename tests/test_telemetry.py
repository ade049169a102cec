"""Telemetry subsystem: deterministic metrics core, event bridge,
byte-identical exports, SLO burn-rate alerts, jit profiling hooks.

The acceptance contracts this file pins:

  (a) two virtual-clock replays of the same seeded workload produce
      BYTE-identical Prometheus (and OTLP JSON) exports;
  (b) the bridge is lossless: every folded event is accounted in
      ``repro_events_total`` and per-family counts reconcile against
      the raw stream;
  (c) telemetry is free when off: attaching a bridge does not perturb a
      run — the event stream and result are bit-identical to a bare
      session's;
  (d) histogram exemplars carry the SAME span ids ``fold_spans``
      assigns the stream, so a latency sample links into its span tree.
"""
import json

import pytest

from repro.apps.session import RunSpec, Session
from repro.core.events import (LLMCompleted, RunCompleted, RunStarted,
                               SloAlertFired, ToolInvoked, events_from_wire,
                               events_to_wire, to_wire)
from repro.core.metrics import LLMEvent
from repro.telemetry import (DEFAULT_LATENCY_BUCKETS, EventMetricsBridge,
                             JitProfiler, MetricsRegistry, SloMonitor,
                             export_otlp_metrics_json, fold_report,
                             log_buckets, parse_prometheus,
                             render_prometheus, to_otlp_metrics)
from repro.tenancy.tracing import fold_spans
from repro.traffic import SLOTarget, Scenario, TrafficDriver, Workload

SCENARIOS = tuple(
    Scenario(f"web/{inst}/{pat}", "web_search", inst, pat, weight=1.0)
    for inst in ("quantum", "edge") for pat in ("agentx", "react"))


def _workload(n=24, seed=0):
    return Workload(scenarios=SCENARIOS, arrival="poisson", rate=10.0,
                    n_requests=n, seed=seed)


def _fold_workload(n=24, seed=0):
    """One seeded oracle workload folded into a fresh registry."""
    report = TrafficDriver(Session()).run(_workload(n, seed))
    registry = MetricsRegistry()
    bridge = EventMetricsBridge(registry)
    fold_report(bridge, report)
    return report, registry


# ---------------------------------------------------------------------------
# metrics core


def test_log_buckets_pattern():
    assert log_buckets(0.001, 2) == [0.001, 0.0025, 0.005,
                                     0.01, 0.025, 0.05]
    assert DEFAULT_LATENCY_BUCKETS[0] == 0.001
    assert DEFAULT_LATENCY_BUCKETS == tuple(sorted(DEFAULT_LATENCY_BUCKETS))


def test_counter_labels_and_monotonicity():
    r = MetricsRegistry()
    c = r.counter("x_total", "help")
    c.inc(tool="search")
    c.inc(2.0, tool="search")
    c.inc(tool="fetch")
    assert c.value(tool="search") == 3.0
    assert c.value(tool="fetch") == 1.0
    assert c.value(tool="never") == 0.0
    assert c.total() == 4.0
    with pytest.raises(ValueError):
        c.inc(-1.0, tool="search")


def test_gauge_set_add_max():
    r = MetricsRegistry()
    g = r.gauge("g", "help")
    g.set(3.0)
    g.add(-1.0)
    assert g.value() == 2.0
    g.max_of(7.0)
    g.max_of(4.0)
    assert g.value() == 7.0


def test_histogram_bucket_edge_cases():
    """Prometheus ``le`` semantics: an observation EQUAL to a bound
    lands in that bound's bucket; past the last bound lands in +Inf."""
    r = MetricsRegistry()
    h = r.histogram("h", "help", buckets=(1.0, 2.5, 5.0))
    for v in (1.0, 2.5, 5.0, 5.0001, 0.0):
        h.observe(v)
    snap = h.snapshot()
    # counts per bucket: <=1.0 gets {1.0, 0.0}; <=2.5 gets {2.5};
    # <=5.0 gets {5.0}; +Inf gets {5.0001}
    assert snap["counts"] == [2, 1, 1, 1]
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(13.5001)


def test_label_cardinality_and_ordering():
    """Label order never matters; distinct values make distinct series;
    labelsets iterate sorted (the determinism the exports rest on)."""
    r = MetricsRegistry()
    c = r.counter("c_total", "help")
    c.inc(a="1", b="2")
    c.inc(b="2", a="1")        # same series, different kwarg order
    c.inc(a="1", b="3")
    assert c.value(a="1", b="2") == 2.0
    assert len(c.labelsets()) == 2
    assert c.labelsets() == sorted(c.labelsets())
    assert r.label_values("c_total", "b") == ["2", "3"]


def test_registry_kind_mismatch_raises():
    r = MetricsRegistry()
    r.counter("m", "help")
    assert r.counter("m") is r.get("m")     # re-request: same family
    with pytest.raises(TypeError):
        r.gauge("m")


def test_scope_stamps_const_labels():
    r = MetricsRegistry()
    eng = r.scope(layer="engine")
    eng.counter("s_total", "help").inc(2.0, kind="decode")
    assert r.get("s_total").value(layer="engine", kind="decode") == 2.0
    # reserved call params pass through, they never become labels
    eng.histogram("s_lat", "help", buckets=(1.0,)).observe(
        0.5, t=3.0, exemplar={"run": "1"}, kind="decode")
    series = r.get("s_lat").series
    assert list(series.values())[0].exemplars[0][0] == {"run": "1"}
    assert dict(list(series)[0]) == {"kind": "decode", "layer": "engine"}


# ---------------------------------------------------------------------------
# exports


def _toy_registry():
    r = MetricsRegistry(clock=lambda: 12.5)
    r.counter("repro_demo_total", "demo counter").inc(3, tool="search")
    r.gauge("repro_demo_gauge", "demo gauge").set(1.5)
    r.histogram("repro_demo_seconds", "demo hist", unit="s",
                buckets=(0.1, 1.0)).observe(
                    0.5, exemplar={"run": "1", "span": "%016x" % 2})
    return r


def test_prometheus_text_renders_and_parses():
    r = _toy_registry()
    text = render_prometheus(r)
    assert "# TYPE repro_demo_total counter" in text
    assert "# TYPE repro_demo_seconds histogram" in text
    assert render_prometheus(r) == text          # stable
    parsed = parse_prometheus(text)
    assert parsed["repro_demo_total"]['{tool="search"}'] == 3.0
    assert parsed["repro_demo_gauge"][""] == 1.5
    # cumulative le buckets + +Inf + _sum/_count
    assert parsed["repro_demo_seconds_bucket"]['{le="+Inf"}'] == 1.0
    assert parsed["repro_demo_seconds_count"][""] == 1.0


def test_otlp_metrics_shape_and_determinism():
    r = _toy_registry()
    doc = to_otlp_metrics(r, service="repro-test")
    rm = doc["resourceMetrics"][0]
    names = [m["name"] for m in rm["scopeMetrics"][0]["metrics"]]
    assert names == sorted(names)
    assert "repro_demo_seconds" in names
    hist = [m for m in rm["scopeMetrics"][0]["metrics"]
            if m["name"] == "repro_demo_seconds"][0]
    dp = hist["histogram"]["dataPoints"][0]
    assert dp["count"] == "1" and len(dp["exemplars"]) == 1
    assert export_otlp_metrics_json(r) == export_otlp_metrics_json(r)
    json.loads(export_otlp_metrics_json(r))      # valid JSON


# ---------------------------------------------------------------------------
# the bridge: losslessness, wire parity, exemplar linkage


def _one_run(seed=3):
    spec = RunSpec("web_search", "quantum", "agentx", seed=seed)
    result = Session().execute(spec)
    return result, list(result.extras["events"])


def test_bridge_losslessness():
    """Every event lands in repro_events_total and per-family counts
    reconcile against the raw stream — no accounting escapes."""
    _, events = _one_run()
    registry = MetricsRegistry()
    EventMetricsBridge(registry).feed(events)
    assert registry.total("repro_events_total") == len(events)
    assert registry.total("repro_tool_calls_total") == \
        sum(isinstance(e, ToolInvoked) for e in events)
    assert registry.total("repro_llm_calls_total") == \
        sum(isinstance(e, LLMCompleted) for e in events)
    assert registry.get("repro_llm_latency_seconds") is not None
    assert registry.total("repro_llm_latency_seconds") == \
        registry.total("repro_llm_calls_total")


def test_wire_replay_folds_identically():
    """In-process stream and its wire round-trip write the identical
    registry — byte-identical Prometheus text."""
    _, events = _one_run()
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    EventMetricsBridge(r1).feed(events)
    EventMetricsBridge(r2).feed(events_from_wire(events_to_wire(events)))
    assert render_prometheus(r1) == render_prometheus(r2)
    assert export_otlp_metrics_json(r1) == export_otlp_metrics_json(r2)


def test_exemplars_carry_fold_spans_ids():
    """A latency exemplar's span id is the id fold_spans assigns the
    same stream — histograms link into the span tree."""
    _, events = _one_run()
    registry = MetricsRegistry()
    EventMetricsBridge(registry).feed(events)
    tree_ids = {s.span_id for root in fold_spans(events)
                for s in root.walk()}
    exemplar_ids = set()
    for fam in ("repro_llm_latency_seconds", "repro_tool_latency_seconds"):
        for series in registry.get(fam).series.values():
            for labels, _v, _t in series.exemplars.values():
                exemplar_ids.add(labels["span"])
    assert exemplar_ids, "expected latency exemplars"
    assert exemplar_ids <= tree_ids


def test_telemetry_off_is_free():
    """(c): a session with a bridge attached produces the bit-identical
    event stream and result a bare session does — telemetry never
    perturbs the run it observes."""
    spec = RunSpec("web_search", "edge", "react", seed=11)
    bare = Session().execute(spec)
    bridge = EventMetricsBridge()
    observed = Session(on_event=bridge).execute(spec)
    assert events_to_wire(observed.extras["events"]) == \
        events_to_wire(bare.extras["events"])
    assert observed.success == bare.success
    assert observed.faas_cost == bare.faas_cost
    assert observed.trace.llm_cost == bare.trace.llm_cost
    # and the bridge saw the run
    assert bridge.registry.total("repro_events_total") == \
        len(bare.extras["events"])


# ---------------------------------------------------------------------------
# (a): byte-identical exports across two virtual replays


def test_two_virtual_replays_byte_identical_export():
    report1, reg1 = _fold_workload(seed=0)
    report2, reg2 = _fold_workload(seed=0)
    text1, text2 = render_prometheus(reg1), render_prometheus(reg2)
    assert text1 == text2
    assert export_otlp_metrics_json(reg1) == export_otlp_metrics_json(reg2)
    # and the key series are actually populated
    parsed = parse_prometheus(text1)
    assert reg1.total("repro_tool_latency_seconds") > 0
    assert reg1.total("repro_run_latency_seconds") == len(report1.records)
    assert any(k.startswith("repro_tool_latency_seconds")
               for k in parsed)


def test_different_seeds_diverge():
    """Sanity for the invariant above: the export is a function of the
    workload, not a constant."""
    _, reg1 = _fold_workload(seed=0)
    _, reg2 = _fold_workload(seed=5)
    assert render_prometheus(reg1) != render_prometheus(reg2)


# ---------------------------------------------------------------------------
# SLO burn-rate alerts


def _slo():
    return SLOTarget(latency_s=10.0, ttft_s=5.0, success_rate=0.9)


def test_burn_rate_windows_and_alert():
    registry = MetricsRegistry()
    fired = []
    mon = SloMonitor(_slo(), window_s=60.0, threshold=2.0,
                     registry=registry, on_alert=fired.append)
    # window 0: all healthy — no alert
    for i in range(4):
        mon.observe(t=10.0 * i, ok=True, latency_s=1.0, ttft_s=0.5)
    # window 1: 2/4 failures => burn = 0.5 / 0.1 = 5.0 >= 2.0
    for i in range(4):
        mon.observe(t=60.0 + 10.0 * i, ok=(i % 2 == 0), latency_s=1.0,
                    ttft_s=0.5)
    mon.finalize()
    success_alerts = [a for a in fired if a.slo == "success"]
    assert len(success_alerts) == 1
    a = success_alerts[0]
    assert a.window_start == 60.0 and a.bad == 2 and a.total == 4
    assert a.burn_rate == pytest.approx(5.0)
    assert a.t == 120.0
    assert registry.get("repro_slo_alerts_total").value(slo="success") == 1
    assert registry.get("repro_slo_burn_rate").value(slo="success") == \
        pytest.approx(5.0)
    assert mon.summary()["by_objective"]["success"] == 1


def test_latency_and_ttft_objectives_share_budget_currency():
    fired = []
    mon = SloMonitor(_slo(), window_s=60.0, threshold=2.0,
                     on_alert=fired.append)
    for i in range(4):
        # all succeed, but half blow the latency target and all blow TTFT
        mon.observe(t=5.0 * i, ok=True,
                    latency_s=99.0 if i % 2 else 1.0, ttft_s=50.0)
    mon.finalize()
    assert {a.slo for a in fired} == {"latency", "ttft"}


def test_min_count_suppresses_thin_windows():
    fired = []
    mon = SloMonitor(_slo(), window_s=60.0, threshold=2.0, min_count=3,
                     on_alert=fired.append)
    mon.observe(t=0.0, ok=False, latency_s=1.0)
    mon.finalize()
    assert fired == []


def test_alert_event_folds_through_bridge():
    """A replayed alert stream lands in repro_slo_alerts_total — alerts
    are first-class events on the wire."""
    alert = SloAlertFired(t=120.0, slo="success", window_start=60.0,
                          window_s=60.0, burn_rate=5.0, threshold=2.0,
                          bad=2, total=4, target=0.9)
    registry = MetricsRegistry()
    EventMetricsBridge(registry).feed([to_wire(alert)])   # wire dicts ok
    assert registry.get("repro_slo_alerts_total").value(slo="success") == 1
    assert registry.total("repro_events_total") == 1


def test_slo_monitor_over_traffic_records_deterministic():
    report = TrafficDriver(Session()).run(_workload(16, seed=2))
    outs = []
    for _ in range(2):
        mon = SloMonitor(SLOTarget(), window_s=30.0, threshold=1.0)
        mon.observe_records(report.records)
        outs.append((len(mon.alerts), mon.summary()))
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# jit profiling hooks


def test_profiler_counts_calls_and_compiles():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return x * 2.0

    prof = JitProfiler()
    g = prof.wrap("f", f)
    a = g(jnp.ones((4,)))
    b = g(jnp.ones((4,)))          # cached trace
    c = g(jnp.ones((8,)))          # new shape -> recompile
    assert list(a) == [2.0] * 4 and list(b) == [2.0] * 4
    assert list(c) == [2.0] * 8
    s = prof.stats()["f"]
    assert s["calls"] == 3 and s["compiles"] == 2
    assert s["total_s"] >= 0 and s["max_ms"] >= s["min_ms"]
    assert prof.registry.get("repro_jit_calls_total").value(fn="f") == 3
    assert prof.registry.get("repro_jit_compiles_total").value(fn="f") == 2
    assert any("f" in row for row in prof.table())


def test_profiler_propagates_device_errors():
    """A failing ``block_until_ready`` is the device's error: the wrapper
    raises it instead of timing the enqueue and carrying on."""
    class _Failing:
        def block_until_ready(self):
            raise RuntimeError("device lost")

    prof = JitProfiler()
    g = prof.wrap("f", lambda: _Failing())
    with pytest.raises(RuntimeError, match="device lost"):
        g()
    h = prof.wrap("g", lambda: {"n": 3, "name": "x"})   # non-arrays pass
    assert h() == {"n": 3, "name": "x"}


def test_profiler_keeps_private_registry_by_default():
    """Wall times are nondeterministic, so they must not leak into a
    bridge registry that byte-identical-replay tests compare."""
    bridge = EventMetricsBridge()
    prof = JitProfiler()
    assert prof.registry is not bridge.registry
    shared = JitProfiler(registry=bridge.registry)
    assert shared.registry is bridge.registry


def test_wrap_kernel_ops_rebinds_and_restores():
    from repro import kernels
    from repro.kernels import ops
    prof = JitProfiler()
    originals = {n: getattr(ops, n) for n in prof.KERNEL_OPS
                 if hasattr(ops, n)}
    assert originals, "expected kernel ops to wrap"
    restore = prof.wrap_kernel_ops()
    try:
        for n in originals:
            assert getattr(ops, n).__wrapped__ is originals[n]
            if hasattr(kernels, n):
                assert getattr(kernels, n).__wrapped__ is originals[n]
    finally:
        restore()
    for n, fn in originals.items():
        assert getattr(ops, n) is fn


# ---------------------------------------------------------------------------
# RunMonitor as a view over the registry


def test_run_monitor_is_thin_view_over_registry():
    from repro.core.metrics import ToolEvent
    from repro.serving.engine import RunMonitor
    mon = RunMonitor()
    mon(RunStarted(t=0.0, pattern="agentx", task="t", tenant="acme"))
    mon(LLMCompleted(t=1.0, event=LLMEvent("executor", 100, 50, 1.0, 1.0)))
    mon(ToolInvoked(t=2.0, event=ToolEvent("serper", "google_search",
                                           0.5, False, 2.0)))
    mon(RunCompleted(t=3.0, completed=True, data=None))
    assert mon.runs_started == 1 and mon.runs_completed == 1
    assert mon.llm_calls == 1 and mon.calls_per_agent == {"executor": 1}
    assert mon.input_tokens == 100 and mon.output_tokens == 50
    assert mon.tool_calls == 1 and mon.tool_errors == 1
    assert mon.in_flight == 0
    assert mon.tenants["acme"]["llm_calls"] == 1
    assert mon.tenants["acme"]["tokens"] == 150
    # the same fold is live on the wrapped registry, export-ready
    text = render_prometheus(mon.registry)
    assert 'repro_llm_calls_total{agent="executor"} 1' in text
    snap = mon.snapshot()
    assert snap["runs_started"] == 1
    assert snap["tenants"]["acme"]["completed"] == 1
