"""Wire back-compat for the RunEvent protocol.

Round-trips EVERY registered event type through ``to_wire``/``from_wire``
(including the traffic-PR ``ToolRetried``/``RunHedged`` and the
scheduler-v2 extended ``EngineStepped``), and pins the two compat
directions: OLDER wire payloads missing newer fields deserialize via
defaults, NEWER wire payloads carrying unknown fields are tolerated.
"""
import dataclasses

import pytest

from repro.core.events import (_EVENT_TYPES, MIN_WIRE_VERSION, WIRE_VERSION,
                               BudgetExceeded, EngineStepped, LLMCompleted,
                               OverheadIncurred, PlanCacheMiss, PlanCompiled,
                               PlanFallback, PlanProduced, ReflectionEmitted,
                               RunCompleted, RunDegraded, RunHedged,
                               RunStarted, SloAlertFired, StageCompleted,
                               StageStarted, ToolInvoked, ToolRetried,
                               WireVersionError, derive_trace,
                               events_from_wire, events_to_wire, from_wire,
                               to_wire)
from repro.core.metrics import FrameworkEvent, LLMEvent, ToolEvent

# one concrete instance of every wire-registered event type
SAMPLES = [
    RunStarted(t=0.0, pattern="agentx", task="do the thing"),
    StageStarted(t=1.0, index=0, name="search"),
    PlanProduced(t=1.5, index=0, plan={"steps": [{"tool": "google_search"}]}),
    LLMCompleted(t=2.0, event=LLMEvent("executor", 100, 20, 1.2, 2.0)),
    ToolInvoked(t=3.0, event=ToolEvent("serper", "google_search", 0.8,
                                       True, 3.0,
                                       args={"query": "q", "num_results": 8},
                                       result='{"organic": []}')),
    OverheadIncurred(t=3.5, event=FrameworkEvent("plan", 0.18, 3.5)),
    ReflectionEmitted(t=4.0, index=0, reflection={"success": True}),
    StageCompleted(t=4.5, index=0, success=True),
    ToolRetried(t=5.0, server="serper", tool="google_search", attempt=1,
                error="<tool-error ...: transient: injected>",
                backoff_s=0.5),
    RunHedged(t=5.5, server="fetch", tool="fetch", winner="hedge",
              primary_s=12.0, hedge_s=1.0, saved_s=3.0),
    RunCompleted(t=6.0, completed=True, data={"summaries": ["ok"]}),
    PlanCompiled(t=6.2, key="ab12" * 16, template="Search for {var} ...",
                 stages=3, nodes=5, dyn_nodes=1),
    PlanCacheMiss(t=6.3, key="ab12" * 16),
    PlanFallback(t=6.4, key="ab12" * 16, reason="node-failed:fetch",
                 stage=1),
    EngineStepped(t=7.0, live=3, queued=2, generated=3, prefilled=64,
                  preempted=1, blocks_in_use=12, prefix_hits=2,
                  prefill_rows=96),
    RunDegraded(t=0.0, tenant="acme", reason="soft budget exhaustion",
                from_pattern="agentx", to_pattern="agentx-compiled",
                from_deployment="faas", to_deployment="local"),
    BudgetExceeded(t=0.0, tenant="acme", kind="tokens", used=1_000_001.0,
                   budget=1_000_000.0),
    SloAlertFired(t=120.0, slo="success", window_start=60.0, window_s=60.0,
                  burn_rate=5.0, threshold=2.0, bad=2, total=4,
                  target=0.9),
]


def test_every_registered_type_has_a_sample():
    assert {type(s).__name__ for s in SAMPLES} == set(_EVENT_TYPES)


@pytest.mark.parametrize("event", SAMPLES,
                         ids=[type(s).__name__ for s in SAMPLES])
def test_roundtrip(event):
    wire = to_wire(event)
    assert wire["type"] == type(event).__name__
    back = from_wire(wire)
    assert back == event


def test_stream_roundtrip_and_trace():
    wire = events_to_wire(SAMPLES)
    back = events_from_wire(wire)
    assert back == SAMPLES
    trace = derive_trace(back)
    assert trace.llm_events and trace.tool_events and trace.framework_events


@pytest.mark.parametrize("event", SAMPLES,
                         ids=[type(s).__name__ for s in SAMPLES])
def test_unknown_wire_fields_tolerated(event):
    """A NEWER peer may attach fields we don't know — they must be
    dropped, not raised on (forward compat)."""
    wire = to_wire(event)
    wire["future_gauge"] = 123
    wire["another_new_field"] = {"nested": True}
    if isinstance(wire.get("event"), dict):
        wire["event"] = dict(wire["event"], future_nested_field=4.2)
    assert from_wire(wire) == event


def test_missing_newer_fields_default():
    """An OLDER peer's payload (pre-v2 EngineStepped without the
    admission gauges) still deserializes."""
    old = {"type": "EngineStepped", "t": 1.0, "live": 2, "queued": 0,
           "generated": 2}
    ev = from_wire(old)
    assert ev.prefilled == 0 and ev.preempted == 0


def test_pre_paging_enginestepped_payload_defaults():
    """A pre-paging EngineStepped payload (no paged-KV gauges) still
    deserializes — blocks_in_use/prefix_hits default to 0, which is
    exactly what the contiguous scheduler emits."""
    old = {"type": "EngineStepped", "t": 1.0, "live": 2, "queued": 0,
           "generated": 2, "prefilled": 16, "preempted": 0}
    ev = from_wire(old)
    assert ev.blocks_in_use == 0 and ev.prefix_hits == 0


def test_pre_prefill_rows_enginestepped_payload_defaults():
    """An EngineStepped payload from before the prefill-row counter
    still deserializes, with no rows counted."""
    old = {"type": "EngineStepped", "v": 2, "t": 1.0, "live": 2,
           "queued": 0, "generated": 2, "prefilled": 16, "preempted": 0,
           "blocks_in_use": 3, "prefix_hits": 1}
    ev = from_wire(old)
    assert ev.prefill_rows == 0 and ev.prefilled == 16
    assert to_wire(ev)["prefill_rows"] == 0


def test_pre_plan_toolevent_payload_defaults():
    """A pre-plan-PR ToolInvoked payload (no args/result on the nested
    ToolEvent) still deserializes — the plan-compiler fields default."""
    old = {"type": "ToolInvoked", "t": 3.0,
           "event": {"server": "serper", "tool": "google_search",
                     "latency": 0.8, "ok": True, "t": 3.0}}
    ev = from_wire(old)
    assert ev.event.args is None and ev.event.result is None


def test_pre_tenancy_runstarted_payload_defaults():
    """A pre-tenancy RunStarted payload (no ``tenant`` field) still
    deserializes — the tenant defaults to the single default tenant."""
    old = {"type": "RunStarted", "t": 0.0, "pattern": "agentx",
           "task": "do the thing"}
    ev = from_wire(old)
    assert ev.tenant == ""


def test_tenant_stamped_runstarted_roundtrips():
    ev = RunStarted(t=0.0, pattern="react", task="t", tenant="acme")
    assert from_wire(to_wire(ev)) == ev
    assert to_wire(ev)["tenant"] == "acme"


def test_unknown_type_raises():
    with pytest.raises(KeyError):
        from_wire({"type": "NotARealEvent", "t": 0.0})


def test_new_events_have_json_safe_wire():
    import json
    for ev in (SAMPLES[8], SAMPLES[9]):   # ToolRetried, RunHedged
        assert json.loads(json.dumps(to_wire(ev))) == to_wire(ev)


def test_wire_fields_are_dataclass_fields():
    """to_wire emits exactly the dataclass fields + 'type' + the schema
    version stamp 'v' — the contract _known_fields filtering rests on."""
    for ev in SAMPLES:
        wire = to_wire(ev)
        names = {f.name for f in dataclasses.fields(ev)}
        assert set(wire) == names | {"type", "v"}


# -- explicit wire-schema versioning (durable-journal PR) -------------------


def test_wire_version_stamped():
    for ev in SAMPLES:
        assert to_wire(ev)["v"] == WIRE_VERSION


def test_old_stamped_payload_raises():
    """A payload stamped with a pre-MIN_WIRE_VERSION schema is rejected
    up front — never mis-parsed field by field."""
    wire = to_wire(SAMPLES[0])
    wire["v"] = MIN_WIRE_VERSION - 1
    with pytest.raises(WireVersionError):
        from_wire(wire)


def test_unstamped_payload_tolerated():
    """Pre-versioning payloads carry no 'v' at all — they predate the
    stamp, not the schema floor, and must keep deserializing."""
    wire = to_wire(SAMPLES[0])
    del wire["v"]
    assert from_wire(wire) == SAMPLES[0]


def test_newer_stamped_payload_tolerated():
    """A NEWER peer's stamp is fine: unknown fields drop, known fields
    parse (same forward-compat rule as unknown wire fields)."""
    wire = to_wire(SAMPLES[0])
    wire["v"] = WIRE_VERSION + 7
    wire["field_from_the_future"] = 1
    assert from_wire(wire) == SAMPLES[0]


def test_wire_version_error_is_value_error():
    """Callers already catching ValueError on corrupt payloads keep
    working."""
    assert issubclass(WireVersionError, ValueError)


# -- telemetry PR: the SLO alert event --------------------------------------


def test_slo_alert_roundtrips_and_is_json_safe():
    import json
    ev = SloAlertFired(t=120.0, slo="latency", window_start=60.0,
                       window_s=60.0, burn_rate=3.5, threshold=2.0,
                       bad=7, total=20, target=120.0)
    wire = to_wire(ev)
    assert json.loads(json.dumps(wire)) == wire
    assert from_wire(wire) == ev


def test_pre_telemetry_peer_alert_payload_forward_compat():
    """A NEWER monitor may stamp extra alert context (e.g. a runbook
    URL) — a pre-telemetry-schema peer must drop it, not raise, and the
    known burn-rate fields must survive the trip."""
    ev = SloAlertFired(t=60.0, slo="success", window_start=0.0,
                       window_s=60.0, burn_rate=10.0, threshold=2.0,
                       bad=6, total=6, target=0.9)
    wire = to_wire(ev)
    wire["runbook_url"] = "https://example.invalid/runbooks/slo-burn"
    wire["severity"] = "page"
    back = from_wire(wire)
    assert back == ev
    assert back.burn_rate == 10.0 and back.bad == 6


def test_run_monitor_snapshot_gauges_on_paged_backend():
    """RunMonitor (now a thin view over the telemetry registry) must
    keep its historical snapshot() keys populated when subscribed to the
    paged serving backend's EngineStepped stream."""
    from repro.serving import get_llm_backend, reset_llm_backends
    from repro.serving.engine import RunMonitor

    reset_llm_backends()
    try:
        backend = get_llm_backend("jax-batched-paged")
        monitor = RunMonitor()
        backend.subscribe(monitor)      # before the client exists
        out = backend.client().generate("count to three", 6)
        assert out.new_tokens > 0
        snap = monitor.snapshot()
        assert snap["engine_steps"] > 0
        # prefill yields the first token; decode steps produce the rest
        assert snap["engine_tokens"] >= out.new_tokens - 1
        assert snap["engine_prefill_tokens"] > 0
        assert snap["engine_peak_live"] >= 1
        assert snap["engine_blocks_in_use"] >= 0
        # the same numbers must be live on the registry the monitor wraps
        assert monitor.registry.total("repro_engine_steps_total") == \
            snap["engine_steps"]
    finally:
        reset_llm_backends()
