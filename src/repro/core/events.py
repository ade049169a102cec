"""Typed run-event stream emitted by every workflow pattern.

All patterns execute through :class:`repro.core.runtime.AgentRuntime`,
which emits one :class:`RunEvent` per orchestration step (stage dispatch,
plan, tool invocation, reflection, ...). Observers — the experiment
harness, ``benchmarks/figures.py``, the serving-side
:class:`repro.serving.engine.RunMonitor` — subscribe via
``Session(on_event=...)`` or ``AgentRuntime.subscribe`` and see runs
*live* instead of post-hoc.

``Trace`` is derived from the stream: :func:`derive_trace` rebuilds the
full accounting trace (LLM / tool / framework events) from an event list,
and the runtime keeps its ``Trace`` in sync by reducing every emitted
event into it.

Events also cross process boundaries: :func:`to_wire` / :func:`from_wire`
serialize any event to a JSON-safe dict and back, so FaaS / A2A response
envelopes can carry the full event stream of a remotely executed run and
a local observer (e.g. ``RunMonitor``) sees exactly what an in-process
subscriber would.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from .metrics import FrameworkEvent, LLMEvent, ToolEvent, Trace


@dataclasses.dataclass(frozen=True)
class RunEvent:
    """Base class: ``t`` is the virtual-clock timestamp of emission."""
    t: float


@dataclasses.dataclass(frozen=True)
class RunStarted(RunEvent):
    """``tenant`` is the principal the run is billed to (multi-tenant
    serving, :mod:`repro.tenancy`); ``""`` is the single default tenant
    — pre-tenancy wire payloads deserialize to it."""
    pattern: str
    task: str
    tenant: str = ""


@dataclasses.dataclass(frozen=True)
class StageStarted(RunEvent):
    index: int
    name: str


@dataclasses.dataclass(frozen=True)
class PlanProduced(RunEvent):
    index: int
    plan: Any


@dataclasses.dataclass(frozen=True)
class LLMCompleted(RunEvent):
    event: LLMEvent


@dataclasses.dataclass(frozen=True)
class ToolInvoked(RunEvent):
    event: ToolEvent


@dataclasses.dataclass(frozen=True)
class OverheadIncurred(RunEvent):
    event: FrameworkEvent


@dataclasses.dataclass(frozen=True)
class ReflectionEmitted(RunEvent):
    index: int
    reflection: Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class StageCompleted(RunEvent):
    index: int
    success: bool


@dataclasses.dataclass(frozen=True)
class RunCompleted(RunEvent):
    completed: bool
    data: Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class ToolRetried(RunEvent):
    """A tool invocation failed with a *retryable* error (fault-injected
    transient failure, throttling — see :mod:`repro.traffic.faults`) and
    the runtime's :class:`repro.core.policies.RetryPolicy` re-dispatched
    it after ``backoff_s`` of virtual time.  ``attempt`` is the 1-based
    index of the attempt that FAILED, so a call that succeeds on its
    third try emits two ``ToolRetried`` events (attempts 1 and 2)."""
    server: str
    tool: str
    attempt: int
    error: str
    backoff_s: float


@dataclasses.dataclass(frozen=True)
class RunHedged(RunEvent):
    """A slow tool invocation was hedged: the runtime's
    :class:`repro.core.policies.HedgePolicy` issued a backup call at
    ``hedge_after_s`` into the primary's flight and took whichever
    finished first.  ``winner`` is ``"primary"`` or ``"hedge"``;
    ``saved_s`` is the virtual latency the hedge shaved off the
    primary's completion time (0.0 when the primary won)."""
    server: str
    tool: str
    winner: str
    primary_s: float
    hedge_s: float
    saved_s: float


@dataclasses.dataclass(frozen=True)
class PlanCompiled(RunEvent):
    """A successful run's trace was compiled into a :class:`PlanGraph`
    (:mod:`repro.plans.compile`) and stored in the session's plan cache
    under ``key`` (the app/task-template fingerprint).  ``stages`` /
    ``nodes`` describe the graph; ``dyn_nodes`` counts the nodes whose
    arguments could not be bound statically and still need an executor
    LLM call on replay."""
    key: str
    template: str
    stages: int
    nodes: int
    dyn_nodes: int = 0


@dataclasses.dataclass(frozen=True)
class PlanCacheMiss(RunEvent):
    """The session looked for a compiled plan under ``key`` and found
    none — this run executes with full AgentX planning (and compiles a
    graph on success)."""
    key: str


@dataclasses.dataclass(frozen=True)
class PlanFallback(RunEvent):
    """A compiled-plan replay deviated (node failure, tool mismatch,
    template mismatch — ``reason``) at stage ``stage`` and the session
    fell back to full AgentX re-planning.  Emitted on the FALLBACK run's
    stream, before its ``RunStarted``."""
    key: str
    reason: str
    stage: int = -1


@dataclasses.dataclass(frozen=True)
class RunDegraded(RunEvent):
    """A tenant's soft budget exhaustion downgraded this run to a cheaper
    configuration before execution (:class:`repro.tenancy.DegradePolicy`):
    ``from_pattern``/``to_pattern`` and ``from_deployment``/
    ``to_deployment`` describe the swap (equal when that axis kept its
    value).  Emitted on the degraded run's stream BEFORE its
    ``RunStarted`` — the decision is part of the run's billed history."""
    tenant: str
    reason: str
    from_pattern: str
    to_pattern: str
    from_deployment: str
    to_deployment: str


@dataclasses.dataclass(frozen=True)
class BudgetExceeded(RunEvent):
    """A tenant's hard budget exhaustion rejected this run outright —
    no world is built, nothing executes, nothing is billed.  ``kind`` is
    the exhausted axis (``"tokens"`` | ``"cost"``), ``used``/``budget``
    the meter reading at rejection time."""
    tenant: str
    kind: str
    used: float
    budget: float


@dataclasses.dataclass(frozen=True)
class SloAlertFired(RunEvent):
    """Telemetry-side alert: an :class:`repro.telemetry.SloMonitor`
    window burned error budget faster than its threshold.  ``slo`` names
    the objective (``"success"`` | ``"latency"`` | ``"ttft"``),
    ``burn_rate`` the window's error rate divided by the SLO's error
    budget (1.0 = burning exactly at budget), ``bad``/``total`` the
    window's violating/observed run counts, and ``target`` the SLO value
    the objective was checked against.  ``t`` is the end of the
    (virtual-clock-aligned) window, so replaying a workload re-fires the
    identical alerts at the identical instants."""
    slo: str
    window_start: float
    window_s: float
    burn_rate: float
    threshold: float
    bad: int
    total: int
    target: float


@dataclasses.dataclass(frozen=True)
class EngineStepped(RunEvent):
    """Serving-side event: the continuous-batching scheduler advanced all
    live decode slots by one step.  Emitted by the *engine*, not a run —
    ``t`` carries the scheduler's monotonic step counter (the engine has
    no virtual clock; it serves many runs/worlds at once).  ``live`` is
    the decode-batch occupancy during the step, ``queued`` the number of
    requests still waiting for a slot, and ``generated`` how many tokens
    this step produced (== ``live``).

    Scheduler-v2 admission gauges (default 0, so pre-v2 wire payloads
    still deserialize): ``prefilled`` counts the prompt tokens prefilled
    during the step's admission phase (bucketed batches, one chunk of a
    chunked admission, or a preemption-resume replay), ``preempted`` the
    number of live slots evicted for a higher-priority request.

    Paged-KV gauges (default 0, so pre-paging wire payloads still
    deserialize — and the contiguous-cache scheduler emits exactly the
    pre-paging payload): ``blocks_in_use`` is the block allocator's
    occupancy after the step, ``prefix_hits`` how many admissions this
    step reused cached prefix blocks.

    ``prefill_rows`` (default 0, so older payloads still deserialize)
    counts the rows the step's prefill programs computed, padding
    included (``Engine.prefill_rows``); ``prefilled`` counts only the
    real tokens among them."""
    live: int
    queued: int
    generated: int
    prefilled: int = 0
    preempted: int = 0
    blocks_in_use: int = 0
    prefix_hits: int = 0
    prefill_rows: int = 0


# ---------------------------------------------------------------------------
# wire protocol

# Explicit wire-schema version, stamped on every ``to_wire`` payload as
# ``"v"``.  Bump WIRE_VERSION on any *semantic* change to event payloads
# (a renamed field, changed units, changed truncation); raise
# MIN_WIRE_VERSION when the change is incompatible enough that older
# stamped payloads must be REJECTED rather than parsed-with-defaults.
# Durable journal segments (:mod:`repro.durable.journal`) additionally
# carry the version in their header, so a whole segment from an older
# schema is detected up front instead of mis-parsed event by event.
#
# v2 == the schema as of the plan-compiler PR (ToolEvent carries
# args/result); unstamped payloads (written before versioning existed)
# are treated as v-unknown and parsed with the historical tolerant
# behavior.
WIRE_VERSION = 2
MIN_WIRE_VERSION = 2


class WireVersionError(ValueError):
    """A stamped wire payload predates :data:`MIN_WIRE_VERSION` — its
    field semantics can no longer be trusted, so it must be rejected
    (detected), not silently parsed with defaults."""


_EVENT_TYPES: Dict[str, type] = {
    cls.__name__: cls
    for cls in (RunStarted, StageStarted, PlanProduced, LLMCompleted,
                ToolInvoked, OverheadIncurred, ReflectionEmitted,
                StageCompleted, RunCompleted, ToolRetried, RunHedged,
                PlanCompiled, PlanCacheMiss, PlanFallback, EngineStepped,
                RunDegraded, BudgetExceeded, SloAlertFired)
}

# events whose ``event`` field is a nested metrics dataclass
_NESTED_EVENT: Dict[str, type] = {
    "LLMCompleted": LLMEvent,
    "ToolInvoked": ToolEvent,
    "OverheadIncurred": FrameworkEvent,
}


def _jsonable(value: Any) -> Any:
    """Best-effort JSON sanitization: payloads (plans, outcome data) are
    JSON-shaped in practice; anything exotic degrades to ``repr``."""
    if isinstance(value, dict):
        return {str(k): _jsonable(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_jsonable(v) for v in value]
    if value is None or isinstance(value, (bool, int, float, str)):
        return value
    return repr(value)


def to_wire(event: RunEvent) -> Dict[str, Any]:
    """Serialize one event to a JSON-safe dict (``type`` + ``v`` +
    fields)."""
    d = _jsonable(dataclasses.asdict(event))
    d["type"] = type(event).__name__
    d["v"] = WIRE_VERSION
    return d


def _known_fields(cls: type, d: Dict[str, Any]) -> Dict[str, Any]:
    """Drop wire fields the local dataclass doesn't know: a NEWER peer
    (remote orchestrator Lambda, disk cache written by a later version)
    may attach extra gauges; tolerating them keeps the wire protocol
    forward-compatible (missing fields still need defaults, as with
    ``EngineStepped``'s v2 gauges)."""
    known = {f.name for f in dataclasses.fields(cls)}
    return {k: v for k, v in d.items() if k in known}


def from_wire(d: Dict[str, Any]) -> RunEvent:
    """Inverse of :func:`to_wire`.

    Raises ``KeyError`` on unknown type and :class:`WireVersionError`
    on a payload stamped with a schema older than
    :data:`MIN_WIRE_VERSION`; unknown *fields* of a known type are
    ignored (forward compat — a NEWER peer's extra gauges parse fine),
    and unstamped payloads (pre-versioning) keep the historical
    tolerant behavior."""
    d = dict(d)
    v = d.pop("v", None)
    if v is not None and v < MIN_WIRE_VERSION:
        raise WireVersionError(
            f"wire payload schema v{v} predates the oldest supported "
            f"schema v{MIN_WIRE_VERSION} (current v{WIRE_VERSION})")
    name = d.pop("type")
    try:
        cls = _EVENT_TYPES[name]
    except KeyError:
        raise KeyError(f"unknown RunEvent type {name!r}; known: "
                       f"{sorted(_EVENT_TYPES)}") from None
    d = _known_fields(cls, d)
    nested = _NESTED_EVENT.get(name)
    if nested is not None:
        d["event"] = nested(**_known_fields(nested, d["event"]))
    return cls(**d)


def events_to_wire(events: List[RunEvent]) -> List[Dict[str, Any]]:
    return [to_wire(e) for e in events]


def events_from_wire(wire: List[Dict[str, Any]]) -> List[RunEvent]:
    return [from_wire(d) for d in wire]


def reduce_into_trace(event: RunEvent, trace: Trace) -> None:
    """Fold one event into a Trace. ``LLMCompleted`` is a no-op because the
    LLM backend appends to the shared Trace itself (it also serves callers
    that bypass the runtime)."""
    if isinstance(event, ToolInvoked):
        trace.tool_events.append(event.event)
    elif isinstance(event, OverheadIncurred):
        trace.framework_events.append(event.event)


def derive_trace(events: List[RunEvent]) -> Trace:
    """Rebuild the full accounting Trace from an event stream."""
    trace = Trace()
    for ev in events:
        if isinstance(ev, LLMCompleted):
            trace.llm_events.append(ev.event)
        else:
            reduce_into_trace(ev, trace)
    return trace
