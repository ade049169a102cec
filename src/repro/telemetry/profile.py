"""Profiling hooks for the jitted hot paths.

``JitProfiler.wrap(name, fn)`` returns a drop-in callable that times
every call (wall seconds, synchronized via ``jax.block_until_ready`` so
async dispatch doesn't hide the work) and counts *compiles*: a call
whose abstract signature — array shapes/dtypes plus static kwargs — has
not been seen before triggers a trace+compile in jax, so first-seen
signatures are counted as compiles (cross-checked against the jit
cache's ``_cache_size`` when the wrapped function exposes it).

The wrapper changes WHEN the python thread resumes, never WHAT the
computation returns — profiled engines stay bit-identical to bare ones
(the parity suite runs both ways).  Wall times are inherently
nondeterministic, which is why the profiler keeps its OWN registry by
default: the deterministic bridge registry can be byte-compared across
replays while profile stats ride in a separate export/section.

``wrap_engine`` hooks the serving engine's jitted members in place
(``decode_step``, the fixed-shape prefill behind ``prefill_batch_ids``,
the extend/chunk path, exact prefill).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from .metrics import MetricsRegistry, log_buckets

# call-time buckets: 10µs .. 5s
JIT_CALL_BUCKETS = tuple(log_buckets(1e-5, 6))


def _signature(args, kwargs) -> tuple:
    """Abstract signature of one call: shapes/dtypes for array-likes,
    values for hashable statics, type names otherwise."""
    def one(v):
        shape = getattr(v, "shape", None)
        dtype = getattr(v, "dtype", None)
        if shape is not None and dtype is not None:
            return ("arr", tuple(shape), str(dtype))
        if isinstance(v, dict):
            return ("dict", tuple((k, one(v[k])) for k in sorted(v)))
        if isinstance(v, (list, tuple)):
            return ("seq", tuple(one(x) for x in v))
        if isinstance(v, (bool, int, float, str, type(None))):
            return ("lit", v)
        return ("type", type(v).__name__)
    return (tuple(one(a) for a in args),
            tuple((k, one(kwargs[k])) for k in sorted(kwargs)))


class JitProfile:
    """Stats for one wrapped function."""

    __slots__ = ("name", "calls", "compiles", "total_s", "min_s", "max_s",
                 "last_s", "_signatures")

    def __init__(self, name: str):
        self.name = name
        self.calls = 0
        self.compiles = 0
        self.total_s = 0.0
        self.min_s = float("inf")
        self.max_s = 0.0
        self.last_s = 0.0
        self._signatures: set = set()

    def as_dict(self) -> Dict[str, Any]:
        return {
            "calls": self.calls,
            "compiles": self.compiles,
            "total_s": self.total_s,
            "avg_ms": (self.total_s / self.calls * 1e3) if self.calls
            else 0.0,
            "min_ms": (self.min_s * 1e3) if self.calls else 0.0,
            "max_ms": self.max_s * 1e3,
        }


class JitProfiler:
    """Owns the profiles plus the metric families they feed.

    ``registry`` defaults to a fresh private one (see module docstring);
    pass a shared registry to co-locate profile series with other
    metrics when byte-determinism of that registry is not required."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self._clock = clock
        self._lock = threading.Lock()
        self.profiles: Dict[str, JitProfile] = {}
        self._calls = self.registry.counter(
            "repro_jit_calls_total", "Profiled jit executions, by fn")
        self._compiles = self.registry.counter(
            "repro_jit_compiles_total",
            "Traces compiled (first-seen call signatures), by fn")
        self._seconds = self.registry.histogram(
            "repro_jit_call_seconds", "Per-call wall time, by fn",
            unit="s", buckets=JIT_CALL_BUCKETS)

    def profile(self, name: str) -> JitProfile:
        with self._lock:
            p = self.profiles.get(name)
            if p is None:
                p = self.profiles[name] = JitProfile(name)
            return p

    def wrap(self, name: str, fn: Callable) -> Callable:
        """Profiled drop-in for ``fn``; the original stays reachable as
        ``wrapper.__wrapped__``.  A failing ``block_until_ready``
        propagates: it is the device's error, not the profiler's."""
        import jax   # lazy: the telemetry package stays importable jax-free
        prof = self.profile(name)
        cache_size = getattr(fn, "_cache_size", None)

        def wrapper(*args, **kwargs):
            sig = _signature(args, kwargs)
            before = cache_size() if callable(cache_size) else None
            t0 = self._clock()
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
            dt = self._clock() - t0
            with self._lock:
                prof.calls += 1
                prof.total_s += dt
                prof.last_s = dt
                prof.min_s = min(prof.min_s, dt)
                prof.max_s = max(prof.max_s, dt)
                compiled = False
                if before is not None:
                    after = cache_size()
                    compiled = after > before
                    # keep the signature set in sync either way
                    prof._signatures.add(sig)
                elif sig not in prof._signatures:
                    prof._signatures.add(sig)
                    compiled = True
                if compiled:
                    prof.compiles += 1
            self._calls.inc(fn=name)
            if compiled:
                self._compiles.inc(fn=name)
            self._seconds.observe(dt, fn=name)
            return out

        wrapper.__wrapped__ = fn
        # jitted callables already expose __wrapped__ (the undecorated
        # python fn), so idempotency checks use this marker instead
        wrapper._jit_profiled = True
        wrapper.__name__ = f"profiled_{name}"
        return wrapper

    # -- hot-path hookups ----------------------------------------------------
    ENGINE_MEMBERS = (
        ("_decode", "decode_step"),
        ("_prefill_fixed", "prefill_batch_ids"),
        ("_prefill_extend", "prefill_extend"),
        ("_prefill", "prefill_exact"),
    )

    def wrap_engine(self, engine) -> None:
        """Hook the serving engine's jitted members in place.  Idempotent
        per engine (re-wrapping an already-profiled member is skipped)."""
        for attr, name in self.ENGINE_MEMBERS:
            fn = getattr(engine, attr, None)
            if fn is None or getattr(fn, "_jit_profiled", False):
                continue
            setattr(engine, attr, self.wrap(name, fn))

    # -- summaries -----------------------------------------------------------
    def stats(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {name: p.as_dict()
                    for name, p in sorted(self.profiles.items())}

    def table(self) -> List[str]:
        """Aligned text table (launchers print it)."""
        rows = self.stats()
        if not rows:
            return ["  (no profiled jit calls)"]
        head = (f"  {'fn':<22}{'calls':>8}{'compiles':>10}"
                f"{'avg ms':>10}{'max ms':>10}{'total s':>10}")
        out = [head]
        for name, s in rows.items():
            out.append(f"  {name:<22}{s['calls']:>8}{s['compiles']:>10}"
                       f"{s['avg_ms']:>10.3f}{s['max_ms']:>10.3f}"
                       f"{s['total_s']:>10.3f}")
        return out
