"""Serving engine: batched prefill + decode with per-arch cache handling.

The engine backs the ``@register_llm_backend`` serving backends
(:mod:`repro.serving.api`) — the agents' LLM endpoint — and the
serving-side benchmarks. Request flow mirrors production servers:
tokenize -> prefill (cache warm-up) -> sampled decode loop -> detokenize,
with a slot-based continuous-batching scheduler in ``scheduler.py`` that
multiplexes many concurrent requests onto one jitted ``decode_step``.

Sampling is keyed by ``(engine seed, request id, step)`` — never by
shared mutable RNG state — so a request samples the identical token
sequence whether it runs alone, serially after other requests, or inside
a decode batch (and the engine is thread-safe).
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core import events as run_events
from ..data.tokenizer import HashTokenizer
from ..models.model import (decode_step, init_cache, prefill, prefill_attend,
                            prefill_fresh)
from ..models.params import init_params


def prefill_bucket(n: int, floor: int = 8) -> int:
    """Power-of-two length bucket for an ``n``-token prompt (min
    ``floor``).  Admission pads prompts to their bucket so one jitted
    prefill trace serves every length in it — the lever that removes
    per-length recompiles from the admission path."""
    return max(floor, 1 << max(n - 1, 0).bit_length())


def _named(fn, **bound):
    """``functools.partial(fn, **bound)`` under ``fn``'s own name: jit
    names its program (and the profiler that program's device ops) after
    ``__name__``, which a bare partial lacks (``jit__unknown``)."""
    p = functools.partial(fn, **bound)
    p.__name__ = p.__qualname__ = fn.__name__
    return p


def cache_leaf_name(path) -> Optional[str]:
    """Name of a cache leaf ("k"/"v"/"ckv"/"kpe"/"conv"/"ssd") from its
    tree path — shared by seq-axis padding here and the slot-batch row
    writes in ``scheduler.write_slot``."""
    for p in reversed(path):
        if hasattr(p, "key"):
            return p.key
    return None


def pad_cache_to(cfg: ModelConfig, cache, target_len: int):
    """Grow a prefill cache (len S) to ``target_len`` along the seq axis.
    SSM states are length-free; sliding-window caches are re-rolled into
    ring layout."""
    window = cfg.sliding_window

    def pad(path, x):
        name = cache_leaf_name(path)
        if name not in ("k", "v", "ckv", "kpe"):
            return x
        seq_axis = x.ndim - 3 if name in ("k", "v") else x.ndim - 2
        s = x.shape[seq_axis]
        if window and s > window:
            # keep last `window` rows in ring layout: row p -> slot p%window
            idx = jnp.arange(s - window, s)
            slots = idx % window
            sl = [slice(None)] * x.ndim
            sl[seq_axis] = idx
            vals = x[tuple(sl)]
            out = jnp.zeros(x.shape[:seq_axis] + (window,) + x.shape[seq_axis + 1:],
                            x.dtype)
            order = jnp.argsort(slots)
            sl2 = [slice(None)] * x.ndim
            sl2[seq_axis] = slots[order]
            sl3 = [slice(None)] * x.ndim
            sl3[seq_axis] = order
            return out.at[tuple(sl2)].set(vals[tuple(sl3)])
        target = min(target_len, window) if window else target_len
        if s >= target:
            return x
        widths = [(0, 0)] * x.ndim
        widths[seq_axis] = (0, target - s)
        return jnp.pad(x, widths)

    return jax.tree_util.tree_map_with_path(pad, cache)


@dataclasses.dataclass
class GenerationResult:
    text: str
    prompt_tokens: int
    new_tokens: int
    token_ids: List[int]


class RunMonitor:
    """Live serving-side observer of agent runs.

    Subscribe it to the orchestration event stream
    (``Session(on_event=RunMonitor())``) and it aggregates in-flight
    demand on the serving engine — LLM calls, token volume, tool and
    framework activity — *while* runs execute, instead of post-hoc trace
    mining. Thread-safe: ``Session.execute_many`` delivers events from
    worker threads.

    Since the telemetry subsystem landed, the monitor is a *thin view*
    over a :class:`repro.telemetry.MetricsRegistry`: every event is
    folded by an :class:`repro.telemetry.EventMetricsBridge` and the
    historical counters (``runs_started``, ``engine_steps``, ...) are
    read-only properties derived from the registry's series, so the
    same numbers are available as Prometheus/OTLP exports via
    ``monitor.registry`` with zero double counting.  The public surface
    — attribute names, ``snapshot()`` keys, ``wire_observer()`` — is
    unchanged.

    ``runs_succeeded`` counts pattern-level completion
    (``RunCompleted.completed``); artifact location and judge gating
    happen after the run, so it can exceed the number of runs whose
    ``RunResult.success`` is True.

    Subscribe it to a :class:`BatchScheduler` too
    (``BatchScheduler(..., on_event=monitor)`` or
    ``scheduler.subscribe(monitor)``) and the serving-side
    ``EngineStepped`` stream keeps live engine-occupancy gauges:
    decode-batch fill, queue depth, tokens decoded.

    Per-tenant gauges (multi-tenant serving): ``RunStarted.tenant``
    opens a run's billing context — a run's events all arrive on the
    thread executing it, so the current tenant is tracked thread-locally
    between ``RunStarted`` and ``RunCompleted`` — and the admission
    events (``RunDegraded`` / ``BudgetExceeded``) carry their tenant
    explicitly.  ``tenants`` maps tenant -> {runs, completed, llm_calls,
    tokens, cost_usd, degraded, rejected}.
    """

    # tenant label values are unioned across these families so a tenant
    # seen only at admission (degraded/rejected before any run) still
    # gets a gauge row, exactly like the pre-registry monitor
    _TENANT_FAMILIES = (
        "repro_tenant_runs_total", "repro_tenant_completed_total",
        "repro_tenant_llm_calls_total", "repro_tenant_tokens_total",
        "repro_tenant_spend_usd_total", "repro_tenant_degraded_total",
        "repro_tenant_rejected_total")

    def __init__(self, registry=None, bridge=None):
        # lazy import: telemetry stays un-imported until a monitor (or a
        # bridge) is actually constructed — serving hot paths that never
        # attach one run the exact pre-telemetry import graph
        from ..telemetry.bridge import EventMetricsBridge
        if bridge is not None:
            self.bridge = bridge
            self.registry = bridge.registry
        else:
            self.bridge = EventMetricsBridge(registry)
            self.registry = self.bridge.registry

    def __call__(self, event) -> None:
        self.bridge(event)

    def wire_observer(self):
        """Observer accepting wire-serialized event dicts
        (``repro.core.events.to_wire``) — subscribe it where raw wire
        payloads arrive (e.g. an A2A task envelope) without deserializing
        at the call site."""
        def observe(wire_dict) -> None:
            self(run_events.from_wire(wire_dict))
        return observe

    # -- derived counters (registry reads) -----------------------------------
    def _total(self, name: str) -> int:
        return int(self.registry.total(name))

    def _gauge(self, name: str, **labels) -> int:
        g = self.registry.get(name)
        return int(g.value(**labels)) if g is not None else 0

    @property
    def runs_started(self) -> int:
        return self._total("repro_runs_started_total")

    @property
    def runs_completed(self) -> int:
        return self._total("repro_runs_completed_total")

    @property
    def runs_succeeded(self) -> int:
        c = self.registry.get("repro_runs_completed_total")
        return int(c.value(completed="true")) if c is not None else 0

    @property
    def in_flight(self) -> int:
        return self.runs_started - self.runs_completed

    @property
    def llm_calls(self) -> int:
        return self._total("repro_llm_calls_total")

    @property
    def input_tokens(self) -> int:
        c = self.registry.get("repro_llm_tokens_total")
        return int(c.value(direction="input")) if c is not None else 0

    @property
    def output_tokens(self) -> int:
        c = self.registry.get("repro_llm_tokens_total")
        return int(c.value(direction="output")) if c is not None else 0

    @property
    def tool_calls(self) -> int:
        return self._total("repro_tool_calls_total")

    @property
    def tool_errors(self) -> int:
        series = self.registry.series_values("repro_tool_calls_total")
        return int(sum(v for k, v in series.items()
                       if dict(k).get("ok") == "false"))

    @property
    def framework_events(self) -> int:
        return self._total("repro_framework_overhead_total")

    @property
    def calls_per_agent(self) -> Dict[str, int]:
        series = self.registry.series_values("repro_llm_calls_total")
        out: Dict[str, int] = {}
        for key, v in series.items():
            agent = dict(key).get("agent", "")
            out[agent] = out.get(agent, 0) + int(v)
        return out

    # serving-side gauges (EngineStepped stream)
    @property
    def engine_steps(self) -> int:
        return self._total("repro_engine_steps_total")

    @property
    def engine_live(self) -> int:
        return self._gauge("repro_engine_live")

    @property
    def engine_queued(self) -> int:
        return self._gauge("repro_engine_queue_depth")

    @property
    def engine_peak_live(self) -> int:
        return self._gauge("repro_engine_peak_live")

    @property
    def engine_tokens(self) -> int:
        return self._total("repro_engine_decode_tokens_total")

    @property
    def engine_prefill_tokens(self) -> int:
        return self._total("repro_engine_prefill_tokens_total")

    @property
    def engine_preemptions(self) -> int:
        return self._total("repro_engine_preemptions_total")

    @property
    def engine_blocks_in_use(self) -> int:
        return self._gauge("repro_engine_blocks_in_use")

    @property
    def engine_prefix_hits(self) -> int:
        return self._total("repro_engine_prefix_hits_total")

    # per-tenant gauges (multi-tenant serving)
    @property
    def tenants(self) -> Dict[str, Dict[str, Any]]:
        r = self.registry
        names = set()
        for fam in self._TENANT_FAMILIES:
            names.update(r.label_values(fam, "tenant"))
        rejected: Dict[str, int] = {}
        for key, v in r.series_values(
                "repro_tenant_rejected_total").items():
            t = dict(key).get("tenant", "")
            rejected[t] = rejected.get(t, 0) + int(v)
        spend = r.get("repro_tenant_spend_usd_total")

        def val(fam: str, tenant: str) -> int:
            m = r.get(fam)
            return int(m.value(tenant=tenant)) if m is not None else 0

        return {
            t: {
                "runs": val("repro_tenant_runs_total", t),
                "completed": val("repro_tenant_completed_total", t),
                "llm_calls": val("repro_tenant_llm_calls_total", t),
                "tokens": val("repro_tenant_tokens_total", t),
                "cost_usd": (spend.value(tenant=t, eq="1")
                             if spend is not None else 0.0),
                "degraded": val("repro_tenant_degraded_total", t),
                "rejected": rejected.get(t, 0),
            }
            for t in sorted(names)
        }

    def snapshot(self) -> Dict[str, Any]:
        # the registry RLock makes the cross-family read atomic, like
        # the single monitor lock did pre-refactor
        with self.registry._lock:
            return {
                "runs_started": self.runs_started,
                "runs_completed": self.runs_completed,
                "runs_succeeded": self.runs_succeeded,
                "in_flight": self.in_flight,
                "llm_calls": self.llm_calls,
                "input_tokens": self.input_tokens,
                "output_tokens": self.output_tokens,
                "tool_calls": self.tool_calls,
                "tool_errors": self.tool_errors,
                "framework_events": self.framework_events,
                "calls_per_agent": self.calls_per_agent,
                "engine_steps": self.engine_steps,
                "engine_live": self.engine_live,
                "engine_queued": self.engine_queued,
                "engine_peak_live": self.engine_peak_live,
                "engine_tokens": self.engine_tokens,
                "engine_prefill_tokens": self.engine_prefill_tokens,
                "engine_preemptions": self.engine_preemptions,
                "engine_blocks_in_use": self.engine_blocks_in_use,
                "engine_prefix_hits": self.engine_prefix_hits,
                "tenants": self.tenants,
            }


def _sample_row(logits: jax.Array, key: jax.Array, temperature: float,
                top_p: float) -> jax.Array:
    """Sample one token from a single (V,) logits row.

    The batched scheduler vmaps this over slot rows and the serial path
    calls it on a 1-row batch, so a request's sampled tokens are identical
    either way (given the same per-request key).
    """
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


class Engine:
    """The serving model runner: prefill + decode + keyed sampling.

    ``prefill_chunk`` > 0 enables chunked prefill as part of the
    CANONICAL prefill recipe: prompts longer than the chunk budget are
    prefilled in fixed-shape chunks (``prefill_job``) by *both* the
    serial ``generate_ids`` path and the batch scheduler's admission —
    sharing the recipe is what keeps chunked admission bit-identical to
    serial generation.

    ``prefill_rows`` counts the rows the prefill programs have computed
    since construction, padding included: ``width x bucket`` for a
    bucketed batch, the bucket of a whole prompt or a reused-prefix
    suffix, a whole chunk per chunk, the prompt length on the
    exact-length recipe, and one row per replayed decode token.  Against
    the real prompt tokens it gives the share of prefill compute spent
    on padding (``BatchScheduler`` reports each step's delta on
    ``EngineStepped.prefill_rows``).
    """

    def __init__(self, cfg: ModelConfig, params=None, seed: int = 0,
                 dtype=jnp.float32, temperature: float = 1.0,
                 top_p: float = 1.0, prefill_chunk: int = 0):
        self.cfg = cfg
        self.tokenizer = HashTokenizer(cfg.vocab_size)
        key = jax.random.key(seed)
        self.params = params if params is not None else init_params(
            cfg, key, dtype=dtype)
        self.temperature = temperature
        self.top_p = top_p
        self.prefill_chunk = int(prefill_chunk)
        self.prefill_rows = 0
        self._rows_lock = threading.Lock()
        self._prefill = jax.jit(_named(prefill, cfg=cfg))
        # fixed-shape prefill pair (bucketed whole prompts / chunks):
        # one trace per (batch, bucket, cache_len) — lengths and offset
        # are traced values, so every prompt length in a bucket shares it
        self._prefill_fixed = jax.jit(
            _named(prefill_fresh, cfg=cfg),
            static_argnames=("cache_len",))
        self._prefill_extend = jax.jit(
            _named(prefill_attend, cfg=cfg),
            donate_argnames=("cache",))
        # cache is donated: the decode loop threads it linearly, and the
        # in-place update keeps the per-step cost flat in cache size
        # (without donation XLA copies the whole slot batch every step)
        self._decode = jax.jit(_named(decode_step, cfg=cfg),
                               donate_argnames=("cache",))
        self._base_key = jax.random.key(seed + 1)
        self._sampler = None
        self._sampler_knobs = None

    def _count_rows(self, n: int) -> None:
        with self._rows_lock:
            self.prefill_rows += n

    @property
    def supports_fixed_shape_prefill(self) -> bool:
        """True when the arch can use the bucketed/chunked admission
        recipe (:func:`repro.models.model.prefill_attend`): attention
        caches written at absolute positions.

        Excluded (they keep the exact-length recipe): recurrent-state
        archs (SSM/hybrid — padded tokens would pollute conv/SSD
        states), sliding-window ring caches (the ring re-roll would
        rotate padded rows in), frontend archs, and MoE — the
        capacity-factor dispatch routes over every token in the call, so
        padded rows change which real tokens get dropped and padding
        invariance cannot hold bitwise."""
        cfg = self.cfg
        return (cfg.arch_type not in ("ssm", "hybrid")
                and not cfg.sliding_window and not cfg.frontend
                and not cfg.is_moe)

    def _get_sampler(self):
        """Jitted sampler for the CURRENT (temperature, top_p) — the
        knobs steer python-level branches, so they are baked into the
        trace; mutating them rebuilds the sampler (one retrace)."""
        knobs = (self.temperature, self.top_p)
        if knobs != self._sampler_knobs:
            base_key, (temperature, top_p) = self._base_key, knobs

            def sampler(logits, rids, steps):
                keys = jax.vmap(lambda r, s: jax.random.fold_in(
                    jax.random.fold_in(base_key, r), s))(rids, steps)
                row = functools.partial(_sample_row, temperature=temperature,
                                        top_p=top_p)
                return jax.vmap(row)(logits, keys)

            self._sampler = jax.jit(sampler)
            self._sampler_knobs = knobs
        return self._sampler

    def sample(self, logits: jax.Array, rids, steps) -> jax.Array:
        """Per-row sampling keyed by (engine seed, request id, step).

        logits: (B, V); rids/steps: length-B int sequences. Stateless —
        results are independent of request interleaving and of whether
        rows share a batch.
        """
        return self._get_sampler()(logits, jnp.asarray(rids, jnp.int32),
                                   jnp.asarray(steps, jnp.int32))

    def generate(self, prompt: str, max_new_tokens: int = 32,
                 rid: int = 0, priority: int = 0,
                 tenant: str = "") -> GenerationResult:
        """``priority`` and ``tenant`` are accepted (and ignored) so
        ``Engine`` and ``EngineClient`` stay interchangeable endpoints
        for ``JaxLLMBackend``; only the scheduler-backed client uses
        them."""
        ids = self.tokenizer.encode(prompt)
        return self.generate_ids(ids, max_new_tokens, rid=rid)

    def prefill_ids(self, ids: List[int], cache_len: int):
        """Prefill one request (batch 1) into a ``cache_len``-length
        cache (+ frontend offset). Returns (last logits (1, V), cache).

        THE canonical prefill recipe — the serial ``generate_ids`` loop,
        the batch scheduler's admission and preemption-resume replay all
        call it (or its batched row-stable equivalent), which is what
        keeps batched/chunked decode bit-identical to serial generation.
        On archs supporting fixed-shape prefill the prompt is padded to
        its power-of-two bucket (one compile per bucket instead of one
        per length) and, when ``prefill_chunk`` is set and the prompt
        exceeds it, prefilled chunk-by-chunk via :meth:`prefill_job`."""
        if not self.supports_fixed_shape_prefill:
            return self.prefill_ids_exact(ids, cache_len)
        if self.prefill_chunk and len(ids) > self.prefill_chunk:
            job = self.prefill_job(ids, cache_len)
            while not job.done:
                job.step()
            return job.logits, job.cache
        bucket = prefill_bucket(len(ids))
        tokens = jnp.asarray([list(ids) + [0] * (bucket - len(ids))],
                             jnp.int32)
        lengths = jnp.asarray([len(ids)], jnp.int32)
        self._count_rows(bucket)
        return self._prefill_fixed(self.params, tokens=tokens,
                                   lengths=lengths,
                                   cache_len=int(cache_len))

    def prefill_ids_exact(self, ids: List[int], cache_len: int):
        """The historical exact-length prefill: one trace per prompt
        length, cache padded (or ring re-rolled) to ``cache_len``
        afterwards. Canonical for SSM/hybrid/sliding-window/frontend
        archs; kept callable everywhere as the pre-bucketing baseline
        (``benchmarks/serving.py`` measures admission latency against
        it)."""
        cfg = self.cfg
        prompt = jnp.asarray([ids], jnp.int32)
        fe = None
        if cfg.frontend:
            fe = jnp.zeros((1, cfg.frontend_positions, cfg.d_model),
                           self.params["embed"].dtype)
        self._count_rows(len(ids))
        logits, cache = self._prefill(self.params, tokens=prompt,
                                      frontend_embeds=fe)
        cache = pad_cache_to(cfg, cache, cache_len +
                             (cfg.frontend_positions if cfg.frontend else 0))
        return logits, cache

    def prefill_batch_ids(self, ids_list: List[List[int]], cache_len: int,
                          width: Optional[int] = None):
        """Bucketed BATCHED prefill: stack several prompts (padded to the
        shared power-of-two bucket of the longest, batch padded to
        ``width`` rows) and prefill them in ONE jitted call.

        Row results are bit-identical to batch-1 :meth:`prefill_ids` of
        each prompt (batch stacking at a fixed padded length is
        row-stable), so the scheduler can admit a burst of requests
        together without breaking serial parity. Returns
        (logits (width, V), cache with a ``width`` batch axis); callers
        read the first ``len(ids_list)`` rows.
        """
        width = width if width is not None else len(ids_list)
        bucket = prefill_bucket(max(len(i) for i in ids_list))
        rows = [list(i) for i in ids_list] + [[0]] * (width - len(ids_list))
        tokens = jnp.asarray([r + [0] * (bucket - len(r)) for r in rows],
                             jnp.int32)
        lengths = jnp.asarray([len(r) for r in rows], jnp.int32)
        self._count_rows(width * bucket)
        return self._prefill_fixed(self.params, tokens=tokens,
                                   lengths=lengths,
                                   cache_len=int(cache_len))

    def prefill_job(self, ids: List[int], cache_len: int) -> "PrefillJob":
        """Incremental chunked prefill: a :class:`PrefillJob` whose
        ``step()`` prefills ONE ``prefill_chunk``-sized chunk — the
        scheduler interleaves these steps with live decode so a long
        prompt bounds (instead of monopolizing) the stall it causes."""
        return PrefillJob(self, ids, cache_len)

    def prefill_continue(self, ids: List[int], start: int, cache):
        """Prefill only ``ids[start:]`` against a cache whose rows
        ``0..start-1`` already hold the prompt's prefix K/V — the
        prefix-reuse admission recipe (paged serving): a prefix-cache hit
        hands the scheduler the shared blocks, and only the divergent
        suffix runs through the model.

        Bit-identical to whole-prompt :meth:`prefill_ids` by the chunked
        ==-whole argument: :func:`repro.models.model.prefill_attend`
        continuation is split-agnostic (every query attends over the
        full cache width under the ``col <= q_pos`` validity mask, and
        padded suffix rows sit beyond every valid query's mask), so
        resuming at ``start`` over reused rows reproduces the exact
        logits the full prefill would have produced.  The suffix is
        padded to its power-of-two bucket — same trace economy as
        admission.  Returns (last logits (1, V), cache); ``cache`` is
        donated."""
        suffix = list(ids)[start:]
        bucket = prefill_bucket(len(suffix))
        tokens = jnp.asarray([suffix + [0] * (bucket - len(suffix))],
                             jnp.int32)
        self._count_rows(bucket)
        return self._prefill_extend(self.params, cache=cache, tokens=tokens,
                                    off=jnp.int32(start),
                                    lengths=jnp.asarray([len(suffix)],
                                                        jnp.int32))

    def replay_ids(self, ids: List[int], kept: List[int], cache_len: int):
        """Rebuild the exact decode state of a request that already
        generated ``kept`` tokens (preemption resume): canonical prefill
        of the prompt, then per-token decode replay of ``kept[:-1]``.

        Replay — not re-prefill of prompt+kept — because prefill and
        decode group their float reductions differently: a prefilled row
        is not bitwise the row decode would have written.  Replaying the
        identical jitted decode calls in the identical order *is* bitwise
        (already-sampled tokens are never resampled), so a preempted
        request resumes onto exactly the uninterrupted token stream.
        Returns (cache, next_pos, next_token) ready for ``write_slot``.
        """
        _, cache = self.prefill_ids(ids, cache_len)
        offset = self.cfg.frontend_positions if self.cfg.frontend else 0
        base = offset + len(ids)
        self._count_rows(len(kept) - 1)
        for i, tok in enumerate(kept[:-1]):
            _, cache = self._decode(self.params, cache=cache,
                                    token=jnp.asarray([[tok]], jnp.int32),
                                    pos=jnp.int32(base + i))
        return cache, base + len(kept) - 1, kept[-1]

    def generate_ids(self, ids: List[int], max_new_tokens: int,
                     rid: int = 0, cache_len: Optional[int] = None
                     ) -> GenerationResult:
        """Serial per-request generation.

        ``rid`` keys the sampling RNG; ``cache_len`` fixes the decode
        cache length (defaults to exactly prompt+new tokens — pass the
        scheduler's ``max_len`` to compare against batched decode under
        identical shapes).
        """
        cfg = self.cfg
        total = cache_len if cache_len is not None else (
            len(ids) + max_new_tokens)
        logits, cache = self.prefill_ids(ids, total)
        new_ids: List[int] = []
        tok = self.sample(logits, [rid], [0])
        offset = cfg.frontend_positions if cfg.frontend else 0
        for i in range(max_new_tokens):
            new_ids.append(int(tok[0]))
            if int(tok[0]) == self.tokenizer.eos:
                break
            pos = jnp.int32(offset + len(ids) + i)
            logits, cache = self._decode(self.params, cache=cache,
                                         token=tok[:, None], pos=pos)
            tok = self.sample(logits, [rid], [i + 1])
        return GenerationResult(self.tokenizer.decode(new_ids), len(ids),
                                len(new_ids), new_ids)

    def score(self, text: str) -> float:
        """Mean NLL of text under the model (used by eval harnesses)."""
        from ..models.model import loss_fn
        ids = self.tokenizer.encode(text)[:512]
        batch = {"tokens": jnp.asarray([ids], jnp.int32)}
        loss, _ = loss_fn(self.params, self.cfg, batch)
        return float(loss)


class PrefillJob:
    """Chunk-at-a-time prefill of one prompt (batch 1).

    Every ``step()`` runs one fixed-shape ``prefill_chunk``-token chunk
    through :func:`repro.models.model.prefill_attend` against the
    accumulating cache (the final partial chunk is right-padded to the
    same shape, so ONE jitted trace serves every chunk of every prompt).
    ``done`` flips once the whole prompt is in; ``logits`` then holds the
    last-position logits to sample the first token from, and ``cache``
    the full prefilled cache ready for ``write_slot``.

    Both ``Engine.prefill_ids`` (synchronous drain: the serial recipe)
    and ``BatchScheduler`` (one chunk per scheduler step, interleaved
    with live decode) drive the same job, so chunked admission stays
    bit-identical to serial generation.

    ``start`` > 0 resumes the job at that offset against a caller-built
    ``cache`` already holding rows ``0..start-1`` (prefix-reuse
    admission: shared blocks skip their chunks entirely) — the chunk
    trace is the same either way, only the traced offset differs.
    """

    def __init__(self, engine: Engine, ids: List[int], cache_len: int,
                 cache=None, start: int = 0):
        if not engine.supports_fixed_shape_prefill:
            raise NotImplementedError(
                f"chunked prefill needs fixed-shape prefill support; "
                f"{engine.cfg.name} uses the exact-length recipe")
        self.engine = engine
        self.ids = list(ids)
        self.cache_len = int(cache_len)
        self.chunk = max(1, engine.prefill_chunk or len(self.ids))
        self.start = int(start)
        self.off = self.start
        self.logits = None
        self.cache = cache if cache is not None else init_cache(
            engine.cfg, 1, self.cache_len,
            dtype=engine.params["embed"].dtype)

    @property
    def done(self) -> bool:
        return self.off >= len(self.ids)

    def step(self) -> int:
        """Prefill the next chunk; returns how many prompt tokens it
        consumed (the scheduler's ``prefilled`` gauge).

        No-op once ``done``: a prefix-reuse job whose suffix fits one
        chunk completes at creation, and the scheduler's next-step
        drive must not run a zero-length chunk over the finished
        logits."""
        if self.done:
            return 0
        chunk = self.ids[self.off:self.off + self.chunk]
        valid = len(chunk)
        tokens = jnp.asarray([chunk + [0] * (self.chunk - valid)], jnp.int32)
        self.engine._count_rows(self.chunk)
        self.logits, self.cache = self.engine._prefill_extend(
            self.engine.params, cache=self.cache, tokens=tokens,
            off=jnp.int32(self.off),
            lengths=jnp.asarray([valid], jnp.int32))
        self.off += valid
        return valid
