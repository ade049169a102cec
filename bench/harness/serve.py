"""The system under test and the measured window.

The window drives ``BatchScheduler.submit()`` / ``step()`` on the paged
path with the prefix cache on, over an ``Engine`` built from the
configuration's ``serving`` block and the benchmark's seeded weights.
An ``agent`` mix is submitted open loop from a thread of its own, each
request at its due time; a ``batch`` mix keeps a backlog topped up after
every step.  Each step's end is stamped on the host clock: a step ends in
host reads of its sampled tokens, so the stamp is device-synchronised.
Each step keeps the int counters of the program's ``EngineStepped`` for
it, and each request the program's admission stamp.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def engine_seed(seed: int) -> int:
    """The sampler's seed: any run seed folded below 2**31."""
    return seed % 2_147_483_629


@dataclasses.dataclass
class Step:
    idx: int
    start: float        # seconds after the window opened
    end: float
    admissions: int     # from the prefix cache's stats, over the step
    reused: int
    # every int field of the step's EngineStepped by its own name
    # (``live``, ``generated``, ``prefill_rows``, ...): a reader of a
    # counter the program adds later needs no edit here
    counters: Dict[str, int]


def int_fields(event) -> Dict[str, int]:
    """The int fields of a dataclass event (``EngineStepped``)."""
    return {f.name: v for f in dataclasses.fields(event)
            if isinstance(v := getattr(event, f.name), int)
            and not isinstance(v, bool)}


def build(cell, cfg, seed: int):
    """Weights (one jitted draw on the device), engine and scheduler."""
    from repro.serving.engine import Engine
    from repro.serving.scheduler import BatchScheduler
    sv = cell.serving
    dtype = DTYPES[sv["dtype"]]
    params = weights.make_params(cell.model, seed, dtype, cell.arch)
    jax.block_until_ready(params)
    engine = Engine(cfg, params=params, seed=engine_seed(seed), dtype=dtype,
                    temperature=float(sv["temperature"]),
                    prefill_chunk=int(sv["prefill_chunk"]))
    sched = BatchScheduler(engine, n_slots=int(sv["n_slots"]),
                           max_len=int(sv["max_len"]), paged_kv=True,
                           block_size=int(sv["block_size"]),
                           prefix_cache=True)
    return engine, sched


def warm_up(sched, shapes: dict, vocab: int, seed: int) -> int:
    """Compile the shapes the window will use and no others: one whole
    prompt per admission bucket it sends, a chunked prompt fresh and
    again at a cached prefix, and decode over the full slot batch.  The
    prompts' ids come from another stream than the traffic's, so the
    prefix cache holds nothing the window can hit.  Returns the number
    of warm-up requests."""
    from repro.serving.engine import prefill_bucket
    rng = np.random.default_rng([seed, 7])
    chunk = sched.engine.prefill_chunk

    def ids(n):
        return rng.integers(1, vocab - 2, size=n).tolist()

    rounds: List[List[List[int]]] = []
    buckets = sorted({prefill_bucket(n) for n in shapes["whole"]})
    if buckets:
        rounds.append([ids(b) for b in buckets])
    if shapes["chunked"]:
        base = ids(2 * chunk + 3 * sched.block_size)
        rounds.append([base])
        rounds.append([base + ids(chunk // 2)])     # a job from a hit
    n = 0
    for prompts in rounds:
        for p in prompts:
            sched.submit(prompt_ids=p, max_new=3)
            n += 1
        while sched.has_work():
            sched.step()
    sched.block_until_ready()
    return n


@dataclasses.dataclass
class Window:
    requests: List                  # the requests the run tracks
    steps: List[Step]
    seconds: float
    drain_deadline: float


class _Events:
    """Keeps the scheduler's per-step event for the step loop."""

    def __init__(self):
        self.last = None

    def __call__(self, ev):
        self.last = ev


def run_window(sched, mix: dict, reqs, seconds: float, *,
               trace_dir: Optional[str] = None,
               trace_span=(0.25, 0.75), trace_cap_s: float = 20.0,
               on_open=None) -> Window:
    """Drive the scheduler for ``seconds``, then follow every tracked
    request to its end, up to the mix's drain limit.  ``reqs`` is the
    list of an agent mix or the stream of a batch mix."""
    agent = mix["kind"] == "agent"
    backlog = int(mix.get("backlog", 0))
    drain_s = float(mix.get("drain_s", 0.0)) if agent else 0.0
    events = _Events()
    sched.subscribe(events)
    tracked: List = list(reqs) if agent else []
    by_rid: Dict[int, object] = {}
    lock = threading.Lock()
    wake = threading.Event()
    stop = threading.Event()
    steps: List[Step] = []
    prof_on = trace_dir is not None
    t_trace0 = seconds * trace_span[0]
    t_trace1 = min(seconds * trace_span[1], t_trace0 + trace_cap_s)
    tracing, window_ann = False, None

    base = sched.paging_stats()
    adm0 = base.get("hits", 0) + base.get("misses", 0)
    reused0 = base.get("tokens_reused", 0)

    profiling = False
    if prof_on:
        # started before the window opens: starting stalls the host for a
        # second or more.  No Python tracer: it slows the host severalfold
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        profiling = True
    if on_open:
        on_open()
    t0 = time.perf_counter()

    def now():
        return time.perf_counter() - t0

    def submit(r):
        with lock:
            r.rid = sched.submit(prompt_ids=r.prompt, max_new=r.max_new)
            r.submit = now()
            by_rid[r.rid] = r

    def submitter():
        for r in tracked:
            delay = r.due - now()
            if delay > 0 and stop.wait(delay):
                return
            submit(r)
            wake.set()

    thread = None
    if agent:
        thread = threading.Thread(target=submitter, name="bench-clients",
                                  daemon=True)
        thread.start()
    else:
        for _ in range(backlog):
            r = next(reqs)
            tracked.append(r)
            submit(r)

    deadline = seconds + drain_s
    idx = 0
    adm_prev, reused_prev = adm0, reused0
    try:
        while True:
            t = now()
            if prof_on and not tracing and t_trace0 <= t < t_trace1:
                window_ann = jax.profiler.TraceAnnotation("bench_window")
                window_ann.__enter__()
                tracing = True
            if tracing and t >= t_trace1:
                window_ann.__exit__(None, None, None)
                tracing = False
            if profiling and t >= seconds:
                jax.profiler.stop_trace()       # stalls: only after the window
                profiling = False
            if t >= seconds:
                if not agent:
                    break
                with lock:
                    pending = any(r.done is None and r.rid >= 0
                                  for r in tracked)
                if (not thread.is_alive() and not pending) or t >= deadline:
                    break
            if not sched.has_work():
                nxt = seconds if t < seconds else deadline
                timeout = max(0.0005, min(0.05, nxt - t))
                if tracing:
                    with jax.profiler.TraceAnnotation("wait_for_arrivals"):
                        wake.wait(timeout=timeout)
                else:
                    wake.wait(timeout=timeout)
                wake.clear()
                continue
            ts = now()
            if tracing:
                with jax.profiler.TraceAnnotation(f"sched_step:{idx}"):
                    finished = sched.step()
            else:
                finished = sched.step()
            te = now()
            st = sched.paging_stats()
            adm = st["hits"] + st["misses"]
            ev = events.last
            steps.append(Step(idx=idx, start=ts, end=te,
                              admissions=adm - adm_prev,
                              reused=st["tokens_reused"] - reused_prev,
                              counters=int_fields(ev)))
            adm_prev, reused_prev = adm, st["tokens_reused"]
            if finished:
                with lock:
                    for q in finished:
                        r = by_rid.get(q.rid)
                        if r is not None:
                            r.done = te
                            r.out = list(q.out_ids)
            idx += 1
            if not agent and te < seconds:
                while sched.queue_depth() < backlog:
                    r = next(reqs)
                    tracked.append(r)
                    submit(r)
    finally:
        stop.set()
        if thread is not None:
            thread.join()
        if tracing:
            window_ann.__exit__(None, None, None)
        if profiling:
            jax.profiler.stop_trace()
    sched.block_until_ready()

    with lock:
        for r in tracked:
            if r.rid < 0:
                continue
            q = sched.requests.get(r.rid)
            if q is not None and q.t_first_token:
                r.first = q.t_first_token - t0
            # the program's admission stamp; None if never admitted
            r.admit = q.t_admit - t0 if q is not None and q.t_admit else None
    _mark_admissions(tracked, steps)
    return Window(requests=tracked, steps=steps, seconds=seconds,
                  drain_deadline=deadline if agent else seconds)


def _mark_admissions(tracked, steps: List[Step]) -> None:
    """First in, first out: the k-th admission of the window is the k-th
    request submitted in it; the tokens a step reused belong to the one
    request it admitted (several admissions in a step reuse nothing in
    these mixes)."""
    order = sorted((r for r in tracked if r.rid >= 0), key=lambda r: r.rid)
    it = iter(order)
    for s in steps:
        for j in range(s.admissions):
            r = next(it, None)
            if r is None:
                return
            r.admit_step = s.idx
            if s.admissions == 1:
                r.reused = s.reused
