"""One run of one cell: set-up, the measured window, the check, the
metrics, and the result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``check``: each number compared beside its limit.
``--control 1`` judges the float8 control in the program's place; the
benchmark's own runs never set it.
The same numbers close standard error.  A host whose JAX finds no
accelerator, or fewer chips than the cell asks for, exits 3 and prints no
result.
"""
from __future__ import annotations

import argparse
import gc
import glob
import json
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

from harness import spec

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts programs built or loaded while ``on`` is set."""

    def __init__(self):
        self.on = False
        self.n = 0
        self.total = 0

    def __call__(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.total += 1
            if self.on:
                self.n += 1


class RunData:
    """What a metric reader sees of a run."""

    def __init__(self, cell, window, counts, peaks, trace, chunk,
                 spans=None):
        self.kind = cell.mix["kind"]
        self.seconds = window.seconds
        self.drain_deadline = window.drain_deadline
        self.requests = window.requests
        self.steps = window.steps
        self.n_slots = int(cell.serving["n_slots"])
        self.counts = counts
        self.peaks = peaks
        self.trace = trace
        self.spans = spans      # the program's own spans, traced runs only
        self.chunk = chunk
        self._work = None

    def due_requests(self) -> List:
        """The requests the tails are over: every request due in the
        window (an agent mix), or every one finished in it (a batch)."""
        if self.kind == "agent":
            return [r for r in self.requests if r.due < self.seconds]
        return [r for r in self.requests
                if r.done is not None and r.done <= self.seconds]

    def window_output_tokens(self) -> int:
        """Output tokens produced inside the window: first tokens from
        prefill, and one per live slot from each step's decode."""
        firsts = sum(1 for r in self.requests
                     if r.first is not None and r.first <= self.seconds)
        return firsts + sum(s.counters["generated"] for s in self.steps
                            if s.end <= self.seconds)

    @property
    def work(self):
        if self._work is None:
            from harness.account import step_work
            self._work = step_work(self)
        return self._work


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the profiler trace to this directory")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: judge the float8 control in the program's "
                         "place (it must come out not correct)")
    return ap.parse_args(argv)


def main(argv, t_start: float, require_accelerator: bool = True,
         fault=None, root=spec.ROOT) -> int:
    """``require_accelerator=False``, ``fault`` (called with the engine
    and scheduler before warm-up, to break the timed path underneath) and
    ``root`` (another tree of benchmark files) are for the harness's own
    tests."""
    args = parse(argv)
    if args.seed < 0:
        say("--seed must be a whole number >= 0")
        return 2
    cell = spec.find_cell(args.workload, root=root)
    env = open_device(cell, require_accelerator)
    if env is None:
        return 3
    result, _ = execute(cell, args.seed, args.seconds, args.trace, env,
                        t_start=t_start, fault=fault, root=root,
                        control=bool(args.control),
                        keep_trace=args.keep_trace)
    for k, c in result["check"].items():
        say(f"check {k} = {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


def open_device(cell, require_accelerator: bool = True) -> Optional[dict]:
    """The devices, their peaks, and JAX's compile cache and counter, once
    per process; None (after saying why) where the cell cannot run."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if require_accelerator and (platform == "cpu" or len(devices) < cell.chips):
        say(f"no accelerator for {cell.name}: JAX finds {len(devices)} "
            f"{platform} device(s), the cell asks for {cell.chips} chip(s)")
        return None
    kind = devices[0].device_kind
    from harness.peaks import peaks_for
    peaks = peaks_for(kind) if require_accelerator else None
    cache_dir = None
    if require_accelerator:
        # a fixed directory inside the checkout, whatever the environment
        # says: only the checkout's first run of a cell compiles, and two
        # checkouts share nothing (tests on the CPU keep JAX's defaults)
        cache_dir = str(spec.ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    return {"devices": devices, "platform": platform, "kind": kind,
            "peaks": peaks, "cache_dir": cache_dir, "compiles": compiles}


def execute(cell, seed: int, seconds: float, trace: int, env: dict, *,
            t_start: float, fault=None, root=spec.ROOT, control=False,
            keep_trace=None):
    """Set-up, window, check and metrics of one run.  Returns the result
    object and the numbers compared: with ``control``, the float8
    control's, judged in the program's place, and the program's own
    ``program_gap`` beside them."""
    from harness import check, serve, traffic
    compiles = env["compiles"]
    devices = env["devices"]
    cfg = spec.model_config(cell.config)
    sv = cell.serving
    m = cell.model
    vocab, max_len, chunk = m["vocab_size"], int(sv["max_len"]), int(
        sv["prefill_chunk"])

    t = time.perf_counter()
    engine, sched = serve.build(cell, cfg, seed)
    say(f"weights and engine {time.perf_counter() - t:.2f} s "
        f"(since start {time.perf_counter() - t_start:.2f} s)")
    if fault is not None:
        fault(engine, sched)
    mix = cell.mix
    shapes = traffic.warm_shapes(mix, seed, seconds, vocab, max_len, chunk)
    t = time.perf_counter()
    n_warm = serve.warm_up(sched, shapes, vocab, seed)
    say(f"warm-up {time.perf_counter() - t:.2f} s")
    if mix["kind"] == "agent":
        reqs = traffic.agent_requests(mix, seed, seconds, vocab, max_len)
    else:
        reqs = traffic.batch_requests(mix, seed, vocab, max_len)
    setup_s = time.perf_counter() - t_start
    say(f"setup_s={setup_s:.3f} warmup_requests={n_warm} "
        f"programs_built_or_loaded={compiles.total} "
        f"cache={env['cache_dir']}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None

    def opened():
        compiles.n = 0
        compiles.on = True

    window = serve.run_window(sched, mix, reqs, seconds,
                              trace_dir=trace_dir, on_open=opened)
    compiles.on = False
    stats = devices[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    n_steps = len(window.steps)
    preempted = sum(s.counters["preempted"] for s in window.steps)
    print(f"compiles_in_window={compiles.n} steps={n_steps} "
          f"preempted={preempted}", flush=True)
    say(f"compiles_in_window={compiles.n} steps={n_steps} "
        f"preempted={preempted} memory_peak_bytes={peak}")

    del engine, sched
    gc.collect()

    counts = cell.arch.counts(m, sv["dtype"])
    summary = program = None
    if trace_dir is not None:
        from harness import spans
        from harness import trace as trace_mod
        t = time.perf_counter()
        files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        if files and keep_trace:
            shutil.copy(files[0], keep_trace)
        if files:
            dev_ops, host = trace_mod.read_xspace(files[0])
            summary = trace_mod.summarize(dev_ops, host)
            program = spans.summarize(dev_ops, host)
        shutil.rmtree(trace_dir, ignore_errors=True)
        say(f"trace read {time.perf_counter() - t:.1f} s")
    run = RunData(cell, window, counts, env["peaks"], summary, chunk,
                  spans=program)

    due = run.due_requests()
    attempted = len(due)
    failed = sum(1 for r in due if r.done is None)

    t_check = time.perf_counter()
    picked = check.sample(window.requests, seed,
                          int(cell.limits["check_requests"]), chunk)
    numbers: Dict[str, float] = {}
    if picked:
        numbers = check.gaps(m, seed, serve.DTYPES[sv["dtype"]],
                             serve.engine_seed(seed), picked, cell.arch,
                             mode="fp8" if control else "f32",
                             max_len=max_len)
        say(f"check: {len(picked)} requests, {int(numbers['tokens'])} "
            f"served tokens, {time.perf_counter() - t_check:.1f} s")
    limits = cell.limits["limits"]
    compared = {k: {"value": numbers.get(k), "limit": limits[k]}
                for k in limits}
    correct = bool(picked) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values())

    metrics: Dict[str, dict] = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for spec_m in wanted:
        if spec_m["name"] == "setup_s":
            value: Optional[float] = setup_s
        else:
            value = spec.metric_reader(spec_m["name"], root)(run)
        if value is not None:
            metrics[spec_m["name"]] = {"value": value, "unit": spec_m["unit"]}

    device = {"platform": env["platform"], "kind": env["kind"],
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_gaps}
    result["check"] = compared
    return result, numbers
