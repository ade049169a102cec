"""Serving launcher: batched requests through the continuous-batching
scheduler (one jitted decode step advances all live slots; admission is
bucketed batched prefill, optionally chunked via --prefill-chunk).

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --requests 8 --max-new 16 [--prefill-chunk 32] \
        [--high-priority-every 4]
"""
from __future__ import annotations

import argparse
import time

import jax

from ..configs import ARCHS, get_config
from ..serving import BatchScheduler, Engine, RunMonitor
from .compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-prefill budget (0 = whole-prompt)")
    ap.add_argument("--per-request-prefill", action="store_true",
                    help="v1 admission: one exact-length prefill per "
                         "request (disables length bucketing)")
    ap.add_argument("--high-priority-every", type=int, default=0,
                    help="submit every Nth request at priority 1 to "
                         "exercise queue jumping / preemption")
    ap.add_argument("--metrics-out", default="",
                    help="write the monitor registry's Prometheus text "
                         "export here (plus <path>.otlp.json)")
    ap.add_argument("--profile-jit", action="store_true",
                    help="wrap the engine's jitted hot paths and print "
                         "per-fn compile counts and call-time stats")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    engine = Engine(cfg, seed=args.seed, prefill_chunk=args.prefill_chunk)
    monitor = RunMonitor()
    profiler = None
    if args.profile_jit:
        from ..telemetry import JitProfiler
        profiler = JitProfiler()
        profiler.wrap_engine(engine)
    sched = BatchScheduler(engine, n_slots=args.slots, max_len=args.max_len,
                           on_event=monitor,
                           batched_prefill=not args.per_request_prefill)
    prompts = [f"request {i}: summarize the latest agentic workflow results"
               for i in range(args.requests)]
    t0 = time.time()
    for i, p in enumerate(prompts):
        pri = (1 if args.high_priority_every
               and i % args.high_priority_every == 0 else 0)
        sched.submit(p, max_new=args.max_new, priority=pri)
    results = sched.run()
    wall = time.time() - t0
    toks = monitor.engine_tokens + len(results)   # + first (prefill) tokens
    dev = jax.devices()[0]
    print(f"# served {len(results)} requests, {toks} new tokens in "
          f"{wall:.1f}s ({toks / wall:.1f} tok/s on {dev.platform} "
          f"{dev.device_kind}, compiles included) — "
          f"{monitor.engine_steps} decode steps, peak occupancy "
          f"{monitor.engine_peak_live}/{args.slots}, "
          f"{monitor.engine_prefill_tokens} prompt tokens prefilled, "
          f"{monitor.engine_preemptions} preemptions")
    for rid in sorted(results)[:3]:
        print(f"req{rid}: {results[rid][:48]!r}")
    if profiler is not None:
        print("# jit profile (calls / compiles / wall time per fn):")
        for row in profiler.table():
            print(row)
    if args.metrics_out:
        from ..telemetry import export_otlp_metrics_json, render_prometheus
        otlp_path = args.metrics_out + ".otlp.json"
        with open(args.metrics_out, "w") as fh:
            fh.write(render_prometheus(monitor.registry))
        with open(otlp_path, "w") as fh:
            fh.write(export_otlp_metrics_json(monitor.registry))
        print(f"# wrote {args.metrics_out} + {otlp_path}")


if __name__ == "__main__":
    main()
