"""Traffic launcher: drive a seeded workload through the asyncio
virtual-clock driver (or the wall-clock real mode) and print per-scenario
SLO telemetry.

    # 500-request bursty day over the default mix, faults + retry:
    PYTHONPATH=src python -m repro.launch.traffic --requests 500 \
        --arrival bursty --rate 5 --transient-rate 0.2 --retry

    # closed loop, 16 users:
    PYTHONPATH=src python -m repro.launch.traffic --arrival closed \
        --users 16 --requests 64

    # real wall-clock mode against the batched JAX engine (CPU):
    PYTHONPATH=src python -m repro.launch.traffic --real \
        --llm jax-batched --requests 8 --rate 1 --time-scale 20

    # repeat-heavy agentx mix with the plan cache (prints hit/miss/
    # fallback telemetry; repeats replay compiled graphs planner-free):
    PYTHONPATH=src python -m repro.launch.traffic --plan-cache \
        --unique-seeds 4 --requests 60 \
        --scenario web_search:quantum:agentx \
        --scenario stock_correlation:netflix:agentx:faas

    # multi-tenant noisy neighbor: the mix replicated per tenant (noisy
    # offers 5x the load), fair-share admission at 8 slots, a token
    # budget on the noisy tenant, per-tenant telemetry at the end:
    PYTHONPATH=src python -m repro.launch.traffic --requests 105 \
        --rate 0.21 --concurrency 8 \
        --tenants steady-a,steady-b,noisy:5 \
        --tenant-weights steady-a:1,steady-b:1,noisy:1 \
        --budget noisy:500000
"""
from __future__ import annotations

import argparse
import dataclasses
import json

from ..apps.session import Session
from ..core.policies import HedgePolicy, RetryPolicy
from ..traffic import (DEFAULT_MIX, FaultPlan, SLOTarget, Scenario,
                       TrafficDriver, Workload, aggregate_report,
                       register_fault_plan)
from ..traffic.faults import FaultStats
from .compile_cache import enable_compile_cache


def _mix(args) -> tuple:
    if args.scenario:
        scenarios = []
        for i, raw in enumerate(args.scenario):
            parts = raw.split(":")
            if len(parts) < 3:
                raise SystemExit(f"--scenario {raw!r}: expected "
                                 f"app:instance:pattern[:deployment[:weight]]")
            app, inst, pat = parts[:3]
            dep = parts[3] if len(parts) > 3 else "local"
            weight = float(parts[4]) if len(parts) > 4 else 1.0
            scenarios.append(Scenario(f"{app}/{dep}/{pat}", app, inst, pat,
                                      dep, weight=weight))
        mix = tuple(scenarios)
    else:
        mix = DEFAULT_MIX
    if args.llm != "oracle":
        mix = tuple(dataclasses.replace(s, llm=args.llm) for s in mix)
    return mix


def _tenancy(args):
    """Parse the tenant knobs into (load multipliers, registry, Tenancy).

    ``--tenants a,b,noisy:5`` — tenant names with optional arrival-load
    multipliers; ``--tenant-weights a:1,noisy:0.5`` — fair-share
    weights; ``--budget noisy:500000`` or ``noisy:500000:0.25`` — token
    (and optional USD) caps.  Returns ``(None, None, None)`` when
    ``--tenants`` is absent — the tenancy-off path, bit-identical to
    the single-tenant launcher."""
    if not args.tenants:
        if args.tenant_weights or args.budget:
            raise SystemExit("--tenant-weights/--budget require --tenants")
        return None, None, None
    from ..tenancy import Tenancy, Tenant, TenantRegistry

    def pairs(raw, what):
        out = {}
        for part in raw.split(","):
            if not part:
                continue
            bits = part.split(":")
            try:
                out[bits[0]] = [float(b) for b in bits[1:]]
            except ValueError:
                raise SystemExit(f"bad {what} entry {part!r}")
        return out

    mults = {t: (v[0] if v else 1.0)
             for t, v in pairs(args.tenants, "--tenants").items()}
    weights = {t: (v[0] if v else 1.0)
               for t, v in pairs(args.tenant_weights or "",
                                 "--tenant-weights").items()}
    budgets = pairs(args.budget or "", "--budget")
    for t in list(weights) + list(budgets):
        if t not in mults:
            raise SystemExit(f"tenant {t!r} not listed in --tenants")
    registry = TenantRegistry(*(
        Tenant(t, weight=weights.get(t, 1.0),
               token_budget=(budgets[t][0] if t in budgets
                             else float("inf")),
               cost_budget_usd=(budgets[t][1]
                                if t in budgets and len(budgets[t]) > 1
                                else float("inf")))
        for t in mults))
    return mults, registry, Tenancy(registry)


def _export_metrics(args, report):
    """Fold the finished report into a fresh registry, write the
    Prometheus + OTLP exports, and return ``(registry, slo_monitor,
    paths)`` for the summary tables."""
    from ..telemetry import (EventMetricsBridge, MetricsRegistry,
                             SloMonitor, export_otlp_metrics_json,
                             fold_report, render_prometheus)
    registry = MetricsRegistry()
    fold_report(EventMetricsBridge(registry), report)
    slo_mon = SloMonitor(SLOTarget(), window_s=args.slo_window,
                         threshold=args.burn_threshold, registry=registry)
    slo_mon.observe_records(report.records)
    otlp_path = args.metrics_out + ".otlp.json"
    with open(args.metrics_out, "w") as fh:
        fh.write(render_prometheus(registry))
    with open(otlp_path, "w") as fh:
        fh.write(export_otlp_metrics_json(registry))
    return registry, slo_mon, (args.metrics_out, otlp_path)


def _print_telemetry(registry, slo_mon, paths) -> None:
    def t(name):
        return int(registry.total(name))

    def hit_rate(cache):
        g = registry.get("repro_cache_hit_rate")
        return g.value(cache=cache) if g is not None else 0.0

    print(f"# telemetry: {t('repro_events_total')} events folded into "
          f"{len(registry.names())} families | wrote {paths[0]} + "
          f"{paths[1]}")
    rows = [
        ("orchestration",
         f"runs={t('repro_runs_started_total')} "
         f"llm_calls={t('repro_llm_calls_total')} "
         f"tool_calls={t('repro_tool_calls_total')} "
         f"retries={t('repro_tool_retries_total')} "
         f"hedges={t('repro_hedges_total')}"),
        ("engine",
         f"steps={t('repro_engine_steps_total')} "
         f"decode_tokens={t('repro_engine_decode_tokens_total')} "
         f"prefill_tokens={t('repro_engine_prefill_tokens_total')} "
         f"prefix_hits={t('repro_engine_prefix_hits_total')}"),
        ("tenancy",
         f"spend_usd={registry.total('repro_tenant_spend_usd_total'):.5f} "
         f"degraded={t('repro_tenant_degraded_total')} "
         f"rejected={t('repro_tenant_rejected_total')}"),
        ("caches",
         f"plan_hit_rate={hit_rate('plan'):.0%} "
         f"lookups={t('repro_cache_lookups_total')} "
         f"plan_events={t('repro_plan_cache_events_total')}"),
        ("durability",
         f"crashes={t('repro_run_crashes_total')} "
         f"resumes={t('repro_run_resumes_total')}"),
        ("slo",
         f"alerts={len(slo_mon.alerts)} " + " ".join(
             f"{o}={n}" for o, n in
             slo_mon.summary()["by_objective"].items())),
    ]
    for layer, detail in rows:
        print(f"#   {layer:14s} {detail}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", action="append", default=[],
                    help="app:instance:pattern[:deployment[:weight]] "
                         "(repeatable; default: the built-in mix)")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "bursty", "uniform", "closed"])
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--users", type=int, default=8,
                    help="closed-loop virtual users")
    ap.add_argument("--think", type=float, default=5.0,
                    help="closed-loop mean think time (virtual s)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--concurrency", type=int, default=0,
                    help="in-flight run cap (0 = unbounded)")
    ap.add_argument("--llm", default="oracle")
    # multi-tenant serving (repro.tenancy)
    ap.add_argument("--tenants", default="",
                    help="comma list of tenant[:load-multiplier] — "
                         "replicate the mix per tenant (noisy neighbor: "
                         "'a,b,noisy:5') and admit fair-share")
    ap.add_argument("--tenant-weights", default="",
                    help="comma list of tenant:weight fair-share weights "
                         "(default 1.0 each)")
    ap.add_argument("--budget", default="",
                    help="comma list of tenant:tokens[:usd] budget caps "
                         "(soft 80%% degrades, hard cap rejects)")
    # plan compilation (repro.plans)
    ap.add_argument("--plan-cache", action="store_true",
                    help="compile successful agentx runs into plan graphs "
                         "and replay repeats planner-free")
    ap.add_argument("--unique-seeds", type=int, default=0,
                    help="cap distinct spec seeds (repeat-heavy mix; "
                         "0 = every request unique)")
    # fault injection + resilience
    ap.add_argument("--transient-rate", type=float, default=0.0)
    ap.add_argument("--throttle-rate", type=float, default=0.0)
    ap.add_argument("--cold-start-rate", type=float, default=0.0)
    ap.add_argument("--cold-start-s", type=float, default=2.5)
    ap.add_argument("--retry", action="store_true",
                    help="enable RetryPolicy on the session")
    ap.add_argument("--hedge-after", type=float, default=0.0,
                    help="enable HedgePolicy at this deadline (virtual s)")
    # durable execution (repro.durable)
    ap.add_argument("--crash-rate", type=float, default=0.0,
                    help="per-attempt platform-kill probability "
                         "(crashed runs restart; with --journal-dir they "
                         "resume from the journal)")
    ap.add_argument("--journal-dir", default="",
                    help="journal every run's event stream to this "
                         "directory and resume crashed runs from it")
    # real (wall-clock) mode
    ap.add_argument("--real", action="store_true",
                    help="wall-clock mode: thread-pool dispatch at scaled "
                         "arrival times (use with --llm jax-batched)")
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="real mode: compress arrival time by this factor")
    # unified telemetry (repro.telemetry)
    ap.add_argument("--metrics-out", default="",
                    help="fold the run into the metrics registry and "
                         "write the Prometheus text export here (plus "
                         "<path>.otlp.json), printing per-layer "
                         "telemetry tables")
    ap.add_argument("--slo-window", type=float, default=60.0,
                    help="SLO burn-rate window (virtual s)")
    ap.add_argument("--burn-threshold", type=float, default=2.0,
                    help="burn-rate multiple that fires an alert")
    ap.add_argument("--json", action="store_true",
                    help="print the full aggregate as JSON")
    args = ap.parse_args()
    enable_compile_cache()

    mix = _mix(args)
    mults, registry, tenancy = _tenancy(args)
    if mults is not None:
        from ..traffic import tenant_mix
        mix = tenant_mix(mults, base=mix)
    stats = None
    if (args.transient_rate or args.throttle_rate or args.cold_start_rate
            or args.crash_rate):
        plan = FaultPlan(transient_rate=args.transient_rate,
                         throttle_rate=args.throttle_rate,
                         cold_start_rate=args.cold_start_rate,
                         cold_start_s=args.cold_start_s,
                         first_call_cold=False, seed=args.seed,
                         crash_rate=args.crash_rate)
        stats = FaultStats()
        faulty = []
        for s in mix:
            name = f"{s.deployment}+faults"
            register_fault_plan(name, s.deployment, plan, stats=stats)
            faulty.append(dataclasses.replace(s, deployment=name))
        mix = tuple(faulty)

    plan_cache = None
    if args.plan_cache:
        from ..plans import PlanCache
        plan_cache = PlanCache()
    journal = None
    if args.journal_dir:
        from ..durable import RunJournal
        journal = RunJournal(args.journal_dir)
    session = Session(
        retry=RetryPolicy(max_attempts=8, backoff_s=0.25)
        if args.retry else None,
        hedge=HedgePolicy(hedge_after_s=args.hedge_after)
        if args.hedge_after > 0 else None,
        plan_cache=plan_cache,
        journal=journal,
        tenancy=tenancy)
    wl = Workload(scenarios=mix, arrival=args.arrival, rate=args.rate,
                  n_requests=args.requests, seed=args.seed,
                  users=args.users, think_s=args.think,
                  unique_seeds=args.unique_seeds)
    restart = ("resume" if journal is not None
               else ("rerun" if args.crash_rate else "auto"))
    driver = TrafficDriver(session, max_concurrency=args.concurrency,
                           mode="real" if args.real else "virtual",
                           time_scale=args.time_scale,
                           restart=restart,
                           tenants=registry)
    report = driver.run(wl)
    agg = aggregate_report(report, SLOTarget())

    telemetry = None
    if args.metrics_out:
        telemetry = _export_metrics(args, report)

    if args.json:
        print(json.dumps(agg, indent=2))
        return
    rp = agg["replay"]
    print(f"# {len(report.records)} runs | virtual {rp['virtual_s']:.0f}s "
          f"in wall {rp['wall_s']:.2f}s ({rp['speedup']:.0f}x) | peak "
          f"{rp['peak_concurrency']} in flight | "
          f"{rp['throughput_rps']:.2f} runs/s")
    if stats is not None:
        print(f"# injected faults: {stats.snapshot()}")
    du = agg["overall"]["durability"]
    if du["crashes"]:
        print(f"# durability: {du['crashed_runs']} runs crashed "
              f"({du['crashes']} kills) | {du['resumes']} resumed from "
              f"journal | {du['replayed_events']} events replayed | "
              f"{du['recovered_tokens']} tokens "
              f"(${du['recovered_cost_usd']:.5f}) recovered | "
              f"${du['sunk_cost_usd']:.5f} sunk")
    if report.plan_cache is not None:
        p = report.plan_cache
        print(f"# plan cache: {p['hits']} hits / {p['misses']} misses / "
              f"{p['fallbacks']} fallbacks | hit rate {p['hit_rate']:.0%} | "
              f"{p['entries']} compiled graphs")
    hdr = (f"{'scenario':28s} {'n':>4s} {'ok%':>6s} {'p50':>7s} {'p95':>7s} "
           f"{'ttft95':>7s} {'qwait95':>8s} {'$/run':>9s} {'retry':>5s}")
    print(hdr)
    rows = list(agg["scenarios"].items()) + [("TOTAL", agg["overall"])]
    for name, a in rows:
        print(f"{name:28s} {a['n']:4d} {a['success_rate'] * 100:5.1f}% "
              f"{a['latency_s']['p50']:7.1f} {a['latency_s']['p95']:7.1f} "
              f"{a['ttft_s']['p95']:7.1f} {a['queue_wait_s']['p95']:8.1f} "
              f"{a['cost_usd']['total_mean']:9.5f} "
              f"{a['resilience']['retries']:5d}")
    if "tenants" in agg:
        print(f"{'tenant':28s} {'n':>4s} {'tokens':>9s} {'$total':>9s} "
              f"{'tok/s':>7s} {'qwait95':>8s} {'degr':>4s} {'rej':>4s}")
        for name, a in agg["tenants"].items():
            t = a["tenant"]
            print(f"{name:28s} {a['n']:4d} {t['tokens']:9.0f} "
                  f"{t['cost_usd']:9.5f} {t['token_throughput']:7.1f} "
                  f"{a['queue_wait_s']['p95']:8.1f} "
                  f"{t['degraded_runs']:4d} {t['rejected_runs']:4d}")
    if telemetry is not None:
        _print_telemetry(*telemetry)


if __name__ == "__main__":
    main()
