"""Serving throughput benchmark: slot-batched decode vs the serial
per-slot loop, and scheduler-v2 admission latency.

Measures, on the reduced tinyllama config (CPU CI baseline; pass
--arch/--full for others):

  * decode-step throughput: tokens/s of ONE jitted ``decode_step`` over
    the full ``n_slots`` batch vs ``n_slots`` sequential batch-1 calls
    (the pre-redesign scheduler's inner loop);
  * end-to-end: ``BatchScheduler.drain`` wall time vs serial
    ``Engine.generate_ids`` per request;
  * admission latency: time-to-first-token percentiles (p50/p95) under a
    bursty arrival of mixed-length prompts — bucketed batched prefill
    (scheduler v2) vs the v1 per-request exact-length admission, whose
    per-length jit recompiles dominate cold TTFT.

Writes ``artifacts/BENCH_serving.json`` (uploaded by CI).

    PYTHONPATH=src python -m benchmarks.serving --slots 8
"""
from __future__ import annotations

import argparse
import json
import os
import random
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import BatchScheduler, Engine

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")


def _time_decode(engine, batch, max_len, reps) -> float:
    """Steady-state seconds per jitted decode step at the given batch
    width (the cache is donated, so it threads through the loop)."""
    from repro.models.model import init_cache
    cache = init_cache(engine.cfg, batch, max_len,
                       dtype=engine.params["embed"].dtype)
    tok = jnp.ones((batch, 1), jnp.int32)
    pos = jnp.arange(8, 8 + batch, dtype=jnp.int32)   # mixed positions
    logits, cache = engine._decode(engine.params, cache=cache, token=tok,
                                   pos=pos)    # warm (compile)
    jax.block_until_ready(logits)
    t0 = time.perf_counter()
    for _ in range(reps):
        logits, cache = engine._decode(engine.params, cache=cache,
                                       token=tok, pos=pos)
        jax.block_until_ready(logits)
    return (time.perf_counter() - t0) / reps


def _pct(sorted_vals, q: float) -> float:
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def measure_admission(engine, n_slots: int = 4, max_len: int = 64,
                      n_requests: int = 12, max_new: int = 4,
                      seed: int = 0) -> dict:
    """TTFT under a bursty arrival: ``n_requests`` mixed-length prompts
    submitted at once, drained by the scheduler step loop.

    Compares scheduler-v2 bucketed batched prefill against the v1
    per-request exact-length admission (``batched_prefill=False``). Both
    run cold on the prefill path: the v1 mode pays one jit compile per
    distinct prompt length, the bucketed mode one per power-of-two
    bucket — plus it prefills same-bucket requests together — which is
    where the admission-latency win comes from. Decode and sampler
    traces at the admission shapes are warmed up front so the timed
    drains measure admission, not decode compiles.
    """
    from repro.models.model import init_cache
    cache = init_cache(engine.cfg, n_slots, max_len,
                       dtype=engine.params["embed"].dtype)
    tok = jnp.ones((n_slots, 1), jnp.int32)
    pos = jnp.arange(n_slots, dtype=jnp.int32)
    logits, _ = engine._decode(engine.params, cache=cache, token=tok, pos=pos)
    engine.sample(logits, [0] * n_slots, [0] * n_slots)
    engine.sample(logits[:1], [0], [0])
    jax.block_until_ready(logits)

    rng = random.Random(seed)
    lengths = [rng.randint(4, max_len // 2) for _ in range(n_requests)]
    prompts = [[rng.randrange(1, engine.cfg.vocab_size) for _ in range(n)]
               for n in lengths]
    out = {"n_requests": n_requests,
           "prompt_lengths": sorted(set(lengths))}
    for mode, flag in (("bucketed", True), ("per_request", False)):
        sched = BatchScheduler(engine, n_slots=n_slots, max_len=max_len,
                               batched_prefill=flag)
        rids = [sched.submit(prompt_ids=ids, max_new=max_new)
                for ids in prompts]
        t0 = time.perf_counter()
        sched.drain()
        wall = time.perf_counter() - t0
        ttfts = sorted(sched.requests[r].t_first_token -
                       sched.requests[r].t_submit for r in rids)
        out[mode] = {"ttft_p50_s": _pct(ttfts, 0.50),
                     "ttft_p95_s": _pct(ttfts, 0.95),
                     "wall_s": wall}
    out["ttft_p95_speedup"] = (out["per_request"]["ttft_p95_s"] /
                               out["bucketed"]["ttft_p95_s"])
    out["ttft_p50_speedup"] = (out["per_request"]["ttft_p50_s"] /
                               out["bucketed"]["ttft_p50_s"])
    return out


def measure_paging(engine, n_slots: int = 4, max_len: int = 64,
                   block_size: int = 8, n_requests: int = 8,
                   max_new: int = 4, seed: int = 0) -> dict:
    """Prefix-reuse economics of the paged KV cache.

    One paged scheduler serves two bursts: a COLD burst of prompts with
    disjoint prefixes (every admission prefills the whole prompt) and a
    HOT burst sharing one of the now-cached prefixes (admissions skip to
    the divergent suffix).  Reports TTFT percentiles per phase, the hot
    hit rate and blocks-in-use vs the contiguous footprint.  CI-asserted:
    the hot burst must actually hit (> 0 rate) and its TTFT p95 must
    beat cold — prefix reuse that doesn't show up in admission latency
    is a regression.
    """
    rng = random.Random(seed)
    plen, slen = 5 * block_size, block_size          # 40 + 8 token prompts
    sched = BatchScheduler(engine, n_slots=n_slots, max_len=max_len,
                           paged_kv=True, block_size=block_size)

    def burst(prompts):
        rids = [sched.submit(prompt_ids=ids, max_new=max_new)
                for ids in prompts]
        sched.drain()
        return sorted(sched.requests[r].t_first_token -
                      sched.requests[r].t_submit for r in rids)

    def prompt(prefix):
        return prefix + [rng.randrange(1, engine.cfg.vocab_size)
                         for _ in range(slen)]

    # warm every trace both phases use (full prefill, suffix
    # continuation, decode, sampler, gather/scatter) before timing
    warm_prefix = [rng.randrange(1, engine.cfg.vocab_size)
                   for _ in range(plen)]
    burst([prompt(warm_prefix)])
    burst([prompt(warm_prefix)])

    prefixes = [[rng.randrange(1, engine.cfg.vocab_size)
                 for _ in range(plen)] for _ in range(n_requests)]
    base = sched.paging_stats()
    cold = burst([prompt(p) for p in prefixes])
    mid = sched.paging_stats()
    hot = burst([prompt(prefixes[0]) for _ in range(n_requests)])
    end = sched.paging_stats()

    hot_hits = end["hits"] - mid["hits"]
    hot_rate = hot_hits / n_requests
    out = {
        "n_requests": n_requests,
        "block_size": block_size,
        "prefix_tokens": plen,
        "cold": {"ttft_p50_s": _pct(cold, 0.50),
                 "ttft_p95_s": _pct(cold, 0.95),
                 "hits": mid["hits"] - base["hits"]},
        "hot": {"ttft_p50_s": _pct(hot, 0.50),
                "ttft_p95_s": _pct(hot, 0.95),
                "hits": hot_hits, "hit_rate": hot_rate},
        "tokens_reused": end["tokens_reused"] - base["tokens_reused"],
        "blocks_in_use_peak": end["n_blocks"] - end["blocks_free"],
        "contiguous_equiv_blocks": n_slots * (max_len // block_size),
        "ttft_p95_hot_speedup": _pct(cold, 0.95) / _pct(hot, 0.95),
    }
    assert hot_rate > 0, f"warm burst never hit the prefix cache: {end}"
    assert out["hot"]["ttft_p95_s"] < out["cold"]["ttft_p95_s"], (
        f"prefix reuse did not improve TTFT p95: {out}")
    return out


def measure(arch: str = "tinyllama-1.1b", reduced: bool = True,
            n_slots: int = 8, max_len: int = 128, max_new: int = 16,
            reps: int = 20) -> dict:
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    engine = Engine(cfg, temperature=0.0)

    # -- decode-step microbench: one batched call vs n_slots serial calls
    batched_s = _time_decode(engine, n_slots, max_len, reps)
    serial_1 = _time_decode(engine, 1, max_len, reps)
    step_batched_tok_s = n_slots / batched_s
    step_serial_tok_s = 1.0 / serial_1   # per-slot loop: one call per token

    # -- end-to-end: scheduler drain vs serial generate per request
    prompts = [f"request {i}: summarize the agentic workflow results"
               for i in range(n_slots)]
    sched = BatchScheduler(engine, n_slots=n_slots, max_len=max_len)
    for p in prompts:   # warm prefill/decode/insert compiles before timing
        sched.submit(p, max_new=2)
    sched.drain()
    rids = [sched.submit(p, max_new=max_new) for p in prompts]
    t0 = time.perf_counter()
    results = sched.drain()
    e2e_batched = time.perf_counter() - t0
    toks = sum(r.new_tokens for r in results.values())

    reqs = [sched.requests[r] for r in rids]
    for r in reqs:   # warm serial compiles before timing
        engine.generate_ids(r.prompt_ids, 1, rid=r.rid,
                            cache_len=sched.max_len)
    t0 = time.perf_counter()
    stoks = 0
    for r in reqs:
        g = engine.generate_ids(r.prompt_ids, r.max_new, rid=r.rid,
                                cache_len=sched.max_len)
        stoks += g.new_tokens
    e2e_serial = time.perf_counter() - t0

    # -- admission latency: bursty arrivals on a FRESH engine (shared
    # weights), so both modes pay their prefill compiles — the quantity
    # being measured; measure_admission warms decode/sampler itself
    adm_engine = Engine(cfg, params=engine.params, temperature=0.0)
    admission = measure_admission(adm_engine, n_slots=n_slots,
                                  max_len=min(max_len, 64))

    # -- paged KV + prefix reuse: hot vs cold admission on a fresh
    # engine (shared weights) so the suffix-continuation traces compile
    # inside the phase that warms them
    from repro.models.model import supports_paged_cache
    if supports_paged_cache(cfg) and engine.supports_fixed_shape_prefill:
        paging_engine = Engine(cfg, params=engine.params, temperature=0.0)
        paging = measure_paging(paging_engine, n_slots=min(n_slots, 4),
                                max_len=min(max_len, 64))
    else:
        paging = {"skipped": f"{cfg.name} has no paged-cache support"}

    return {
        "arch": cfg.name,
        "n_slots": n_slots,
        "max_len": max_len,
        "max_new": max_new,
        "decode_step": {
            "batched_tok_s": step_batched_tok_s,
            "serial_tok_s": step_serial_tok_s,
            "speedup": step_batched_tok_s / step_serial_tok_s,
        },
        "end_to_end": {
            "batched_tok_s": toks / e2e_batched,
            "serial_tok_s": stoks / e2e_serial,
            "speedup": (toks / e2e_batched) / (stoks / e2e_serial),
        },
        "admission": admission,
        "paging": paging,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--full", action="store_true",
                    help="full (non-reduced) config")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--out", default=os.path.join(ART, "BENCH_serving.json"))
    args = ap.parse_args()
    enable_compile_cache()

    rec = measure(args.arch, reduced=not args.full, n_slots=args.slots,
                  max_len=args.max_len, max_new=args.max_new)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)
    ds, ee, adm = rec["decode_step"], rec["end_to_end"], rec["admission"]
    print(f"# serving bench on {rec['arch']} n_slots={rec['n_slots']}")
    print(f"decode_step.batched_tok_s,{ds['batched_tok_s']:.1f},")
    print(f"decode_step.serial_tok_s,{ds['serial_tok_s']:.1f},")
    print(f"decode_step.speedup,{ds['speedup']:.2f},x")
    print(f"end_to_end.batched_tok_s,{ee['batched_tok_s']:.1f},")
    print(f"end_to_end.serial_tok_s,{ee['serial_tok_s']:.1f},")
    print(f"end_to_end.speedup,{ee['speedup']:.2f},x")
    print(f"admission.bucketed.ttft_p50_s,{adm['bucketed']['ttft_p50_s']:.3f},")
    print(f"admission.bucketed.ttft_p95_s,{adm['bucketed']['ttft_p95_s']:.3f},")
    print(f"admission.per_request.ttft_p50_s,"
          f"{adm['per_request']['ttft_p50_s']:.3f},")
    print(f"admission.per_request.ttft_p95_s,"
          f"{adm['per_request']['ttft_p95_s']:.3f},")
    print(f"admission.ttft_p95_speedup,{adm['ttft_p95_speedup']:.2f},x")
    pg = rec["paging"]
    if "skipped" not in pg:
        print(f"paging.cold.ttft_p95_s,{pg['cold']['ttft_p95_s']:.3f},")
        print(f"paging.hot.ttft_p95_s,{pg['hot']['ttft_p95_s']:.3f},")
        print(f"paging.hot.hit_rate,{pg['hot']['hit_rate']:.2f},")
        print(f"paging.tokens_reused,{pg['tokens_reused']},")
        print(f"paging.ttft_p95_hot_speedup,"
              f"{pg['ttft_p95_hot_speedup']:.2f},x")
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
