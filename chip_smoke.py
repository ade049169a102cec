"""Chip smoke test: the serving engine and the AgentX loop on one TPU chip.

Runs in one process, at tinyllama-1.1b's full published widths (22
layers, d_model 2048, vocab 32000, float32, random weights from a seed):

  1. serve  -- 16 requests of 64-500 characters through ``BatchScheduler``
     (8 slots x 2048 context, greedy, 32 new tokens each);
  2. paged  -- the same requests on the same weights through the
     block-paged KV cache; its token streams must equal the contiguous
     ones;
  3. serial -- ``Engine.generate_ids`` for 3 of the requests against
     their batched streams, reported either way (whether batch-1 and
     batch-8 programs round alike on the chip is what this measures);
  4. agent  -- 4 AgentX ``web_search`` runs whose completions go through
     a registered ``jax-batched`` variant serving the same model.

Any failed phase exits nonzero. A host where JAX finds no TPU exits
nonzero before any phase runs. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py
"""
from __future__ import annotations

import gc
import json
import random
import string
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.apps.session import RunSpec, Session  # noqa: E402
from repro.configs import ModelConfig, get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving import (BatchScheduler, Engine, RunMonitor,  # noqa: E402
                           get_llm_backend, register_llm_backend,
                           reset_llm_backends)
from repro.serving.api import JaxBatchedServing  # noqa: E402

ARCH = "tinyllama-1.1b"
N_REQUESTS, MIN_CHARS, MAX_CHARS, MAX_NEW = 16, 64, 500, 32
N_SLOTS, MAX_LEN, BLOCK_SIZE = 8, 2048, 32
N_SERIAL, N_AGENT_RUNS = 3, 4
AGENT_BACKEND = "chip-smoke-batched"


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes() -> Optional[int]:
    """The device's peak allocation so far (None where not reported)."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def make_prompts(lengths: List[int], seed: int) -> List[str]:
    """Prompts of exactly these character counts (``HashTokenizer`` is
    byte-level: n characters encode to n ids plus BOS)."""
    rng = random.Random(seed)
    alphabet = string.ascii_lowercase + " "
    return ["".join(rng.choice(alphabet) for _ in range(n)) for n in lengths]


def serve(engine: Engine, prompts: List[str], warm_prompts: List[str], *,
          n_slots: int, max_len: int, max_new: int, **sched_kw) -> Dict:
    """One scheduler: a warm-up drain that compiles every shape the timed
    drain uses, then the timed drain of ``prompts``.

    The warm-up prompts have the same lengths but other characters, so a
    paged scheduler's prefix cache holds nothing the timed prompts hit."""
    sched = BatchScheduler(engine, n_slots=n_slots, max_len=max_len,
                           **sched_kw)
    t0 = time.perf_counter()
    for p in warm_prompts:
        sched.submit(p, max_new=2)
    sched.drain()
    sched.block_until_ready()
    warm_s = time.perf_counter() - t0

    monitor = RunMonitor()
    sched.subscribe(monitor)
    t0 = time.perf_counter()
    rids = [sched.submit(p, max_new=max_new) for p in prompts]
    results = sched.drain()
    sched.block_until_ready()
    wall_s = time.perf_counter() - t0
    streams = [results[r].token_ids for r in rids]
    return {"rids": rids,
            "prompt_ids": [sched.requests[r].prompt_ids for r in rids],
            "streams": streams, "warmup_s": warm_s, "wall_s": wall_s,
            "new_tokens": sum(len(s) for s in streams),
            "decode_steps": monitor.engine_steps,
            "peak_live": monitor.engine_peak_live}


def first_divergence(a: List[int], b: List[int]) -> Optional[int]:
    """Index of the first differing token (None when equal)."""
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return i
    return None if len(a) == len(b) else min(len(a), len(b))


def engine_phases(cfg: ModelConfig, *, n_requests: int, min_chars: int,
                  max_chars: int, max_new: int, n_slots: int, max_len: int,
                  block_size: int, n_serial: int, seed: int = 0) -> Dict:
    """Serve, paged and serial-vs-batched phases on one engine."""
    t0 = time.perf_counter()
    engine = Engine(cfg, seed=seed, temperature=0.0)
    jax.block_until_ready(engine.params)
    say(f"# engine: {cfg.n_params() / 1e9:.3f}B params initialised in "
        f"{time.perf_counter() - t0:.2f}s")

    rng = random.Random(seed)
    lengths = [rng.randint(min_chars, max_chars) for _ in range(n_requests)]
    prompts = make_prompts(lengths, seed)
    warm = make_prompts(lengths, seed + 1)

    t0 = time.perf_counter()
    logits, _ = engine.prefill_ids(engine.tokenizer.encode(prompts[0]),
                                   max_len)
    logits = jax.block_until_ready(logits)
    check(bool(jnp.isfinite(logits).all()),
          "prefill_ids: last logits are not finite")
    say(f"# prefill_ids: {len(prompts[0]) + 1} tokens, logits "
        f"{tuple(logits.shape)} finite, {time.perf_counter() - t0:.2f}s "
        f"(compile included)")

    sizes = dict(n_slots=n_slots, max_len=max_len, max_new=max_new)
    contig = serve(engine, prompts, warm, **sizes)
    say(f"# serve: {n_requests} requests ({min(lengths)}-{max(lengths)} "
        f"chars), {n_slots} slots x {max_len}: warm-up (compiles) "
        f"{contig['warmup_s']:.2f}s; steady {contig['wall_s']:.3f}s, "
        f"{contig['new_tokens']} new tokens, {contig['decode_steps']} "
        f"decode steps, peak occupancy {contig['peak_live']}/{n_slots}, "
        f"peak_bytes_in_use {peak_bytes()}")

    paged = serve(engine, prompts, warm, paged_kv=True,
                  block_size=block_size, **sizes)
    same = paged["streams"] == contig["streams"]
    say(f"# paged (block {block_size}): warm-up {paged['warmup_s']:.2f}s; "
        f"steady {paged['wall_s']:.3f}s, {paged['new_tokens']} new tokens, "
        f"{paged['decode_steps']} decode steps; streams == contiguous: "
        f"{same}")
    check(same, "paged greedy streams differ from contiguous: "
          + str([first_divergence(a, b) for a, b in
                 zip(contig["streams"], paged["streams"])]))

    divergences = []
    t0 = time.perf_counter()
    for i in range(min(n_serial, n_requests)):
        serial = engine.generate_ids(contig["prompt_ids"][i], max_new,
                                     rid=contig["rids"][i],
                                     cache_len=max_len).token_ids
        step = first_divergence(serial, contig["streams"][i])
        divergences.append(step)
        say(f"# serial vs batched: request {i} "
            + ("equal" if step is None else f"diverges at step {step}: "
               f"serial {serial[step:step + 4]} batched "
               f"{contig['streams'][i][step:step + 4]}"))
    say(f"# serial: {len(divergences)} requests in "
        f"{time.perf_counter() - t0:.2f}s (compile included); "
        f"equal {sum(d is None for d in divergences)}/{len(divergences)}")
    return {"contiguous": contig, "paged": paged,
            "serial_divergence": divergences}


def agent_phase(arch: str, *, reduced: bool, n_slots: int, max_len: int,
                n_runs: int, instance: str = "quantum") -> Dict:
    """AgentX runs whose completions the batched engine serves, through a
    backend variant registered the documented way."""
    register_llm_backend(AGENT_BACKEND, arch=arch, reduced=reduced,
                         n_slots=n_slots, max_len=max_len)(JaxBatchedServing)
    monitor = RunMonitor()
    get_llm_backend(AGENT_BACKEND).subscribe(monitor)
    specs = [RunSpec("web_search", instance, "agentx", "local", seed=s,
                     llm=AGENT_BACKEND) for s in range(n_runs)]
    t0 = time.perf_counter()
    results = Session(on_event=monitor).execute_many(specs,
                                                     max_workers=n_runs)
    wall_s = time.perf_counter() - t0
    reset_llm_backends()   # drop the engine: its weights leave the device
    check(len(results) == n_runs and all(r is not None for r in results),
          f"agent: {len(results)} results for {n_runs} runs")
    check(monitor.engine_steps > 0, "agent: no completion reached the engine")
    out = {"success": [r.success for r in results], "wall_s": wall_s,
           "llm_calls": monitor.snapshot()["llm_calls"],
           "engine_steps": monitor.engine_steps,
           "engine_tokens": monitor.engine_tokens,
           "peak_live": monitor.engine_peak_live}
    say(f"# agent: {n_runs} agentx web_search runs via {AGENT_BACKEND!r} "
        f"({n_slots} slots x {max_len}) in {wall_s:.2f}s (compiles "
        f"included): success {out['success']}, llm_calls "
        f"{out['llm_calls']}, engine_steps {out['engine_steps']}, "
        f"engine_tokens {out['engine_tokens']}, peak occupancy "
        f"{out['peak_live']}/{n_slots}")
    return out


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    say(f"# device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    say(f"# compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)
    say(f"# model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} q / {cfg.n_kv_heads} kv heads x {cfg.head_dim}, "
        f"vocab {cfg.vocab_size}, float32")
    t_all = time.perf_counter()
    engine_phases(cfg, n_requests=N_REQUESTS, min_chars=MIN_CHARS,
                  max_chars=MAX_CHARS, max_new=MAX_NEW, n_slots=N_SLOTS,
                  max_len=MAX_LEN, block_size=BLOCK_SIZE, n_serial=N_SERIAL)
    gc.collect()   # the phases' engine and caches leave the device
    agent_phase(ARCH, reduced=False, n_slots=N_SLOTS, max_len=MAX_LEN,
                n_runs=N_AGENT_RUNS)
    say(f"# total {time.perf_counter() - t_all:.1f}s, peak_bytes_in_use "
        f"{peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
