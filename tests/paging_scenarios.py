"""Shared scenario machinery for the paged-KV parity battery.

Both the always-on seeded tests (``test_paging.py``) and the
hypothesis property suite (``test_properties.py``) drive the same
generator + runner: a scenario is a list of requests (prompt ids,
priority, budget, arrival step) and the assertion is always the same —
the paged scheduler's per-request token streams are bit-identical to
the contiguous scheduler's, which are bit-identical to serial
generation.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from repro.configs import get_config
from repro.core.events import EngineStepped
from repro.serving import BatchScheduler, Engine

BLOCK = 8          # scenario block size: small enough to cross often
MAX_LEN = 64

_ENGINES: Dict[tuple, Engine] = {}


def _mla_dense_cfg():
    # deepseek's reduced config is MLA+MoE; MoE capacity dispatch is
    # batch-composition-dependent, so parity runs on an MLA-dense variant
    import dataclasses
    cfg = get_config("deepseek-v2-236b").reduced()
    return dataclasses.replace(cfg, arch_type="dense", moe=None)


def get_engine(arch: str, temperature: float, chunk: int = 0) -> Engine:
    """Engines are stateless across schedulers (the scheduler owns the
    cache) — build each (arch, temperature, chunk) once per process."""
    key = (arch, temperature, chunk)
    if key not in _ENGINES:
        cfg = (_mla_dense_cfg() if arch == "mla"
               else get_config("tinyllama-1.1b").reduced())
        _ENGINES[key] = Engine(cfg, temperature=temperature,
                               prefill_chunk=chunk)
    return _ENGINES[key]


def gen_scenario(rng, n_req: int, *, vocab: int = 400,
                 max_new_hi: int = 6) -> List[dict]:
    """Random request mix biased toward the paging edge cases: shared
    prefix groups, block-boundary prompt lengths (len % BLOCK in
    {0, 1, BLOCK-1}), priority classes, staggered arrivals."""
    shared = [int(rng.integers(1, vocab))
              for _ in range(int(rng.integers(BLOCK, 3 * BLOCK + 1)))]
    reqs = []
    for i in range(n_req):
        if rng.random() < 0.6:                  # shared-prefix group
            base = list(shared)
        else:
            base = [int(rng.integers(1, vocab))
                    for _ in range(int(rng.integers(1, 2 * BLOCK)))]
        # land total lengths on/next to block boundaries half the time;
        # prompts range all the way up to MAX_LEN - max_new_hi - 2, so the
        # battery exercises prefill_bucket(len) == MAX_LEN (the historical
        # half-context submit clamp that desynced the serial cross-check
        # at that edge is fixed; prompt+generation must still fit the
        # fixed cache for the serial comparison to stay meaningful)
        if rng.random() < 0.5:
            target = int(rng.integers(1, 8)) * BLOCK + int(rng.integers(-1, 2))
            target = max(len(base) + 1,
                         min(target, MAX_LEN - max_new_hi - 2))
        else:
            target = len(base) + int(rng.integers(1, BLOCK + 1))
        ids = base + [int(rng.integers(1, vocab))
                      for _ in range(target - len(base))]
        reqs.append({"ids": ids,
                     "priority": int(rng.integers(0, 3)),
                     "max_new": int(rng.integers(1, max_new_hi + 1)),
                     "at": int(rng.integers(0, 6))})
    return reqs


def run_scenario(engine: Engine, scenario: List[dict], *,
                 paged: bool, prefix: bool = True,
                 n_slots: int = 2, n_blocks: Optional[int] = None,
                 events: Optional[list] = None) -> Dict[int, List[int]]:
    """Drive one scheduler over the scenario's arrival schedule; returns
    {request index: generated token ids}."""
    kw: dict = {}
    if paged:
        kw = dict(paged_kv=True, block_size=BLOCK, n_blocks=n_blocks,
                  prefix_cache=prefix)
    sched = BatchScheduler(engine, n_slots=n_slots, max_len=MAX_LEN, **kw)
    if events is not None:
        sched.subscribe(lambda e: events.append(e)
                        if isinstance(e, EngineStepped) else None)
    order = sorted(range(len(scenario)), key=lambda i: scenario[i]["at"])
    rid_to_idx: Dict[int, int] = {}
    out: Dict[int, List[int]] = {}
    pos, step = 0, 0
    while len(out) < len(scenario):
        while pos < len(order) and scenario[order[pos]]["at"] <= step:
            r = scenario[order[pos]]
            rid = sched.submit(prompt_ids=r["ids"], max_new=r["max_new"],
                               priority=r["priority"])
            rid_to_idx[rid] = order[pos]
            pos += 1
        for fin in sched.step():
            out[rid_to_idx[fin.rid]] = list(fin.out_ids)
        step += 1
        assert step < 10_000, "scenario did not drain"
    return out


def serial_tokens(engine: Engine, scenario: List[dict],
                  rid_of: Dict[int, int]) -> Dict[int, List[int]]:
    """Uninterrupted per-request generation with the same sampling keys
    the schedulers use (rid = submission order)."""
    out = {}
    for idx, r in enumerate(scenario):
        res = engine.generate_ids(r["ids"], r["max_new"], rid=rid_of[idx],
                                  cache_len=MAX_LEN)
        out[idx] = list(res.token_ids)
    return out


def submission_rids(scenario: List[dict]) -> Dict[int, int]:
    """rid each request gets from the runner's arrival-ordered submit
    loop (stable sort by arrival step)."""
    order = sorted(range(len(scenario)), key=lambda i: scenario[i]["at"])
    return {idx: rid for rid, idx in enumerate(order)}


def assert_parity(engine: Engine, scenario: List[dict], *,
                  n_blocks: Optional[int] = None,
                  check_serial: bool = True,
                  lockstep: bool = True) -> None:
    """The battery's core assertion: contiguous == paged+prefix ==
    paged-no-prefix (== serial), and the no-prefix paged event stream
    matches contiguous modulo the paging gauges.  ``lockstep=False``
    where the pool is tight enough to defer an admission that the
    contiguous cache makes: the tokens still match, the step counts
    need not."""
    ev_contig: list = []
    ev_paged: list = []
    contig = run_scenario(engine, scenario, paged=False, events=ev_contig)
    paged = run_scenario(engine, scenario, paged=True, n_blocks=n_blocks)
    noprefix = run_scenario(engine, scenario, paged=True, prefix=False,
                            n_blocks=n_blocks, events=ev_paged)
    assert paged == contig, f"paged+prefix diverged: {paged} != {contig}"
    assert noprefix == contig, f"paged-no-prefix diverged: {noprefix}"
    if check_serial:
        serial = serial_tokens(engine, scenario, submission_rids(scenario))
        assert serial == contig, f"contiguous diverged from serial: {serial}"
    if not lockstep:
        return
    # without prefix reuse (and with blocks to spare) the step loop is
    # lockstep-identical, so every event field except the paging gauges
    # must match exactly
    assert len(ev_contig) == len(ev_paged)
    for a, b in zip(ev_contig, ev_paged):
        for f in ("t", "live", "queued", "generated", "prefilled",
                  "preempted"):
            assert getattr(a, f) == getattr(b, f), (
                f"event field {f}: contiguous {a} vs paged {b}")
