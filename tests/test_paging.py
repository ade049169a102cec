"""Paged KV cache: allocator/prefix-cache units, an invariant-checking
allocator fuzz, and the seeded paged-vs-contiguous parity battery.

Acceptance criteria of the paging tentpole:
  * block allocator refcounting survives randomized alloc / incref /
    decref / fork sequences with invariants checked after EVERY op
    (the failing seed is printed for replay);
  * the content-hashed prefix cache matches only full blocks, caps the
    match so at least one token is freshly prefilled, and LRU-evicts;
  * paged decode is bit-identical to the contiguous scheduler —
    which is bit-identical to serial generation — across GQA/MLA,
    greedy and sampled, whole/bucketed/chunked admission, shared-prefix
    groups, block-boundary lengths, preemption and pool exhaustion;
  * with prefix reuse off the paged event stream matches the
    contiguous one field-for-field modulo the new paging gauges.
"""
import numpy as np
import pytest

from paging_scenarios import (BLOCK, MAX_LEN, assert_parity, gen_scenario,
                              get_engine, run_scenario)
from repro.serving import (BatchScheduler, BlockAllocator, PagingError,
                          PrefixCache, RunMonitor, prefix_block_keys)
from repro.core.events import EngineStepped

# ---------------------------------------------------------------------------
# block allocator


def test_allocator_alloc_free_cycle():
    a = BlockAllocator(4, 8)
    got = [a.alloc() for _ in range(4)]
    assert sorted(got) == [0, 1, 2, 3]
    assert a.alloc() is None and a.free_count == 0 and a.in_use == 4
    assert a.decref(2) is True          # freed
    assert a.alloc() == 2               # FIFO reuse
    a.incref(2)
    assert a.decref(2) is False         # still referenced
    assert a.decref(2) is True


def test_allocator_double_free_raises():
    a = BlockAllocator(2, 8)
    b = a.alloc()
    a.decref(b)
    with pytest.raises(PagingError):
        a.decref(b)


def test_allocator_fork_semantics():
    a = BlockAllocator(2, 8)
    b = a.alloc()
    assert a.fork(b) == (b, False)      # sole owner: no copy
    a.incref(b)
    new, needs_copy = a.fork(b)
    assert needs_copy and new != b      # shared: one ref moves off
    assert a.ref(b) == 1 and a.ref(new) == 1
    a.incref(b)                         # share b again; pool now empty
    assert a.fork(b) is None            # copy needed -> caller must evict


def _check_invariants(a: BlockAllocator, refs: dict):
    held = {b: n for b, n in refs.items() if n > 0}
    assert a.in_use == len(held)
    assert a.free_count + a.in_use == a.n_blocks
    for b, n in held.items():
        assert a.ref(b) == n, f"block {b}: model {n} != allocator {a.ref(b)}"


def test_allocator_fuzz():
    """Randomized op soup; the shadow refcount model and the allocator
    must agree after every single operation."""
    seed = np.random.SeedSequence().entropy % (2 ** 32)
    rng = np.random.default_rng(seed)
    try:
        a = BlockAllocator(12, 8)
        refs: dict = {}
        for _ in range(2000):
            held = [b for b, n in refs.items() if n > 0]
            op = rng.integers(0, 4)
            if op == 0:
                b = a.alloc()
                if b is None:
                    assert a.free_count == 0
                else:
                    assert refs.get(b, 0) == 0
                    refs[b] = 1
            elif op == 1 and held:
                b = int(rng.choice(held))
                a.incref(b)
                refs[b] += 1
            elif op == 2 and held:
                b = int(rng.choice(held))
                freed = a.decref(b)
                refs[b] -= 1
                assert freed == (refs[b] == 0)
            elif op == 3 and held:
                b = int(rng.choice(held))
                got = a.fork(b)
                if refs[b] == 1:
                    assert got == (b, False)
                elif got is None:
                    assert a.free_count == 0
                else:
                    new, needs_copy = got
                    assert needs_copy and refs.get(new, 0) == 0
                    refs[b] -= 1
                    refs[new] = 1
            _check_invariants(a, refs)
    except AssertionError:
        raise AssertionError(f"allocator fuzz failed with seed {seed}")


# ---------------------------------------------------------------------------
# prefix cache


def test_prefix_chain_keys():
    ids = list(range(20))
    keys = prefix_block_keys(ids, 8, "salt")
    assert len(keys) == 2               # only FULL blocks are keyed
    # chained: a diverging first block changes every downstream key
    other = prefix_block_keys([99] + ids[1:], 8, "salt")
    assert keys[0] != other[0] and keys[1] != other[1]
    # same chain, different salt -> disjoint key space
    assert prefix_block_keys(ids, 8, "other")[0] != keys[0]
    # prefix property: shared leading blocks share leading keys
    assert prefix_block_keys(ids[:16] + [500], 8, "salt")[:2] == keys


def test_prefix_cache_match_cap_and_lru():
    a = BlockAllocator(16, 4)
    pc = PrefixCache(a, salt="s")
    ids = list(range(12))
    blocks = [a.alloc() for _ in range(3)]
    pc.insert(ids, blocks)              # caches 3 full blocks
    # exact-length match is capped one block short: the last position
    # must be freshly prefilled for its logits
    n, got = pc.match(ids)
    assert n == 8 and got == blocks[:2]
    n, got = pc.match(ids + [50])       # longer prompt: all 3 usable
    assert n == 12 and got == blocks
    assert pc.match([99, 98, 97, 96])[0] == 0
    # cached blocks are pinned: the insert incref survives our decref
    for b in blocks:
        a.decref(b)
    assert a.in_use == 3
    pc.evict()                          # LRU pop releases the pin
    assert a.in_use == 2 and len(pc) == 2
    s = pc.stats()
    assert s["hits"] == 2 and s["misses"] == 1 and s["tokens_reused"] == 20


# ---------------------------------------------------------------------------
# scheduler-level paging behaviour


def test_paged_scheduler_rejects_bad_geometry():
    eng = get_engine("gqa", 0.0)
    with pytest.raises(ValueError):
        BatchScheduler(eng, n_slots=2, max_len=MAX_LEN, paged_kv=True,
                       block_size=7)   # max_len % block_size != 0
    with pytest.raises(ValueError):
        BatchScheduler(eng, n_slots=2, max_len=MAX_LEN, paged_kv=True,
                       block_size=BLOCK, n_blocks=3)  # < one sequence


def test_paged_exhaustion_requeues_and_recovers():
    """A pool two sequences wide still serves six requests: admission
    failures requeue instead of deadlocking, stats stay coherent."""
    eng = get_engine("gqa", 0.0)
    sched = BatchScheduler(eng, n_slots=2, max_len=MAX_LEN, paged_kv=True,
                           block_size=BLOCK, n_blocks=2 * (MAX_LEN // BLOCK))
    rids = [sched.submit(prompt_ids=[i + 1] * 21, max_new=4)
            for i in range(6)]
    res = sched.drain()
    assert sorted(res) == sorted(rids)
    assert all(len(res[r].token_ids) == 4 for r in rids)
    s = sched.paging_stats()
    # drained: the only live references left are the prefix cache's pins
    assert s["blocks_in_use"] == s["entries"]
    assert s["blocks_free"] + s["blocks_in_use"] == s["n_blocks"]


def test_paged_prefix_hits_and_gauges():
    """Same-prefix admissions hit the prefix cache; EngineStepped
    carries live blocks_in_use and cumulative prefix_hits, and
    RunMonitor aggregates them."""
    eng = get_engine("gqa", 0.0)
    sched = BatchScheduler(eng, n_slots=2, max_len=MAX_LEN, paged_kv=True,
                           block_size=BLOCK)
    mon = RunMonitor()
    events = []
    sched.subscribe(lambda e: (mon(e), events.append(e))
                    if isinstance(e, EngineStepped) else None)
    base = list(range(1, 18))
    for i in range(4):
        sched.submit(prompt_ids=base + [100 + i], max_new=3)
    sched.drain()
    s = sched.paging_stats()
    assert s["hits"] >= 3 and s["tokens_reused"] >= 3 * 16
    assert max(e.blocks_in_use for e in events) > 0
    assert max(e.prefix_hits for e in events) >= 1
    snap = mon.snapshot()
    assert snap["engine_prefix_hits"] >= 3
    assert snap["engine_blocks_in_use"] >= 0


def test_paged_miss_admission_uses_bucketed_program(monkeypatch):
    """A prefix-cache miss prefills through the contiguous path's
    batch-``n_slots`` program: on the TPU a batch-1 prefill rounds
    differently, and paged == contiguous would break there."""
    eng = get_engine("gqa", 0.0)
    widths = []
    batch = eng.prefill_batch_ids

    def spy(ids_list, cache_len, width=None):
        widths.append(width)
        return batch(ids_list, cache_len, width=width)

    def batch_1(*args, **kwargs):
        raise AssertionError("paged admission ran a batch-1 prefill")

    monkeypatch.setattr(eng, "prefill_batch_ids", spy)
    monkeypatch.setattr(eng, "prefill_ids", batch_1)
    sched = BatchScheduler(eng, n_slots=3, max_len=MAX_LEN, paged_kv=True,
                           block_size=BLOCK)
    sched.submit(prompt_ids=list(range(1, 12)), max_new=2)
    sched.drain()
    assert widths == [3]


def test_contiguous_emits_zero_paging_gauges():
    """With paging off the new gauges stay at their defaults — the
    wire payload is exactly the pre-paging one."""
    eng = get_engine("gqa", 0.0)
    sched = BatchScheduler(eng, n_slots=2, max_len=MAX_LEN)
    events = []
    sched.subscribe(lambda e: events.append(e)
                    if isinstance(e, EngineStepped) else None)
    sched.submit(prompt_ids=list(range(1, 10)), max_new=3)
    sched.drain()
    assert events
    assert all(e.blocks_in_use == 0 and e.prefix_hits == 0 for e in events)


# ---------------------------------------------------------------------------
# parity battery (seeded-random; the hypothesis suite widens the search)

PARITY_CASES = [
    ("gqa", 0.0, 0, 11),     # greedy, whole-prompt/bucketed admission
    ("gqa", 1.0, 0, 12),     # sampled
    ("gqa", 1.0, 8, 13),     # sampled + chunked prefill
    ("mla", 0.0, 0, 14),     # MLA cache family, greedy
    ("mla", 1.0, 8, 15),     # MLA sampled + chunked
]


@pytest.mark.parametrize("arch,temp,chunk,seed", PARITY_CASES,
                         ids=[f"{a}-t{t}-c{c}" for a, t, c, _ in PARITY_CASES])
def test_paged_parity(arch, temp, chunk, seed):
    rng = np.random.default_rng(seed)
    eng = get_engine(arch, temp, chunk)
    scenario = gen_scenario(rng, n_req=6)
    assert_parity(eng, scenario)


def test_paged_parity_tight_pool():
    """Pool sized for barely over one sequence: constant eviction,
    exhaustion-requeue and CoW churn must not change a single token."""
    rng = np.random.default_rng(21)
    eng = get_engine("gqa", 1.0, 8)
    scenario = gen_scenario(rng, n_req=6)
    assert_parity(eng, scenario, n_blocks=MAX_LEN // BLOCK + 2,
                  check_serial=False)


def test_paged_parity_under_preemption():
    """Late high-priority arrivals preempt live low-priority slots;
    resumed requests replay into fresh blocks bit-identically."""
    rng = np.random.default_rng(31)
    eng = get_engine("gqa", 1.0, 8)
    scenario = gen_scenario(rng, n_req=4, max_new_hi=10)
    for r in scenario:
        r["priority"], r["at"] = 0, 0
    late = gen_scenario(rng, n_req=2)
    for r in late:
        r["priority"], r["at"] = 5, 4
    assert_parity(eng, scenario + late)
