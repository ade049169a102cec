"""Continuous-batching scheduler v2: batched + chunked prefill with
priority preemption, with an optional block-paged KV mode.

Slot-based, vLLM-style, TPU-friendly fixed shapes:

  * the decode cache carries an ``n_slots`` batch axis allocated once
    (``init_cache(cfg, n_slots, max_len)``);
  * every :meth:`BatchScheduler.step` runs ONE jitted ``decode_step``
    over the whole slot batch with a per-slot position *vector* — live
    slots advance together, finished slots free their row and queued
    requests are admitted into it.

**Paged KV mode** (``paged_kv=True``): the dense ``n_slots x max_len``
cache is replaced by a refcounted block pool
(:mod:`repro.serving.paging` bookkeeping +
:func:`repro.models.model.init_paged_cache` arrays) with per-slot block
tables, and a content-hashed prefix cache on top — admissions whose
leading full blocks match a cached prefix pin the SHARED blocks and
prefill only the divergent suffix (``Engine.prefill_continue``).  The
decode step stays bit-identical to the contiguous path by construction:
the pool is gathered through the block tables into the exact dense view
the contiguous cache holds (``max_len % block_size == 0`` makes the
widths equal), that view runs through the SAME jitted ``decode_step``
executable, and the freshly written rows scatter back into the pool.
Junk rows gathered from recycled blocks sit beyond every sequence's
valid length, where the decode validity mask zeroes them exactly as it
zeroes the contiguous cache's stale rows.  Preemption frees the
victim's blocks; resume re-pins (prefix blocks re-shared, the rest
freshly allocated).  The contiguous path stays the default — parity is
testable request-for-request (``tests/test_properties.py``).

Admission (the v2 overhaul) no longer prefills one request per exact
prompt length:

  * **bucketed batched prefill** — waiting requests are padded to shared
    power-of-two length buckets (:func:`repro.serving.engine.prefill_bucket`)
    and a same-bucket group is prefilled into the freed slots with ONE
    jitted call per bucket (``Engine.prefill_batch_ids``), eliminating
    per-length recompiles from the admission path;
  * **chunked prefill** — a prompt longer than the engine's
    ``prefill_chunk`` budget is prefilled one fixed-shape chunk per
    scheduler step (:class:`repro.serving.engine.PrefillJob`) while live
    slots keep decoding, so a long prompt *bounds* rather than
    monopolizes the stall it imposes;
  * **priority classes + preemption** — ``submit(priority=...)`` feeds a
    priority queue (FIFO within a class); when a waiting request
    outranks the lowest-priority live slot and no slot is free, that
    slot is evicted and requeued *keeping its generated tokens*; on
    re-admission the engine replays them through the identical decode
    recipe (``Engine.replay_ids``), so a preempted request's token
    stream is bit-identical to an uninterrupted run.

Sampling is keyed by (engine seed, request id, step) via
``Engine.sample``, and all three admission paths share the engine's
canonical prefill recipe — a request's token sequence is bit-identical
to serial ``Engine.generate_ids`` whether it was admitted alone, inside
a bucket batch, in chunks, or after an eviction (enforced by test).

``EngineClient`` is the blocking handle that multiplexes many concurrent
agent runs onto one scheduler: callers block in ``generate`` while one of
them pumps ``step()`` — fan-out runs (``Session.execute_many`` workers)
therefore share the decode batch instead of serializing on the engine.

Observability: each step emits a serving-side
:class:`repro.core.events.EngineStepped` run event (occupancy, queue
depth, tokens decoded, prompt tokens prefilled, slots preempted) to
subscribers — ``RunMonitor`` consumes it live.
"""
from __future__ import annotations

import dataclasses
import heapq
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.events import EngineStepped
from ..models.model import (copy_block, gather_cache, init_cache,
                            init_paged_cache, scatter_cache,
                            scatter_decode_rows, supports_paged_cache)
from .engine import (Engine, GenerationResult, PrefillJob, cache_leaf_name,
                     prefill_bucket)
from .paging import BlockAllocator, PrefixCache


@dataclasses.dataclass
class Request:
    """One in-flight generation request.

    ``priority``: higher jumps the queue (FIFO within a class).
    ``seq``: the submission ticket — preserved across preemptions so a
    requeued request keeps its place among equal-priority peers.
    ``tenant``: the billing principal (multi-tenant serving) — under
    fair-share admission requests queue per tenant and slots are granted
    in deficit-round-robin order across tenants.
    ``t_submit`` / ``t_first_token``: wall-clock stamps (``time.perf_counter``)
    used by ``benchmarks/serving.py`` for admission-latency (TTFT)
    percentiles.
    """
    rid: int
    prompt_ids: List[int]
    max_new: int
    priority: int = 0
    tenant: str = ""
    out_ids: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    seq: int = 0
    preemptions: int = 0
    t_submit: float = 0.0
    t_first_token: float = 0.0

    def to_result(self, tokenizer) -> GenerationResult:
        return GenerationResult(tokenizer.decode(self.out_ids),
                                len(self.prompt_ids), len(self.out_ids),
                                list(self.out_ids))


# cache leaves carry their slot (batch) axis at a name-dependent offset
# from the right: (*stack, B, C, Hkv, hd) for k/v, (*stack, B, nh, hd, ds)
# for ssd states, (*stack, B, C, r) for MLA, (*stack, B, W-1, ch) for conv.
_ROW_AXIS_OFFSET = {"k": 4, "v": 4, "ssd": 4, "ckv": 3, "kpe": 3, "conv": 3}


def write_slot(batched_cache, row_cache, slot):
    """Write a batch-1 cache (already padded to the batched cache's seq
    length, see ``pad_cache_to``) into row ``slot`` of the slot-batched
    decode cache. Works for every cache family (GQA/MLA/SSM/hybrid) via
    the leaf-name -> batch-axis table."""
    def ins(path, big, small):
        axis = big.ndim - _ROW_AXIS_OFFSET[cache_leaf_name(path)]
        return jax.lax.dynamic_update_slice_in_dim(
            big, small.astype(big.dtype), slot, axis)
    return jax.tree_util.tree_map_with_path(ins, batched_cache, row_cache)


def take_slot(batched_cache, slot):
    """Inverse of :func:`write_slot`: slice row ``slot`` out of a
    slot-batched cache as a batch-1 cache (used to move rows of a
    bucketed batch-prefill result into their target slots)."""
    def take(path, big):
        axis = big.ndim - _ROW_AXIS_OFFSET[cache_leaf_name(path)]
        return jax.lax.dynamic_slice_in_dim(big, slot, 1, axis)
    return jax.tree_util.tree_map_with_path(take, batched_cache)


class BatchScheduler:
    """Drives an Engine's model with a fixed slot batch.

    ``submit()`` enqueues (with a priority class); ``step()`` runs one
    scheduler cycle — preempt, admit, decode — and ``drain()`` steps to
    completion. ``run()`` is the historical drain-to-text entry point.

    One ``step()`` performs, in order:

    1. *preempt*: if the queue head outranks the lowest-priority live
       slot and no slot is free, that slot is evicted and requeued (at
       most one eviction per step — bounds thrash); equal priority never
       preempts;
    2. *admit*: advance the in-flight chunked admission by ONE chunk,
       then fill free slots in strict priority order — same-bucket
       groups via one batched prefill call, preempted requests via
       decode replay, long prompts by starting a chunk job;
    3. *decode*: ONE jitted ``decode_step`` over the whole slot batch
       advances every live slot by a token.

    ``batched_prefill=False`` restores the v1 admission (one
    exact-length prefill per request, a trace per prompt length) — kept
    as the benchmark baseline; the bit-identical-to-serial contract is
    guaranteed for the default ``True``.

    ``requests`` keeps per-rid bookkeeping for inspection after a
    bounded submit/drain cycle; long-lived callers should go through
    :class:`EngineClient`, which prunes completed entries.

    Invariants (tested):
      * a request's tokens are bit-identical to serial
        ``Engine.generate_ids(prompt_ids, max_new, rid, cache_len=max_len)``
        across bucketed, chunked and preempted admission;
      * a preempted request never loses generated tokens, and never
        resumes with different ones;
      * slots are preempted only by strictly higher priority.
    """

    def __init__(self, engine: Engine, n_slots: int = 4,
                 max_len: int = 512,
                 on_event: Optional[Callable] = None,
                 batched_prefill: bool = True,
                 fair_share=None,
                 paged_kv: bool = False,
                 block_size: int = 32,
                 n_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 prefix_salt: str = ""):
        self.engine = engine
        self.cfg = engine.cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.batched_prefill = batched_prefill
        self._offset = self.cfg.frontend_positions if self.cfg.frontend else 0
        self._cache_len = max_len + self._offset
        # priority queue of (-priority, seq, Request): highest priority
        # first, FIFO (submission ticket) within a class
        self._heap: List[Tuple[int, int, Request]] = []
        # fair-share admission (multi-tenant serving): per-tenant heaps
        # drained in deficit-round-robin order — DRR picks WHICH tenant
        # admits next, priority classes still order WITHIN a tenant.
        # ``fair_share`` is the weight source (TenantRegistry / dict /
        # callable / True for equal weights); None keeps the single
        # global heap, bit-identical to the pre-tenancy scheduler.
        if fair_share is not None:
            from ..tenancy.fair_share import TenantQueue
            self._tq: Optional["TenantQueue"] = TenantQueue(
                None if fair_share is True else fair_share)
        else:
            self._tq = None
        self._qlock = threading.Lock()
        self.slots: List[Optional[Request]] = [None] * n_slots
        self._reserved: set = set()   # slots held by an in-flight chunk job
        self._chunk_job: Optional[Tuple[PrefillJob, Request, int]] = None
        self.requests: Dict[int, Request] = {}
        self._next_rid = 0
        self._seq = 0
        self._steps = 0
        self._pos = [0] * n_slots   # next decode position per slot
        self._tok = [0] * n_slots   # last sampled token per slot
        dtype = self.engine.params["embed"].dtype
        self._paged = bool(paged_kv)
        if self._paged:
            if (not supports_paged_cache(self.cfg)
                    or not engine.supports_fixed_shape_prefill):
                raise NotImplementedError(
                    f"paged KV needs an attention cache with fixed-shape "
                    f"prefill; {self.cfg.name} keeps the contiguous path")
            if max_len % block_size != 0:
                # gathered view width (max_blocks * block_size) must equal
                # the contiguous cache width — that equality is what lets
                # both paths share one decode executable (bit parity)
                raise ValueError(
                    f"max_len ({max_len}) must be a multiple of "
                    f"block_size ({block_size})")
            self.block_size = int(block_size)
            self._mb = max_len // self.block_size   # max blocks / sequence
            self.n_blocks = (int(n_blocks) if n_blocks is not None
                             else n_slots * self._mb)
            if self.n_blocks < self._mb:
                raise ValueError(
                    "n_blocks must cover at least one full-length sequence")
            # physical pool carries one extra TRASH block (index n_blocks):
            # the scatter target for rows outside a sequence's allocated
            # blocks (prefill padding, shared-prefix redirects, dead slots)
            self._trash = self.n_blocks
            self._alloc = BlockAllocator(self.n_blocks, self.block_size)
            self._prefix = (PrefixCache(self._alloc,
                                        salt=f"{self.cfg.name}:{prefix_salt}")
                            if prefix_cache else None)
            self._pool = init_paged_cache(self.cfg, self.n_blocks + 1,
                                          self.block_size, dtype=dtype)
            self._blocks: List[List[int]] = [[] for _ in range(n_slots)]
            self._tables_dirty = True
            self._tables_dev = None
            self._cache = None
            self._gather = jax.jit(gather_cache)
            self._scatter_rows = jax.jit(scatter_decode_rows,
                                         donate_argnums=(0,))
            self._scatter_prefill = jax.jit(scatter_cache,
                                            donate_argnums=(0,))
            self._copy = jax.jit(copy_block, donate_argnums=(0,))
        else:
            self._prefix = None
            self._cache = init_cache(self.cfg, n_slots, self._cache_len,
                                     dtype=dtype)
        # batched cache is donated through admission writes too: the slot
        # row update happens in place instead of copying all slots
        self._insert = jax.jit(write_slot, donate_argnums=(0,))
        self._take = jax.jit(take_slot)
        self._subscribers: List[Callable] = []
        if on_event is not None:
            self._subscribers.append(on_event)

    # -- events -------------------------------------------------------------
    def subscribe(self, fn: Callable) -> None:
        self._subscribers.append(fn)

    def _emit(self, event) -> None:
        for fn in self._subscribers:
            fn(event)

    # -- admission ----------------------------------------------------------
    def submit(self, prompt: Optional[str] = None, max_new: int = 32,
               prompt_ids: Optional[List[int]] = None,
               priority: int = 0, tenant: str = "") -> int:
        """Enqueue one request; returns its rid. Thread-safe.

        ``priority``: higher-priority requests are admitted first and may
        preempt lower-priority live slots; within a class admission is
        FIFO. ``tenant``: under fair-share admission the request queues
        with its tenant's peers and waits its tenant's DRR turn.
        ``max_new`` is clamped to the slot context minus one, then the
        prompt keeps its last ``max_len - max_new`` ids — the requested
        decode budget is always honored and prompt+generation always fit
        the fixed cache.  Prompts that fit are admitted verbatim even
        when their prefill bucket equals ``max_len``: the fixed-shape
        prefill recipe masks the padded cache rows (``col <= q_pos``)
        and decode overwrites them before they become visible, so
        ``bucket == cache_len`` is exact — the historical half-context
        clamp (which silently dropped prompt heads and desynced the
        serial cross-check) is gone."""
        ids = (list(prompt_ids) if prompt_ids is not None
               else self.engine.tokenizer.encode(prompt))
        max_new = max(1, min(max_new, self.max_len - 1))
        ids = ids[-(self.max_len - max_new):]
        with self._qlock:
            req = Request(self._next_rid, ids, max_new, priority=priority,
                          tenant=tenant, seq=self._seq,
                          t_submit=time.perf_counter())
            self._next_rid += 1
            self._seq += 1
            self.requests[req.rid] = req
            if self._tq is not None:
                self._tq.push(req.tenant, (-req.priority, req.seq), req)
            else:
                heapq.heappush(self._heap, (-req.priority, req.seq, req))
        return req.rid

    def queue_depth(self) -> int:
        with self._qlock:
            return (len(self._tq) if self._tq is not None
                    else len(self._heap))

    def _peek(self) -> Optional[Request]:
        with self._qlock:
            if self._tq is not None:
                return self._tq.peek()
            return self._heap[0][2] if self._heap else None

    def _pop(self) -> Optional[Request]:
        with self._qlock:
            if self._tq is not None:
                popped = self._tq.pop()
                return popped[1] if popped is not None else None
            return heapq.heappop(self._heap)[2] if self._heap else None

    def _push(self, req: Request) -> None:
        with self._qlock:
            if self._tq is not None:
                self._tq.push(req.tenant, (-req.priority, req.seq), req)
            else:
                heapq.heappush(self._heap, (-req.priority, req.seq, req))

    def _needs_chunk(self, req: Request) -> bool:
        return bool(self.engine.prefill_chunk
                    and len(req.prompt_ids) > self.engine.prefill_chunk
                    and self.engine.supports_fixed_shape_prefill)

    def _free_slots(self) -> List[int]:
        return [i for i in range(self.n_slots)
                if self.slots[i] is None and i not in self._reserved]

    def _first_token(self, req: Request, tok: int,
                     finished: List[Request]) -> bool:
        """Record a request's prefill-sampled first token; returns True
        when the request stays live (False: finished on the prefill
        token — the slot write is skipped, nothing would read it)."""
        req.out_ids.append(tok)
        req.t_first_token = time.perf_counter()
        if tok == self.engine.tokenizer.eos or len(req.out_ids) >= req.max_new:
            req.done = True
            finished.append(req)
            return False
        return True

    def _occupy(self, slot: int, req: Request, pos: int, tok: int) -> None:
        self.slots[slot] = req
        self._pos[slot] = pos
        self._tok[slot] = tok

    # -- paged-KV bookkeeping ------------------------------------------------
    def _table_row(self, slot: int) -> jax.Array:
        """One slot's device block table, trash-padded to max blocks."""
        blocks = self._blocks[slot]
        return jnp.asarray(blocks + [self._trash] * (self._mb - len(blocks)),
                           jnp.int32)

    def _tables_device(self) -> jax.Array:
        """The (n_slots, max_blocks) int32 block-table array, rebuilt
        lazily after any host-side table change."""
        if self._tables_dirty:
            self._tables_dev = jnp.asarray(
                [b + [self._trash] * (self._mb - len(b))
                 for b in self._blocks], jnp.int32)
            self._tables_dirty = False
        return self._tables_dev

    def _alloc_block(self) -> Optional[int]:
        """One fresh block, evicting LRU prefix-cache entries on demand;
        ``None`` only once the pool is exhausted AND the prefix cache is
        empty (live sequences hold everything)."""
        while True:
            bid = self._alloc.alloc()
            if bid is not None:
                return bid
            if self._prefix is not None and len(self._prefix):
                self._prefix.evict()
                continue
            return None

    def _paged_admit_blocks(self, ids: List[int], n_rows: int,
                            stats: Dict[str, int]
                            ) -> Optional[Tuple[int, List[int]]]:
        """Pin the longest cached prefix of ``ids`` and allocate fresh
        blocks to cover ``n_rows`` rows.  Returns ``(start, blocks)``
        with ``start`` the reused-prefix row count, or ``None`` (all
        acquisitions rolled back) when the pool is exhausted."""
        start, shared = 0, []
        if self._prefix is not None:
            start, shared = self._prefix.match(ids)
            for bid in shared:
                self._alloc.incref(bid)     # pin before anything can evict
        blocks = list(shared)
        need = -(-n_rows // self.block_size)
        while len(blocks) < need:
            bid = self._alloc_block()
            if bid is None:
                for b in blocks:
                    self._alloc.decref(b)
                return None
            blocks.append(bid)
        if start:
            stats["prefix_hits"] += 1
        return start, blocks

    def _free_slot_blocks(self, slot: int) -> None:
        """Drop one slot's block references (finish / preemption) — the
        allocator reclaims blocks nobody else shares."""
        for bid in self._blocks[slot]:
            self._alloc.decref(bid)
        self._blocks[slot] = []
        self._tables_dirty = True

    def _ensure_block(self, slot: int) -> bool:
        """Make the block holding this slot's next write position exist
        and be exclusively owned.  The fork branch is defensive
        copy-on-write: admission never leaves a shared block at the
        write position (cached prefix blocks are always *full*, and the
        next write lands past them), but if a layout change ever does,
        the shared block is copied rather than corrupted.  False = pool
        exhausted (the caller self-preempts the slot)."""
        bi = self._pos[slot] // self.block_size
        blocks = self._blocks[slot]
        while bi >= len(blocks):
            bid = self._alloc_block()
            if bid is None:
                return False
            blocks.append(bid)
            self._tables_dirty = True
        if self._alloc.ref(blocks[bi]) > 1:
            got = self._alloc.fork(blocks[bi])
            while got is None:
                if self._prefix is None or not len(self._prefix):
                    return False
                self._prefix.evict()
                got = self._alloc.fork(blocks[bi])
            new, needs_copy = got
            if needs_copy:
                self._pool = self._copy(self._pool, jnp.int32(blocks[bi]),
                                        jnp.int32(new))
                blocks[bi] = new
                self._tables_dirty = True
        return True

    def paging_stats(self) -> Dict[str, int]:
        """Allocator + prefix-cache counters (benchmarks/tests); empty
        for the contiguous path."""
        if not self._paged:
            return {}
        s = {"blocks_in_use": self._alloc.in_use,
             "blocks_free": self._alloc.free_count,
             "n_blocks": self.n_blocks, "block_size": self.block_size}
        if self._prefix is not None:
            s.update(self._prefix.stats())
        return s

    def _prefill_into(self, slot: int, req: Request,
                      finished: List[Request], stats: Dict[str, int]) -> bool:
        """Admit one request on its own: the engine's canonical prefill
        (bucketed where supported), or the v1 exact-length recipe when
        ``batched_prefill=False``.  False = paged pool exhausted (the
        request was requeued; stop admitting this step)."""
        if self._paged:
            return self._paged_prefill_into(slot, req, finished, stats)
        prefill = (self.engine.prefill_ids if self.batched_prefill
                   else self.engine.prefill_ids_exact)
        logits, cache = prefill(req.prompt_ids, self.max_len)
        stats["prefilled"] += len(req.prompt_ids)
        tok = int(self.engine.sample(logits, [req.rid], [0])[0])
        if self._first_token(req, tok, finished):
            self._cache = self._insert(self._cache, cache, slot)
            self._occupy(slot, req, self._offset + len(req.prompt_ids), tok)
        return True

    def _paged_prefill_into(self, slot: int, req: Request,
                            finished: List[Request],
                            stats: Dict[str, int]) -> bool:
        """Paged admission of one request: pin/allocate its blocks, skip
        the cached prefix (suffix-only prefill on a hit), scatter the
        prefilled rows into the pool, and index the prompt's full blocks
        in the prefix cache for the next same-prefix admission."""
        got = self._paged_admit_blocks(req.prompt_ids, len(req.prompt_ids),
                                       stats)
        if got is None:
            self._push(req)
            return False
        start, blocks = got
        self._blocks[slot] = blocks
        self._tables_dirty = True
        if start:
            # shared blocks already hold rows 0..start-1: gather this
            # slot's view and prefill only the divergent suffix
            view = self._gather(self._pool, self._table_row(slot)[None])
            logits, cache = self.engine.prefill_continue(
                req.prompt_ids, start, view)
            stats["prefilled"] += len(req.prompt_ids) - start
        else:
            # the contiguous path's bucketed program (batch padded to
            # n_slots rows), not a batch-1 prefill: on the TPU the two
            # programs round differently, and paged == contiguous would
            # not hold
            logits, cache = self.engine.prefill_batch_ids(
                [req.prompt_ids], self.max_len, width=self.n_slots)
            logits, cache = logits[:1], self._take(cache, 0)
            stats["prefilled"] += len(req.prompt_ids)
        self._pool = self._scatter_prefill(self._pool, cache,
                                           self._table_row(slot),
                                           jnp.int32(start))
        if self._prefix is not None:
            self._prefix.insert(req.prompt_ids, blocks)
        tok = int(self.engine.sample(logits, [req.rid], [0])[0])
        if self._first_token(req, tok, finished):
            self._occupy(slot, req, self._offset + len(req.prompt_ids), tok)
        else:
            self._free_slot_blocks(slot)
        return True

    def _admit_bucket(self, group: List[Request], free: List[int],
                      finished: List[Request], stats: Dict[str, int]) -> bool:
        """Admit a same-bucket group with ONE jitted batched prefill
        (batch padded to ``n_slots`` rows so every group size shares the
        same trace).  In paged mode (prefix cache off — hit-aware
        admission goes per-request through ``_paged_prefill_into``) each
        row scatters into its slot's freshly allocated blocks.  False =
        the paged pool ran out mid-group (unplaced members requeued)."""
        logits, cache = self.engine.prefill_batch_ids(
            [r.prompt_ids for r in group], self.max_len, width=self.n_slots)
        slot_iter = iter(free)
        exhausted = False
        for j, req in enumerate(group):
            if exhausted:
                self._push(req)
                continue
            blocks: List[int] = []
            if self._paged:
                got = self._paged_admit_blocks(req.prompt_ids,
                                               len(req.prompt_ids), stats)
                if got is None:
                    exhausted = True
                    self._push(req)
                    continue
                _, blocks = got
            stats["prefilled"] += len(req.prompt_ids)
            tok = int(self.engine.sample(logits[j:j + 1], [req.rid], [0])[0])
            if self._first_token(req, tok, finished):
                slot = next(slot_iter)
                row = self._take(cache, j)
                if self._paged:
                    self._blocks[slot] = blocks
                    self._tables_dirty = True
                    self._pool = self._scatter_prefill(
                        self._pool, row, self._table_row(slot), jnp.int32(0))
                else:
                    self._cache = self._insert(self._cache, row, slot)
                self._occupy(req=req, slot=slot, tok=tok,
                             pos=self._offset + len(req.prompt_ids))
            elif self._paged:
                for bid in blocks:
                    self._alloc.decref(bid)
        return not exhausted

    def _resume_into(self, slot: int, req: Request,
                     stats: Dict[str, int]) -> bool:
        """Re-admit a preempted request: canonical prefill of the prompt
        plus decode replay of its kept tokens (``Engine.replay_ids``) —
        the state rebuild is bit-identical, generated tokens are never
        resampled.  In paged mode the replayed rows scatter into
        re-pinned blocks (shared prefix blocks are reused, not
        rewritten).  False = pool exhausted (request requeued)."""
        if self._paged:
            n_rows = len(req.prompt_ids) + len(req.out_ids) - 1
            got = self._paged_admit_blocks(req.prompt_ids, n_rows, stats)
            if got is None:
                self._push(req)
                return False
            start, blocks = got
            self._blocks[slot] = blocks
            self._tables_dirty = True
            cache, pos, tok = self.engine.replay_ids(
                req.prompt_ids, req.out_ids, self.max_len)
            stats["prefilled"] += len(req.prompt_ids) + len(req.out_ids) - 1
            self._pool = self._scatter_prefill(self._pool, cache,
                                               self._table_row(slot),
                                               jnp.int32(start))
            if self._prefix is not None:
                self._prefix.insert(req.prompt_ids, blocks)
            self._occupy(slot, req, pos, tok)
            return True
        cache, pos, tok = self.engine.replay_ids(
            req.prompt_ids, req.out_ids, self.max_len)
        stats["prefilled"] += len(req.prompt_ids) + len(req.out_ids) - 1
        self._cache = self._insert(self._cache, cache, slot)
        self._occupy(slot, req, pos, tok)
        return True

    def _admit(self, finished: List[Request], stats: Dict[str, int]) -> None:
        """Fill free slots from the priority queue (strict priority
        order), advancing the in-flight chunked admission by one chunk
        first."""
        if self._chunk_job is not None:
            job, req, slot = self._chunk_job
            stats["prefilled"] += job.step()
            if job.done:
                self._chunk_job = None
                self._reserved.discard(slot)
                tok = int(self.engine.sample(job.logits, [req.rid], [0])[0])
                if self._paged:
                    # scatter skips the job's reused-prefix rows (they
                    # live in shared blocks the job never rewrote)
                    self._pool = self._scatter_prefill(
                        self._pool, job.cache, self._table_row(slot),
                        jnp.int32(job.start))
                    if self._prefix is not None:
                        self._prefix.insert(req.prompt_ids,
                                            self._blocks[slot])
                    if self._first_token(req, tok, finished):
                        self._occupy(slot, req,
                                     self._offset + len(req.prompt_ids), tok)
                    else:
                        self._free_slot_blocks(slot)
                elif self._first_token(req, tok, finished):
                    self._cache = self._insert(self._cache, job.cache, slot)
                    self._occupy(slot, req,
                                 self._offset + len(req.prompt_ids), tok)
        while True:
            free = self._free_slots()
            if not free:
                return
            req = self._pop()
            if req is None:
                return
            if req.out_ids:                     # preempted: replay resume
                if not self._resume_into(free[0], req, stats):
                    return                      # pool exhausted this step
                continue
            if self._needs_chunk(req):
                if self._chunk_job is not None:
                    # strict priority order: wait for the running chunk
                    # admission rather than admitting around the head
                    self._push(req)
                    return
                slot = free[0]
                if self._paged:
                    got = self._paged_admit_blocks(
                        req.prompt_ids, len(req.prompt_ids), stats)
                    if got is None:
                        self._push(req)
                        return
                    start, blocks = got
                    self._blocks[slot] = blocks
                    self._tables_dirty = True
                    if start:
                        # hot prefix: the chunk job starts at the first
                        # divergent row against the gathered slot view
                        view = self._gather(self._pool,
                                            self._table_row(slot)[None])
                        job = PrefillJob(self.engine, req.prompt_ids,
                                         self.max_len, cache=view,
                                         start=start)
                    else:
                        job = self.engine.prefill_job(req.prompt_ids,
                                                      self.max_len)
                else:
                    job = self.engine.prefill_job(req.prompt_ids,
                                                  self.max_len)
                self._reserved.add(slot)
                stats["prefilled"] += job.step()   # first chunk this step
                self._chunk_job = (job, req, slot)
                continue
            if (self.batched_prefill
                    and self.engine.supports_fixed_shape_prefill
                    and not (self._paged and self._prefix is not None)):
                group = [req]
                bucket = prefill_bucket(len(req.prompt_ids))
                while len(group) < len(free):
                    nxt = self._pop_matching(bucket, req)
                    if nxt is None:
                        break
                    group.append(nxt)
                if not self._admit_bucket(group, free, finished, stats):
                    return
            else:
                if not self._prefill_into(free[0], req, finished, stats):
                    return

    def _pop_matching(self, bucket: int,
                      leader: Optional[Request] = None) -> Optional[Request]:
        """Pop the queue head iff it is a plain same-bucket admission
        (no resume, no chunking) — grows a bucket group without
        reordering across priorities.  Under fair-share admission the
        group additionally stays within the ``leader``'s tenant, and
        each extra member spends one more of that tenant's DRR turns —
        a batched prefill never becomes a cross-tenant queue jump."""
        def plain(r: Request) -> bool:
            return (not r.out_ids and not self._needs_chunk(r)
                    and prefill_bucket(len(r.prompt_ids)) == bucket)

        with self._qlock:
            if self._tq is not None:
                if leader is None:
                    return None
                return self._tq.pop_same_tenant(leader.tenant, plain)
            if not self._heap:
                return None
            req = self._heap[0][2]
            if not plain(req):
                return None
            return heapq.heappop(self._heap)[2]

    # -- preemption ---------------------------------------------------------
    def _preempt(self, stats: Dict[str, int]) -> None:
        """Evict the lowest-priority live slot when the queue head
        strictly outranks it and no slot is free (at most one eviction
        per step; equal priority never preempts — no thrash). The victim
        keeps its generated tokens and requeues with its original
        submission ticket."""
        head = self._peek()
        if head is None or self._free_slots():
            return
        if self._needs_chunk(head) and self._chunk_job is not None:
            return   # head cannot be admitted yet; don't waste a slot
        live = [(self.slots[i].priority, -self.slots[i].rid, i)
                for i in range(self.n_slots) if self.slots[i] is not None]
        if not live:
            return
        pri, _, victim = min(live)   # lowest priority; tie: youngest rid
        if head.priority <= pri:
            return
        req = self.slots[victim]
        self.slots[victim] = None
        if self._paged:
            self._free_slot_blocks(victim)
        req.preemptions += 1
        stats["preempted"] += 1
        self._push(req)

    # -- the batched decode step --------------------------------------------
    def step(self) -> List[Request]:
        """One scheduler cycle: preempt if a waiting request outranks a
        live slot, admit into free slots (chunked / bucketed / resume),
        then advance ALL live slots one token with a single jitted decode
        over the slot batch. Returns the requests that finished this
        step."""
        finished: List[Request] = []
        stats = {"prefilled": 0, "preempted": 0, "prefix_hits": 0}
        self._preempt(stats)
        self._admit(finished, stats)
        live = [i for i in range(self.n_slots) if self.slots[i] is not None]
        if self._paged:
            # grow each live slot's table to cover its write position;
            # a slot that cannot get a block self-preempts (resume later
            # replays it bit-identically, so nothing is lost)
            for i in list(live):
                if not self._ensure_block(i):
                    req = self.slots[i]
                    self.slots[i] = None
                    self._free_slot_blocks(i)
                    req.preemptions += 1
                    stats["preempted"] += 1
                    self._push(req)
                    live.remove(i)
        if live:
            tokens = jnp.asarray([[t] for t in self._tok], jnp.int32)
            pos = jnp.asarray(self._pos, jnp.int32)
            if self._paged:
                # gather pool -> dense view, decode with the SAME jitted
                # executable as the contiguous path (bit parity), scatter
                # the freshly written rows back into the pool.  Only LIVE
                # slots write back: a dead or chunk-reserved slot decodes
                # junk at a stale position (exactly like the contiguous
                # path), and its table may already hold SHARED prefix
                # blocks — its row is redirected to the trash block.
                tables = self._tables_device()
                live_rows = jnp.asarray(
                    [self.slots[i] is not None for i in range(self.n_slots)])
                wtables = jnp.where(live_rows[:, None], tables, self._trash)
                view = self._gather(self._pool, tables)
                logits, view = self.engine._decode(
                    self.engine.params, cache=view, token=tokens, pos=pos)
                self._pool = self._scatter_rows(self._pool, view, wtables,
                                                pos)
            else:
                logits, self._cache = self.engine._decode(
                    self.engine.params, cache=self._cache, token=tokens,
                    pos=pos)
            rids = [r.rid if (r := self.slots[i]) is not None else 0
                    for i in range(self.n_slots)]
            steps = [len(r.out_ids) if (r := self.slots[i]) is not None else 0
                     for i in range(self.n_slots)]
            toks = [int(t) for t in self.engine.sample(logits, rids, steps)]
            eos = self.engine.tokenizer.eos
            for i in live:
                req = self.slots[i]
                req.out_ids.append(toks[i])
                self._pos[i] += 1
                self._tok[i] = toks[i]
                if toks[i] == eos or len(req.out_ids) >= req.max_new:
                    req.done = True
                    finished.append(req)
                    self.slots[i] = None   # slot freed -> next admission
                    if self._paged:
                        self._free_slot_blocks(i)
        self._steps += 1
        self._emit(EngineStepped(t=float(self._steps), live=len(live),
                                 queued=self.queue_depth(),
                                 generated=len(live),
                                 prefilled=stats["prefilled"],
                                 preempted=stats["preempted"],
                                 blocks_in_use=(self._alloc.in_use
                                                if self._paged else 0),
                                 prefix_hits=stats["prefix_hits"]))
        return finished

    # -- draining -----------------------------------------------------------
    def has_work(self) -> bool:
        if self.queue_depth() or self._chunk_job is not None:
            return True
        return any(s is not None for s in self.slots)

    def occupancy(self) -> int:
        return sum(s is not None for s in self.slots)

    def block_until_ready(self) -> None:
        """Wait until every dispatched step has finished on the device —
        the end of any wall-clock timing of the scheduler."""
        jax.block_until_ready(self._pool if self._paged else self._cache)

    def drain(self) -> Dict[int, GenerationResult]:
        """Step to completion; returns {rid: GenerationResult}."""
        done: Dict[int, GenerationResult] = {}
        while self.has_work():
            for req in self.step():
                done[req.rid] = req.to_result(self.engine.tokenizer)
        return done

    def run(self) -> Dict[int, str]:
        """Historical entry point: drain and return {rid: text}."""
        return {rid: r.text for rid, r in self.drain().items()}


class EngineClient:
    """Blocking, thread-safe handle multiplexing concurrent callers onto
    one :class:`BatchScheduler`.

    ``generate`` submits and blocks until its request completes. While
    any request is in flight exactly one blocked caller "pumps" the
    scheduler (``step()``) with the lock released, so other threads keep
    submitting into the SAME decode batch — this is the pump mode that
    lets ``Session.execute_many`` fan-out share the engine. Duck-types
    ``Engine.generate``, so ``JaxLLMBackend`` can point at either.

    ``priority`` flows through to ``BatchScheduler.submit``:
    latency-sensitive agent runs (``RunSpec.priority``) jump the
    admission queue and may preempt lower-priority slots.
    """

    def __init__(self, scheduler: BatchScheduler):
        self.scheduler = scheduler
        self._cv = threading.Condition()
        self._pumping = False
        self._results: Dict[int, GenerationResult] = {}

    def generate(self, prompt: str, max_new_tokens: int = 32,
                 priority: int = 0, tenant: str = "") -> GenerationResult:
        with self._cv:
            rid = self.scheduler.submit(prompt, max_new=max_new_tokens,
                                        priority=priority, tenant=tenant)
            while rid not in self._results:
                if self._pumping:
                    # someone else is driving the engine; wake on step end
                    self._cv.wait(timeout=0.002)
                    continue
                self._pumping = True
                self._cv.release()
                try:
                    finished = self.scheduler.step()
                finally:
                    self._cv.acquire()
                    self._pumping = False
                self._collect(finished)
            return self._results.pop(rid)

    def _collect(self, finished: List[Request]) -> None:
        """Bank finished requests and drop the scheduler's completed
        bookkeeping — the client is the long-lived path (backend
        singleton), so the scheduler must not grow without bound.
        Caller holds ``_cv``."""
        tokenizer = self.scheduler.engine.tokenizer
        for req in finished:
            self._results[req.rid] = req.to_result(tokenizer)
            self.scheduler.requests.pop(req.rid, None)
        self._cv.notify_all()

    async def generate_async(self, prompt: str, max_new_tokens: int = 32,
                             priority: int = 0,
                             tenant: str = "") -> GenerationResult:
        """Asyncio-friendly pump: like :meth:`generate`, but awaitable —
        many coroutines on ONE event loop multiplex onto the shared
        decode batch with no thread per request.

        While its request is in flight, exactly one waiter pumps
        ``scheduler.step()`` on the loop's default executor (the step is
        a blocking jitted call — running it off-loop keeps other
        coroutines submitting into the same batch); the rest yield.
        Thread-safe alongside blocking ``generate`` callers: both paths
        share the ``_pumping`` baton and the results table."""
        import asyncio
        loop = asyncio.get_running_loop()
        with self._cv:
            rid = self.scheduler.submit(prompt, max_new=max_new_tokens,
                                        priority=priority, tenant=tenant)
        while True:
            with self._cv:
                if rid in self._results:
                    return self._results.pop(rid)
                pump = not self._pumping
                if pump:
                    self._pumping = True
            if pump:
                try:
                    finished = await loop.run_in_executor(
                        None, self.scheduler.step)
                finally:
                    with self._cv:
                        self._pumping = False
                with self._cv:
                    self._collect(finished)
            else:
                # another caller (thread or coroutine) drives the
                # engine; yield the loop until the next step lands
                await asyncio.sleep(0.001)
