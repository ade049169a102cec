"""What the serving hot path tells a profiler and its counters: program
spans on the trace's host plane, named device programs, the admission
stamps on ``Request``, and the prefill rows each step computed, padding
included."""
import glob

import jax
import jax.numpy as jnp
import pytest

from paging_scenarios import BLOCK, MAX_LEN, get_engine
from repro.core.events import EngineStepped
from repro.models.model import init_cache
from repro.serving import BatchScheduler
from repro.serving.engine import prefill_bucket

CHUNK = 16
PHASES = ("BatchScheduler.preempt", "BatchScheduler.admit",
          "BatchScheduler.ensure_blocks", "BatchScheduler.decode_inputs",
          "BatchScheduler.decode_dispatch", "BatchScheduler.readback",
          "BatchScheduler.bookkeeping")
ADMISSIONS = ("BatchScheduler.admit_chunk", "BatchScheduler.admit_prefix_hit",
              "BatchScheduler.admit_bucket", "BatchScheduler.admit_resume")


def _paged(n_slots=2):
    return BatchScheduler(get_engine("gqa", 0.0, CHUNK), n_slots=n_slots,
                          max_len=MAX_LEN, paged_kv=True, block_size=BLOCK)


def _every_admission_path(sched):
    """A short miss (bucket) and a long prompt (chunk job) fill both
    slots; a high-priority arrival then evicts the short one, which later
    resumes; a prompt sharing the short one's first block hits the
    prefix cache.  Returns the rids: short, long, urgent, hit."""
    short = list(range(1, 13))
    rids = [sched.submit(prompt_ids=short, max_new=8),
            sched.submit(prompt_ids=list(range(100, 140)), max_new=4)]
    sched.step()
    rids.append(sched.submit(prompt_ids=list(range(200, 210)), max_new=3,
                             priority=5))
    rids.append(sched.submit(prompt_ids=short[:BLOCK] + [300, 301, 302, 303],
                             max_new=3))
    sched.drain()
    return rids


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("BatchScheduler."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_trace_shows_every_span_nested_in_its_step(tmp_path):
    sched = _paged()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        short, long_, urgent, hit = _every_admission_path(sched)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    names = {n for n, *_ in spans}
    assert {"BatchScheduler.step", "BatchScheduler.submit",
            *PHASES, *ADMISSIONS} <= names
    steps = [(s, e, st) for n, s, e, st in spans if n == "BatchScheduler.step"]
    assert len(steps) == sched._steps
    assert sorted(st["step"] for *_, st in steps) == list(range(len(steps)))
    for n, s, e, st in spans:
        if n in ("BatchScheduler.step", "BatchScheduler.submit"):
            continue
        assert any(s0 <= s and e <= e0 for s0, e0, _ in steps), n
    by_rid = {}
    for n, s, e, st in spans:
        if n in ADMISSIONS:
            assert {"rid", "rows", "reused"} <= set(st), (n, st)
            by_rid.setdefault(st["rid"], set()).add(n)
    assert by_rid[hit] == {"BatchScheduler.admit_prefix_hit"}
    assert by_rid[short] == {"BatchScheduler.admit_bucket",
                             "BatchScheduler.admit_resume"}
    assert by_rid[long_] == {"BatchScheduler.admit_chunk"}
    hit_span = next(st for n, *_, st in spans
                    if n == "BatchScheduler.admit_prefix_hit")
    assert hit_span["reused"] == BLOCK and hit_span["rows"] == 8
    submitted = {st["rid"] for n, *_, st in spans
                 if n == "BatchScheduler.submit"}
    assert submitted == {short, long_, urgent, hit}


def _program_args(eng):
    cfg = eng.cfg
    cache = init_cache(cfg, 1, MAX_LEN, dtype=eng.params["embed"].dtype)
    tok8 = jnp.zeros((1, 8), jnp.int32)
    one = jnp.asarray([5], jnp.int32)
    return {
        "decode_step": (eng._decode, dict(cache=cache,
                                          token=jnp.zeros((1, 1), jnp.int32),
                                          pos=jnp.int32(3))),
        "prefill_fresh": (eng._prefill_fixed, dict(tokens=tok8, lengths=one,
                                                   cache_len=MAX_LEN)),
        "prefill_attend": (eng._prefill_extend, dict(cache=cache, tokens=tok8,
                                                     off=jnp.int32(0),
                                                     lengths=one)),
        "prefill": (eng._prefill, dict(tokens=tok8, frontend_embeds=None)),
    }


@pytest.mark.parametrize("program", ["decode_step", "prefill_fresh",
                                     "prefill_attend", "prefill"])
def test_engine_programs_are_named(program):
    eng = get_engine("gqa", 0.0, CHUNK)
    fn, kwargs = _program_args(eng)[program]
    head = fn.lower(eng.params, **kwargs).as_text().splitlines()[0]
    assert head.startswith(f"module @jit_{program} "), head


def test_admission_stamps_survive_preemption():
    sched = _paged()
    short, long_, urgent, hit = _every_admission_path(sched)
    reqs = {rid: sched.requests[rid] for rid in (short, long_, urgent, hit)}
    for r in reqs.values():
        assert r.admit_step >= 0
        assert r.t_submit <= r.t_admit <= r.t_first_token
    assert reqs[short].preemptions == 1
    assert reqs[short].admit_step == 0 and reqs[long_].admit_step == 0
    assert reqs[urgent].admit_step > 0
    assert reqs[hit].reused == BLOCK
    # the resume re-pinned its own cached block too, but keeps its stamp
    assert sched.paging_stats()["tokens_reused"] == 2 * BLOCK
    assert reqs[short].reused == reqs[long_].reused == 0


def _rows_of(sched, prompts):
    events = []
    sched.subscribe(lambda e: events.append(e)
                    if isinstance(e, EngineStepped) else None)
    for p in prompts:
        sched.submit(prompt_ids=p, max_new=2)
    sched.drain()
    return (sum(e.prefill_rows for e in events),
            sum(e.prefilled for e in events), events)


@pytest.mark.parametrize("case", ["bucket_batch", "chunk_job", "prefix_hit"])
def test_prefill_rows_count_padding(case):
    if case == "bucket_batch":
        sched = BatchScheduler(get_engine("gqa", 0.0), n_slots=3,
                               max_len=MAX_LEN)
        rows, real, events = _rows_of(sched, [list(range(1, 6)),
                                              list(range(1, 8))])
        assert events[0].prefill_rows == 3 * 8 and events[0].prefilled == 12
        assert (rows, real) == (24, 12)          # one 3-row batch of bucket 8
        return
    sched = _paged(n_slots=3)
    if case == "chunk_job":
        rows, real, _ = _rows_of(sched, [list(range(1, 41))])
        assert (rows, real) == (-(-40 // CHUNK) * CHUNK, 40)
        return
    short = list(range(1, 13))
    rows, real, _ = _rows_of(sched, [short])
    assert (rows, real) == (3 * prefill_bucket(12), 12)   # miss: 3-row batch
    rows, real, _ = _rows_of(sched, [short[:BLOCK] + [300, 301, 302, 303]])
    assert (rows, real) == (prefill_bucket(4), 4)          # the suffix alone
