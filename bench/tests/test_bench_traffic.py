"""The traffic generator: one seed gives the same requests, seeds differ
in what they send but not in how much, and agent sessions keep to the
context."""
import itertools
import json

from bench_tiny import BENCH
from harness import traffic


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _agent(seed, name="agent-8k", seconds=45.0, vocab=49152, max_len=8192):
    return traffic.agent_requests(_mix(name), seed, seconds, vocab, max_len)


def test_agent_same_seed_same_requests():
    a, b = _agent(2 ** 31 + 5), _agent(2 ** 31 + 5)
    assert [(r.due, r.prompt, r.max_new) for r in a] == [
        (r.due, r.prompt, r.max_new) for r in b]


def test_agent_seeds_differ_in_content_not_in_amount_or_time():
    a, b = _agent(1), _agent(2)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [(r.due, len(r.prompt), r.max_new, r.session, r.turn) for r in a] \
        == [(r.due, len(r.prompt), r.max_new, r.session, r.turn) for r in b]


def test_agent_sessions_share_prefixes_and_fit_the_context():
    reqs = _agent(7)
    assert reqs == sorted(reqs, key=lambda r: r.due)
    for r in reqs:
        assert len(r.prompt) + r.max_new <= 8192
        assert all(1 <= t <= 49152 - 3 for t in r.prompt)
        assert r.due < 45.0
    by_session = {}
    for r in reqs:
        by_session.setdefault(r.session, []).append(r)
    grown = 0
    for turns in by_session.values():
        turns.sort(key=lambda r: r.turn)
        for prev, nxt in zip(turns, turns[1:]):
            # each turn's prompt starts with the whole previous prompt
            assert nxt.prompt[:len(prev.prompt)] == prev.prompt
            assert nxt.due > prev.due
            grown += 1
    assert grown > 0
    finals = [r for r in reqs if r.final]
    assert finals and all(r.max_new == 256 for r in finals)


def test_batch_stream_same_sizes_other_tokens():
    mix = _mix("batch-2k")
    n = mix["template_requests"]

    def reqs(seed):
        gen = traffic.batch_requests(mix, seed, 151936, 2048)
        return list(itertools.islice(gen, n + 3))

    a, b = reqs(3), reqs(4)
    sizes = [(len(r.prompt), r.max_new) for r in a]
    assert sizes == [(len(r.prompt), r.max_new) for r in b]
    assert sizes[n:] == sizes[:3]                  # the list repeats
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [r.prompt for r in reqs(3)] == [r.prompt for r in a]
    lo, hi = mix["prompt_tokens"]
    assert all(lo <= p <= hi and p + o <= 2048 for p, o in sizes)
    assert len(set(sizes)) > n // 2


def test_warm_shapes_name_what_the_window_sends():
    s = traffic.warm_shapes(_mix("agent-8k"), 1, 45.0, 49152, 8192, 512)
    assert s["chunked"] and s["whole"] == []
    s = traffic.warm_shapes(_mix("batch-2k"), 1, 45.0, 151936, 2048, 512)
    assert s["chunked"]
    assert s["whole"] and max(s["whole"]) <= 512
