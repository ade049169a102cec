"""Hypothesis property-based tests on system invariants."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.metrics import llm_cost
from repro.core.schema import STAGE_SCHEMA, Schema, SchemaError, Field
from repro.data.tokenizer import CountTokenizer, HashTokenizer
from repro.env.clock import VirtualClock
from repro.faas.storage import KVStore, S3Store
from repro.mcp.protocol import McpRequest


@given(st.text(max_size=2000))
@settings(max_examples=60, deadline=None)
def test_tokenizer_roundtrip(text):
    tok = HashTokenizer(32000)
    ids = tok.encode(text, add_bos=False)
    assert tok.decode(ids) == text


@given(st.text(max_size=500))
@settings(max_examples=60, deadline=None)
def test_count_tokenizer_monotone_in_concat(text):
    a = CountTokenizer.count(text)
    b = CountTokenizer.count(text + " suffix")
    assert b >= a >= 0


@given(st.integers(0, 10**7), st.integers(0, 10**7))
@settings(max_examples=60, deadline=None)
def test_cost_eq1_linear(tin, tout):
    """Eq. 1: cost is exactly linear with the published per-token rates."""
    assert llm_cost(tin, tout) == pytest.approx(
        (tin * 0.15 + tout * 0.60) / 1e6)
    assert llm_cost(2 * tin, 2 * tout) == pytest.approx(2 * llm_cost(tin, tout))


@given(st.lists(st.floats(0, 10, allow_nan=False), min_size=1, max_size=30))
@settings(max_examples=40, deadline=None)
def test_virtual_clock_monotone(sleeps):
    clock = VirtualClock()
    t = clock.now()
    for dt in sleeps:
        clock.sleep(dt)
        assert clock.now() >= t
        t = clock.now()
    assert t == pytest.approx(sum(sleeps))


@given(st.dictionaries(st.text(min_size=1, max_size=20).filter(
    lambda s: "/" not in s), st.text(max_size=50), max_size=10))
@settings(max_examples=40, deadline=None)
def test_kvstore_write_read(items):
    store = KVStore()
    for k, v in items.items():
        store.write(k, v)
    for k, v in items.items():
        assert store.read(k) == v
    assert set(store.list()) == set(items)


@given(st.text(alphabet=st.characters(min_codepoint=48, max_codepoint=122),
               min_size=1, max_size=20),
       st.text(alphabet=st.characters(min_codepoint=48, max_codepoint=122),
               min_size=1, max_size=30))
@settings(max_examples=40, deadline=None)
def test_s3_uri_roundtrip(bucket, key):
    s3 = S3Store()
    uri = f"s3://{bucket}/{key}"
    b, k = S3Store.parse_uri(uri)
    assert b == bucket
    s3.put_object(uri, "data")
    assert s3.get_object(uri) == "data"


@given(st.lists(st.text(max_size=40), min_size=1, max_size=8))
@settings(max_examples=40, deadline=None)
def test_schema_validation(sub_tasks):
    obj = {"sub_tasks": sub_tasks}
    assert STAGE_SCHEMA.validate(obj) == obj
    with pytest.raises(SchemaError):
        STAGE_SCHEMA.validate({"sub_tasks": "not-a-list"})
    with pytest.raises(SchemaError):
        STAGE_SCHEMA.validate({})


@given(st.text(max_size=100), st.integers(0, 2**31 - 1))
@settings(max_examples=40, deadline=None)
def test_mcp_request_json_roundtrip(query, rid):
    req = McpRequest("tools/call", {"name": "t", "arguments":
                                    {"query": query}}, id=rid,
                     session_id="s")
    back = McpRequest.from_json(req.to_json())
    assert back.params["arguments"]["query"] == query
    assert back.id == rid and back.session_id == "s"


# --- numerical invariants ---------------------------------------------------


@given(st.integers(1, 4), st.integers(2, 6), st.integers(1, 3))
@settings(max_examples=20, deadline=None)
def test_router_gates_sum_to_one(b, e, k):
    from repro.configs.base import MoEConfig
    from repro.models.moe import router
    k = min(k, e)
    moe = MoEConfig(n_experts=e, top_k=k, d_ff_expert=8)
    x = jax.random.normal(jax.random.key(b * 7 + e), (b * 3, 16))
    params = {"w_router": jax.random.normal(jax.random.key(0), (16, e))}
    gate, idx, aux = router(params, x, moe)
    assert np.allclose(np.asarray(jnp.sum(gate, -1)), 1.0, atol=1e-5)
    assert int(jnp.max(idx)) < e
    assert float(aux) >= 0.99  # E * sum(f_e * p_e) >= 1 by Cauchy-Schwarz


@given(st.integers(8, 64))
@settings(max_examples=10, deadline=None)
def test_sliding_window_masks_match(s):
    from repro.models.layers import causal_mask
    w = max(4, s // 3)
    m = np.asarray(causal_mask(s, s, window=w))
    for i in range(s):
        for j in range(s):
            assert m[i, j] == (j <= i and j > i - w)


@given(st.integers(1, 3), st.integers(16, 48))
@settings(max_examples=8, deadline=None)
def test_ssd_state_neutral_padding(b, s):
    """dt=0 padding must not change the final state (model invariant the
    chunked implementation relies on)."""
    from repro.kernels.ref import ssd_scan_ref
    h, p, n = 2, 8, 4
    ks = jax.random.split(jax.random.key(s), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    _, fin = ssd_scan_ref(x, dt, A, B, C)
    pad = 5
    xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    dtp = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    Bp = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
    Cp = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    _, fin2 = ssd_scan_ref(xp, dtp, A, Bp, Cp)
    assert float(jnp.max(jnp.abs(fin - fin2))) < 1e-5


# --- paged KV parity (the paging tentpole's property suite) -----------------
#
# Strategies draw a scenario SEED plus engine knobs; the scenario
# generator/runner is shared with tests/test_paging.py, so the seeded
# battery there and this wider search assert the exact same property:
# paged (prefix on AND off) == contiguous == serial, request for request.


@given(st.integers(0, 2 ** 31 - 1),
       st.sampled_from([("gqa", 0.0, 0), ("gqa", 1.0, 8), ("mla", 1.0, 0)]))
@settings(max_examples=6, deadline=None)
def test_paged_parity_property(seed, knobs):
    from paging_scenarios import assert_parity, gen_scenario, get_engine
    arch, temp, chunk = knobs
    rng = np.random.default_rng(seed)
    eng = get_engine(arch, temp, chunk)
    assert_parity(eng, gen_scenario(rng, n_req=int(rng.integers(2, 7))))


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=4, deadline=None)
def test_paged_parity_tight_pool_property(seed):
    """Random scenarios under a pool barely over one sequence wide:
    eviction/exhaustion churn never changes a token.  Such a pool may
    defer an admission that the contiguous cache makes at once (seed 0:
    one admission instead of two at step 6), so the step loops need not
    run in lockstep."""
    from paging_scenarios import (BLOCK, MAX_LEN, assert_parity,
                                  gen_scenario, get_engine)
    rng = np.random.default_rng(seed)
    eng = get_engine("gqa", 1.0, 8)
    assert_parity(eng, gen_scenario(rng, n_req=5),
                  n_blocks=MAX_LEN // BLOCK + 2, check_serial=False,
                  lockstep=False)


@given(st.lists(st.integers(1, 300), min_size=1, max_size=24),
       st.integers(2, 16))
@settings(max_examples=40, deadline=None)
def test_prefix_chain_commits_to_whole_prefix(ids, block_size):
    """A chained block key is a commitment to the entire token prefix:
    perturbing ANY earlier token changes every key at or after it."""
    from repro.serving import prefix_block_keys
    keys = prefix_block_keys(ids, block_size, "salt")
    assert len(keys) == len(ids) // block_size
    if not keys:
        return
    mutated = list(ids)
    mutated[0] += 1
    assert all(a != b for a, b in
               zip(keys, prefix_block_keys(mutated, block_size, "salt")))
