"""The plain reference against the serving engine at reduced sizes on
the CPU: prefill, then decode through the cache, must give the
reference's logits (GQA with QKV bias and SwiGLU, MQA with GELU, MHA),
the benchmark's weight draw must be the same whole and layer by layer,
and the reference's sampling noise must reproduce the engine's keyed
sampler."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import reference
from harness import spec, weights

GQA = bench_tiny.TINY_MODEL
MQA = dict(GQA, n_kv_heads=1, mlp_type="gelu", qkv_bias=False)
MHA = dict(GQA, n_kv_heads=4, rope_theta=5e6)
DENSE = spec.arch("dense")


def _engine(m, seed, dtype):
    from repro.serving.engine import Engine
    cfg = spec.model_config({"name": "t", "model": m})
    params = weights.make_params(m, seed, dtype, DENSE)
    return Engine(cfg, params=params, seed=seed, dtype=dtype,
                  prefill_chunk=16)


@pytest.mark.parametrize("m", [GQA, MQA, MHA], ids=["gqa", "mqa", "mha"])
def test_prefill_and_decode_match_the_reference(m):
    seed = 2 ** 31 + 3
    eng = _engine(m, seed, jnp.float32)
    rng = np.random.default_rng(0)
    ids = rng.integers(1, m["vocab_size"] - 2, size=37).tolist()  # 3 chunks
    extra = rng.integers(1, m["vocab_size"] - 2, size=4).tolist()
    logits, cache = eng.prefill_ids(ids, 64)
    got = [logits[0]]
    for i, tok in enumerate(extra):
        logits, cache = eng._decode(eng.params, cache=cache,
                                    token=jnp.asarray([[tok]], jnp.int32),
                                    pos=jnp.int32(len(ids) + i))
        got.append(logits[0])
    want = reference.logits(m, seed, jnp.float32, [ids + extra],
                            [range(len(ids) - 1, len(ids) + len(extra))],
                            DENSE, block=16, pad_to=64)[0]
    got = jnp.stack(got)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-4 * scale


def test_weight_draw_layer_by_layer_equals_the_whole():
    m = GQA
    whole = weights.make_params(m, 9, jnp.bfloat16, DENSE)
    for layer in range(m["n_layers"]):
        one = weights.layer_leaves(m, 9, layer, jnp.bfloat16, DENSE)
        for name, leaf in one.items():
            part = "attn" if name in ("wq", "wk", "wv", "wo", "bq", "bk",
                                      "bv") else "mlp"
            src = (whole["layers"][name] if name.endswith("norm")
                   else whole["layers"][part][name])
            assert jnp.array_equal(src[layer], leaf), name
    assert jnp.array_equal(whole["embed"],
                           weights.top_leaf(m, 9, "embed", jnp.bfloat16,
                                            DENSE))
    other = weights.make_params(m, 10, jnp.bfloat16, DENSE)
    assert not jnp.array_equal(whole["embed"], other["embed"])


def test_sampling_noise_reproduces_the_engine_sampler():
    m = GQA
    eng = _engine(m, 5, jnp.bfloat16)
    logits = jax.random.normal(jax.random.key(1), (6, m["vocab_size"]),
                               jnp.bfloat16)
    rids, steps = [3, 3, 8, 8, 8, 40], [0, 1, 0, 1, 2, 7]
    got = np.asarray(eng.sample(logits, rids, steps))
    for i, (rid, step) in enumerate(zip(rids, steps)):
        g = reference.sampling_noise(5, rid, step + 1, m["vocab_size"],
                                     jnp.bfloat16)[step]
        score = (logits[i] + g.astype(jnp.bfloat16)).astype(jnp.float32)
        assert int(jnp.argmax(score)) == got[i]
