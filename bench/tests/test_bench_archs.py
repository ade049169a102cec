"""Architectures are found by name (``bench/archs``), and moving the dense
decoder there moved nothing: its weights, reference logits and counts are
those the harness gave before, bit for bit.  A module dropped into a copy
of the benchmark, with a configuration that names it, is what weights,
reference, check and counts use, with no other file changed.  Nested
configuration blocks build the program's dataclasses, and a run carries
the program's counters and admission stamps to the metric readers."""
import dataclasses
import hashlib
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import reference
from harness import check, spec, traffic, weights
from harness import main as hm

GQA = bench_tiny.TINY_MODEL
MQA = dict(GQA, n_kv_heads=1, mlp_type="gelu", qkv_bias=False)
MHA = dict(GQA, n_kv_heads=4, rope_theta=5e6)
MODELS = {"gqa": GQA, "mqa": MQA, "mha": MHA}
DENSE = spec.arch("dense")

# sha256 of the draw (bf16, seed 9) and of the reference's logits (f32
# and the float8 control) on SEQ, recorded from the harness before its
# dense code moved to bench/archs/dense.py
PARAMS_SHA = {
    "gqa": "c4e1702401952986f6abb39420de47743c6095977d6b0cab85a23f74d06c28a8",
    "mqa": "0a7513ba87f61269d241b49363640fcd84fcbfbded7f7b194be463a497b23f53",
    "mha": "ccb7a64849e46d87a26cdfb940fc4f803f8bb81613882ae26c0a5d4f25492e77",
}
LOGITS_SHA = {
    ("gqa", "f32"):
        "b2bf1953836ab7e632d1d72e83e74cdb8520c72ba71268ac7086915af2d168fc",
    ("mqa", "f32"):
        "f4cce79d32861ebfedf92c042da1da45bbb735bf35654f3bda74ff9593759bc4",
    ("mha", "f32"):
        "2cc78d74e0f8adaafb3438b759b1eb59f8bb3968ac5f492832700d4858cc41cd",
    ("gqa", "fp8"):
        "3a2866c81d3c3ee9118e63cb4413452df5d8165c411f087f51f4321d1d7be128",
}
# the dense counts of the two configurations, bfloat16, as the harness
# counted them before: matmul_params, weight_params, attn_width,
# n_layers, kv_bytes_per_token, token_flops(1024),
# decode_step_bytes([1000] * 8)
COUNTS = {
    "granite-34b-s11": (4471652352, 4471793664, 6144, 11, 5632,
                        9220128768, 8988643328),
    "qwen1.5-4b-l20": (1974927360, 1975185920, 2560, 20, 204800,
                       4159569920, 5588771840),
}


def _seq(m):
    return [(i * 37 + 11) % (m["vocab_size"] - 3) + 1 for i in range(45)]


def _tree_sha(tree) -> str:
    h = hashlib.sha256()
    leaves = sorted(jax.tree_util.tree_leaves_with_path(tree),
                    key=lambda x: jax.tree_util.keystr(x[0]))
    for path, leaf in leaves:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PARAMS_SHA))
def test_dense_draw_is_bitwise_unchanged(name):
    params = weights.make_params(MODELS[name], 9, jnp.bfloat16, DENSE)
    assert _tree_sha(params) == PARAMS_SHA[name]


@pytest.mark.parametrize("name,mode", sorted(LOGITS_SHA))
def test_dense_reference_logits_are_bitwise_unchanged(name, mode):
    m = MODELS[name]
    out = reference.logits(m, 9, jnp.bfloat16, [_seq(m)], [range(40, 45)],
                           DENSE, mode=mode, block=16, pad_to=64)[0]
    got = hashlib.sha256(np.asarray(out, np.float32).tobytes()).hexdigest()
    assert got == LOGITS_SHA[(name, mode)]


def test_a_stack_of_expert_matrices_keeps_each_ones_fan_in():
    key = weights.root_key(3)
    experts = weights.draw(key, 0, 0, (16, 256, 64), "mat", jnp.float32)
    one = weights.draw(key, 0, 0, (256, 64), "mat", jnp.float32)
    assert float(jnp.max(jnp.abs(experts))) <= 3 ** 0.5 / 16
    assert float(jnp.std(experts)) == pytest.approx(1 / 16, rel=0.02)
    assert float(jnp.std(one)) == pytest.approx(1 / 16, rel=0.02)


@pytest.mark.parametrize("cell", sorted(COUNTS))
def test_dense_counts_are_unchanged(cell):
    c = spec.find_cell({"granite-34b-s11": "granite-34b-s11.agent",
                        "qwen1.5-4b-l20": "qwen1.5-4b-l20.batch"}[cell])
    assert c.arch is DENSE and c.serving["dtype"] == "bfloat16"
    k = c.arch.counts(c.model, c.serving["dtype"])
    assert (k.matmul_params, k.weight_params, k.attn_width, k.n_layers,
            k.kv_bytes_per_token, k.token_flops(1024),
            k.decode_step_bytes([1000] * 8)) == COUNTS[cell]


def _program_configs():
    from repro.configs import ARCHS
    return sorted(ARCHS)


@pytest.mark.parametrize("name", _program_configs())
def test_model_config_builds_every_program_config(name):
    from repro.configs import ARCHS
    want = ARCHS[name]
    got = spec.model_config({"name": name,
                             "model": dataclasses.asdict(want)})
    assert got == want
    for block in ("moe", "mla", "ssm"):
        assert type(getattr(got, block)) is type(getattr(want, block))


def test_model_config_names_an_unknown_key():
    with pytest.raises(ValueError, match="'n_expert'"):
        spec.model_config({"name": "t", "model": dict(
            GQA, moe={"n_expert": 8, "top_k": 2})})
    with pytest.raises(ValueError, match="'d_modle'"):
        spec.model_config({"name": "t", "model": dict(GQA, d_modle=1)})


# an architecture of three scalings of the residual stream, in a layer
# group of its own, with counts of its own: nothing in it is dense
SCALE_ARCH = '''"""Test architecture: the residual stream scaled three times."""
from harness.counts import Counts


def leaf_specs(m):
    d, V = m["d_model"], m["vocab_size"]
    return [("embed", None, (V, d), "embed"),
            ("final_norm", None, (d,), "norm"),
            ("lm_head", None, (d, V), "mat"),
            ("blocks/scale", 3, (d,), "norm")]


def layers(m):
    return [("blocks", i) for i in range(3)]


def layer(h, w, *, m, fp8, block):
    return h * w["scale"]


def counts(m, dtype):
    return Counts(matmul_params=7, weight_params=11, attn_width=0,
                  n_layers=3, kv_bytes_per_token=0, dtype_bytes=2)
'''


def _files(root):
    return {p.relative_to(root): p.read_bytes()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_a_new_arch_module_is_found_by_name(tmp_path):
    root = tmp_path
    shutil.copytree(bench_tiny.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(bench_tiny.ROOT / "BENCHMARK.json", root)
    before = _files(root)
    # what a configuration's PR adds: a module, a configuration naming
    # it, a cell's check file, and entries in BENCHMARK.json
    (root / "bench/archs/scale3.py").write_text(SCALE_ARCH)
    conf = {"name": "scale3", "source": "test",
            "model": dict(GQA, arch_type="scale3"),
            "serving": bench_tiny.TINY_SERVING}
    (root / "bench/configs/scale3.json").write_text(json.dumps(conf))
    (root / "bench/cells/scale3.batch.json").write_text(json.dumps(
        {"check_requests": 2, "limits": {"served_gap": 0.1}}))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "scale3", "source": "test",
                         "file": "bench/configs/scale3.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "scale3.batch", "config": "scale3",
                           "traffic": "batch-2k", "chips": 1,
                           "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    after = _files(root)
    changed = {p for p in before if after[p] != before[p]}
    assert changed == {Path("BENCHMARK.json")}

    cell = spec.find_cell("scale3.batch", root=root)
    arch = cell.arch
    assert arch is spec.arch("scale3", root) and arch.__file__ == str(
        (root / "bench/archs/scale3.py").resolve())
    m, seed = cell.model, 2 ** 31 + 21
    params = weights.make_params(m, seed, jnp.bfloat16, arch)
    assert set(params) == {"embed", "final_norm", "lm_head", "blocks"}
    assert params["blocks"]["scale"].shape == (3, m["d_model"])
    assert jnp.array_equal(weights.layer_leaves(m, seed, 2, jnp.bfloat16,
                                                arch)["scale"],
                           params["blocks"]["scale"][2])
    assert arch.counts(m, "bfloat16").weight_params == 11

    # the reference is the module's three scalings, then the shared head
    ids = _seq(m)
    got = reference.logits(m, seed, jnp.bfloat16, [ids], [range(40, 45)],
                           block=16, pad_to=64, arch=arch)[0]
    up = {k: v.astype(jnp.float32) for k, v in params.items()
          if k != "blocks"}
    with jax.default_matmul_precision("highest"):
        h = up["embed"][jnp.asarray(ids[40:45])]
        for i in range(3):
            h = h * params["blocks"]["scale"][i].astype(jnp.float32)
        want = reference._head(h, up["final_norm"], up["lm_head"],
                               eps=float(m["norm_eps"]), fp8=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    # the check judges served tokens against this module's reference:
    # tokens it picks itself pass (to the rounding of a one-row head),
    # against the dense decoder's reference of the same sizes they fail
    noise = reference.sampling_noise(5, 3, 4, m["vocab_size"],
                                     jnp.bfloat16)
    r = traffic.Req(due=0.0, prompt=ids[:41], max_new=4, rid=3)
    r.out, r.done = [], 1.0
    for j in range(4):
        seq = r.prompt + r.out
        lg = reference.logits(m, seed, jnp.bfloat16, [seq], [[len(seq) - 1]],
                              block=16, pad_to=64, arch=arch)[0][0]
        r.out.append(int(jnp.argmax(lg + noise[j])))
    kw = dict(max_len=64)
    assert check.gaps(m, seed, jnp.bfloat16, 5, [r], arch,
                      **kw)["served_gap"] <= 1e-5
    assert check.gaps(m, seed, jnp.bfloat16, 5, [r], DENSE,
                      **kw)["served_gap"] > 0.1


# the dense decoder under another name, recording which of its parts the
# harness called
PROBE_ARCH = '''"""Test architecture: the dense decoder, each call recorded."""
from harness import spec

_dense = spec.arch("dense")
CALLS = set()


def leaf_specs(m):
    CALLS.add("leaf_specs")
    return _dense.leaf_specs(m)


def layers(m):
    CALLS.add("layers")
    return _dense.layers(m)


def layer(h, w, **kw):
    CALLS.add("layer")
    return _dense.layer(h, w, **kw)


def counts(m, dtype):
    CALLS.add("counts")
    return _dense.counts(m, dtype)
'''


def test_a_whole_run_uses_the_configurations_arch(tmp_path, capsys):
    root = bench_tiny.make_root(
        tmp_path, "batch", model=dict(GQA, arch_type="probe"))
    (root / "bench/archs/probe.py").write_text(PROBE_ARCH)
    out = bench_tiny.run(root, "batch", capsys)
    assert out["correct"] is True
    calls = spec.arch("probe", root).CALLS
    assert calls == {"leaf_specs", "layers", "layer", "counts"}


def test_unknown_arch_is_named(tmp_path):
    root = bench_tiny.make_root(
        tmp_path, "batch", model=dict(GQA, arch_type="nonesuch"))
    with pytest.raises(KeyError, match="nonesuch"):
        spec.find_cell("tiny.batch", root=root)


def test_steps_carry_the_programs_counters_and_requests_their_admission(
        tmp_path):
    root = bench_tiny.make_root(tmp_path, "agent")
    seconds = 2.0
    cell, window, _ = bench_tiny.window(root, "agent", 2 ** 31 + 31,
                                        seconds)
    from repro.core.events import EngineStepped
    ints = {f.name for f in dataclasses.fields(EngineStepped)
            if f.type in ("int", int)}
    assert {"live", "generated", "prefilled", "preempted", "prefill_rows",
            "queued", "blocks_in_use", "prefix_hits"} <= ints
    assert window.steps
    for s in window.steps:
        assert set(s.counters) == ints
        assert s.counters["prefill_rows"] >= s.counters["prefilled"]
    admitted = [r for r in window.requests if r.admit_step is not None]
    assert admitted
    for r in admitted:
        assert r.submit - 0.01 <= r.admit <= seconds + 30
        assert r.first is None or r.admit <= r.first
    sv = cell.serving
    run = hm.RunData(cell, window, cell.arch.counts(cell.model, sv["dtype"]),
                     None, None, int(sv["prefill_chunk"]))
    assert run.spans is None
    for name in ("queue_wait_p90_ms", "prefill_pad_share.agent"):
        assert spec.metric_reader(name)(run) is not None, name
