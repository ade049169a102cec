"""Every entry of BENCHMARK.json loads by name, and the file keeps to
the benchmark's contract; the work counts agree with the program's own
parameter shapes."""
import json
import re

import jax
import jax.numpy as jnp
import pytest

from bench_tiny import BENCH, ROOT
from harness import spec

B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in B["workloads"]])
def test_every_cell_loads_by_name(cell):
    c = spec.find_cell(cell)
    cfg = spec.model_config(c.config)
    assert cfg.n_layers == c.model["n_layers"]
    assert c.mix["kind"] in ("agent", "batch")
    assert c.limits["limits"]
    assert c.end_to_end and c.per_layer
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    for m in c.end_to_end + c.per_layer:
        if m["name"] != "setup_s":
            assert callable(spec.metric_reader(m["name"]))
    for m in c.per_layer:
        assert m["moves"] in names


def test_contract_shape():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["bench"] and B["command"][1] == "bench/run.py"
    assert 1 <= B["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    pairs = set()
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    files = set()
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        conf = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(conf["reduced"])
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank")) and key in (
                "n_layers", "context")
    ends = {m["name"] for m in B["end_to_end"]}
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in B["per_layer"]:
        assert m["moves"] in ends and "\n" not in m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert len(json.dumps(B)) < 64 * 1024


@pytest.mark.parametrize("name", [c["name"] for c in B["configs"]])
def test_counts_match_the_programs_parameter_shapes(name):
    from repro.models.params import abstract_params
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    m = conf["model"]
    cfg = spec.model_config(conf)
    shapes = abstract_params(cfg, dtype=jnp.bfloat16)
    sizes = {"/".join(str(k.key) for k in path): leaf.size
             for path, leaf in
             jax.tree_util.tree_leaves_with_path(shapes)}
    arch = spec.arch(m["arch_type"])
    c = arch.counts(m, "bfloat16")
    assert c.weight_params == sum(sizes.values()) - sizes["embed"]
    matmuls = sum(v for k, v in sizes.items()
                  if k.split("/")[-1].startswith("w") or k == "lm_head")
    assert c.matmul_params == matmuls
    L, kv = m["n_layers"], m["n_kv_heads"] * m["head_dim"]
    assert c.kv_bytes_per_token == 2 * L * kv * 2
    # the benchmark's own draw has the program's layout, leaf for leaf
    mine = {path: shape if stack is None else (stack, *shape)
            for path, stack, shape, _ in arch.leaf_specs(m)}
    assert {k: tuple(v.shape) for k, v in
            zip(sizes, jax.tree_util.tree_leaves(shapes))} == {
        k: tuple(v) for k, v in mine.items()}
