"""A tree of benchmark files around a tiny configuration, for tests that
drive whole runs of the harness on the CPU."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {"arch_type": "dense", "n_layers": 2, "d_model": 128,
              "n_heads": 4, "n_kv_heads": 2, "head_dim": 32, "d_ff": 256,
              "vocab_size": 512, "mlp_type": "swiglu", "qkv_bias": True,
              "tie_embeddings": False, "norm_eps": 1e-6,
              "rope_theta": 10000.0}
TINY_SERVING = {"n_slots": 4, "max_len": 256, "block_size": 16,
                "prefill_chunk": 64, "dtype": "bfloat16",
                "temperature": 1.0}
MIXES = {
    "agent": {"kind": "agent", "apps": 2, "prefix_tokens": [70, 100],
              "growth_tokens": [16, 40], "instruction_tokens": [4, 8],
              "turns": [2, 4], "output_tokens": [4, 10],
              "final_output_tokens": 20, "tool_gap_s": [0.1, 0.3],
              "session_rate_per_s": 3.0, "drain_s": 30, "shape_seed": 1},
    "batch": {"kind": "batch", "prompt_tokens": [8, 120],
              "prompt_sigma": 0.7, "output_tokens": [8, 30], "backlog": 8,
              "template_requests": 64, "shape_seed": 2},
}
# the widest served gap of sound tiny runs read 0 to 0.042 on 8 seeds, the
# float8 control's 0.23 to 0.78 on the same requests (CPU)
TINY_LIMIT = 0.1


def make_root(tmp: Path, kind: str, model=None) -> Path:
    """A checkout-like tree: BENCHMARK.json with a ``tiny.<kind>`` cell,
    its configuration, mix and check file, and the real metric readers
    and architecture modules."""
    root = Path(tmp)
    (root / "bench").mkdir(parents=True, exist_ok=True)
    for d in ("metrics", "archs"):
        shutil.copytree(BENCH / d, root / "bench" / d, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for d in ("configs", "traffic", "cells"):
        (root / "bench" / d).mkdir(exist_ok=True)
    conf = {"name": "tiny", "source": "test", "model": model or TINY_MODEL,
            "serving": TINY_SERVING}
    (root / "bench/configs/tiny.json").write_text(json.dumps(conf))
    (root / f"bench/traffic/tiny-{kind}.json").write_text(
        json.dumps(MIXES[kind]))
    (root / f"bench/cells/tiny.{kind}.json").write_text(json.dumps(
        {"check_requests": 4, "limits": {"served_gap": TINY_LIMIT}}))
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"})
    cell = f"tiny.{kind}"
    b["workloads"].append({"name": cell, "config": "tiny",
                           "traffic": f"tiny-{kind}", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m and any(w.endswith("." + kind)
                                    for w in m["workloads"]):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


def run(root: Path, kind: str, capsys, seed: int = 2 ** 31 + 77,
        seconds: float = 2.0, trace: int = 0, fault=None,
        extra=()) -> dict:
    """One whole harness run of the tiny cell; returns its result line."""
    import time
    from harness.main import main
    rc = main(["--workload", f"tiny.{kind}", "--seed", str(seed),
               "--seconds", str(seconds), "--trace", str(trace), *extra],
              time.perf_counter(), require_accelerator=False, fault=fault,
              root=root)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def window(root: Path, kind: str, seed: int, seconds: float = 2.0):
    """Set-up, warm-up and window of the tiny cell as a run makes them,
    with no check: returns the cell, the window and the scheduler."""
    from harness import serve, spec, traffic
    cell = spec.find_cell(f"tiny.{kind}", root=root)
    m, sv, mix = cell.model, cell.serving, cell.mix
    vocab, max_len = m["vocab_size"], int(sv["max_len"])
    _, sched = serve.build(cell, spec.model_config(cell.config), seed)
    serve.warm_up(sched, traffic.warm_shapes(
        mix, seed, seconds, vocab, max_len, int(sv["prefill_chunk"])),
        vocab, seed)
    if mix["kind"] == "agent":
        reqs = traffic.agent_requests(mix, seed, seconds, vocab, max_len)
    else:
        reqs = traffic.batch_requests(mix, seed, vocab, max_len)
    return cell, serve.run_window(sched, mix, reqs, seconds), sched
