"""Finding a cell's parts by name: ``BENCHMARK.json`` at the root, one
file per configuration (``bench/configs``), traffic mix
(``bench/traffic``), metric reader (``bench/metrics``) and cell's check
limits (``bench/cells``).  Adding a cell adds files; nothing here
changes."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict          # the configuration file
    mix: dict             # the traffic file
    limits: dict          # the cell's check file
    end_to_end: List[dict]
    per_layer: List[dict]
    chips: int

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def serving(self) -> dict:
        return self.config["serving"]


def _for_cell(metrics: List[dict], cell: str) -> List[dict]:
    return [m for m in metrics if "workloads" not in m or cell in m["workloads"]]


def find_cell(name: str, bench: Optional[dict] = None,
              root: Path = ROOT) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = _json(root / confs[w["config"]]["file"])
    mix = _json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = _json(root / "bench" / "cells" / f"{name}.json")
    return Cell(name=name, config=conf, mix=mix, limits=limits,
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name),
                chips=int(w["chips"]))


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``.  A
    quantity split by the end-to-end metric it moves (``step_ms.agent``,
    ``step_ms.batch``) has one reader, named by the part before the dot."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.exists():
        path = root / "bench" / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    fields: Dict = dict(conf["model"])
    fields.setdefault("name", conf["name"])
    fields.setdefault("source", conf.get("source", ""))
    return ModelConfig(**fields)
