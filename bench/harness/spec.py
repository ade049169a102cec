"""Finding a cell's parts by name: ``BENCHMARK.json`` at the root, one
file per configuration (``bench/configs``), traffic mix
(``bench/traffic``), metric reader (``bench/metrics``), cell's check
limits (``bench/cells``) and architecture (``bench/archs``).  Adding a
cell, or a model of another architecture, adds files; nothing here
changes."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import typing
from pathlib import Path
from types import ModuleType
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
    arch: ModuleType      # bench/archs/<the model block's arch_type>.py

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
                chips=int(w["chips"]),
                arch=arch(conf["model"]["arch_type"], root))


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


_ARCHS: Dict[Path, ModuleType] = {}


def arch(name: str, root: Path = ROOT) -> ModuleType:
    """The module ``bench/archs/<name>.py``: what the benchmark draws,
    computes and counts for one architecture.  A configuration names it
    by its model block's ``arch_type``, the key the program builds its
    model by (``dense``, ``moe``, ``ssm``, ``hybrid``); a module covers
    the variants of its type, as the dense one covers QKV bias.  It
    gives:

    * ``leaf_specs(m)``: every weight as ``(path, stack, shape, kind)``,
      in the program's layout (``"layers/attn/wq"``); ``stack`` is the
      length of the layer group the leaf belongs to (its leading axis in
      the program), None for a leaf drawn once.  A leaf's place in the
      list seeds its draw (``harness/weights.py``), so a module appends.
    * ``layers(m)``: the program's order of layers, as (group, index in
      the group); a group is the path prefix its leaves share.
    * ``layer(h, w, *, m, fp8, block)``: one layer's float32 forward
      over a whole sequence ``h: (T, d_model)``, given that layer's
      leaves ``w`` (``weights.layer_leaves``) and the model block ``m``
      frozen (``reference._freeze``); ``fp8`` is the control's rounding
      of every matrix product (``reference._mm``).
    * ``counts(m, dtype)``: a ``harness.counts.Counts``, or a subclass
      of it, of the work a token and a decode step need.

    Loaded once per file."""
    path = (root / "bench" / "archs" / f"{name}.py").resolve()
    if path not in _ARCHS:
        if not path.exists():
            known = sorted(p.stem for p in path.parent.glob("*.py"))
            raise KeyError(f"unknown arch {name!r}; known: {known}")
        s = importlib.util.spec_from_file_location(
            "bench_arch_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(s)
        s.loader.exec_module(mod)
        _ARCHS[path] = mod
    return _ARCHS[path]


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file.  A field
    whose type is a dataclass (``moe``, ``mla``, ``ssm``) is built from
    its block in turn; a key that the program's dataclass lacks raises
    with its name."""
    from repro.configs.base import ModelConfig
    fields: Dict = dict(conf["model"])
    fields.setdefault("name", conf["name"])
    fields.setdefault("source", conf.get("source", ""))
    return _build(ModelConfig, fields)


def _build(cls, values: dict):
    hints = typing.get_type_hints(cls)
    known = {f.name for f in dataclasses.fields(cls)}
    kwargs = {}
    for key, value in values.items():
        if key not in known:
            raise ValueError(f"unknown key {key!r} in a {cls.__name__} "
                             "block of the configuration")
        sub = next((t for t in (hints[key], *typing.get_args(hints[key]))
                    if dataclasses.is_dataclass(t)), None)
        kwargs[key] = (_build(sub, value)
                       if sub is not None and isinstance(value, dict)
                       else value)
    return cls(**kwargs)
