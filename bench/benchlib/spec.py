"""`BENCHMARK.json` and the files it names, found by name.

Everything that belongs to one configuration, traffic mix, metric, graph
generator, system adapter or reference sits in a file of its own:

    bench/configs/<config>.json        sizes, source, guarantees, system
    bench/traffic/<traffic>.json       a mix: its `pattern` and parameters
    bench/traffic/<pattern>.py         `drive(loop, params, seconds)`
    bench/metrics/<metric>.py          `read(run) -> float | None`
    bench/graphs/<generator>.py        `make(params, seed, index)`
    bench/systems/<entry>.py           `System(config, workload)`
    bench/references/<reference>.py    `check(...)`, `solve(...)`

so a later change adds a cell, a mix or a metric by adding files and
entries, never by editing one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
from types import ModuleType
from typing import Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent.parent
ROOT = BENCH.parent

_MODULES: Dict[pathlib.Path, ModuleType] = {}


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file by path (its name may hold dots and dashes)."""
    path = path.resolve()
    if path in _MODULES:
        return _MODULES[path]
    if not path.is_file():
        raise SpecError(f"no such file: {path}")
    name = f"bench_{len(_MODULES)}_" + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


def _read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"no such file: {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    kind: str                 # end_to_end | per_layer
    workloads: Optional[List[str]]

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads

    def reader(self, bench: pathlib.Path) -> ModuleType:
        return load_module(bench / "metrics" / f"{self.name}.py")


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of `workloads`, with its configuration and traffic read."""
    name: str
    bench: pathlib.Path       # the bench/ directory the files came from
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    metrics: List[Metric]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = root / "bench"
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(bench / "traffic" / f"{w['traffic']}.json")
    metrics = [
        Metric(m["name"], m["unit"], kind, m.get("workloads"))
        for kind in ("end_to_end", "per_layer")
        for m in spec[kind]
    ]
    return Cell(
        name=name, bench=bench, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config=config, traffic=traffic,
        metrics=[m for m in metrics if m.applies_to(name)],
    )
