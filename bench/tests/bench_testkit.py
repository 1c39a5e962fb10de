"""Shared helpers of the benchmark's tests: the bench directory on the
import path, and toy-sized copies of the benchmark in a temporary root."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

# toy sizes of each configuration and mix: small enough for the CPU
TOY = {
    "bench/configs/roadnet-pa.json": {
        "graph": {"n_nodes": 4000, "n_edges": 5600},
        # under the program's H3 priorities the toy graph's sets held
        # 1,813-1,850 vertices over 30 keys of the pool, under its uniform
        # priorities 1,688-1,741 (on the CPU)
        "limits": {"mis_size": {"at_least": 1780}},
    },
}


def _merge(doc: dict, change: dict) -> None:
    for k, v in change.items():
        if isinstance(v, dict):
            _merge(doc.setdefault(k, {}), v)
        else:
            doc[k] = v


def toy_copy(dest: pathlib.Path, toy: bool = True) -> pathlib.Path:
    """`BENCHMARK.json` and `bench/` copied under `dest`, cut to toy size."""
    dest.mkdir(parents=True, exist_ok=True)
    shutil.copy(ROOT / "BENCHMARK.json", dest)
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    if toy:
        for rel, change in TOY.items():
            doc = json.loads((dest / rel).read_text())
            _merge(doc, change)
            (dest / rel).write_text(json.dumps(doc))
    return dest


def run_cell(cell, make_system=None, seed: int = 2**31 + 3, seconds: float = 1.0):
    """Drive one run of `cell` past the harness's look for a chip and judge
    it: (correct, checks, run)."""
    import time

    from benchlib import harness

    run, system = harness.execute(cell, seed, seconds, False, time.perf_counter(),
                                  make_system=make_system)
    del system
    correct, checks, _ = harness.judge(cell, run.workload, run.window)
    return correct, checks, run
