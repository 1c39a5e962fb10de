"""A configuration, a traffic mix with a pattern of its own, a metric, a
graph generator and a system added as files, with entries in
BENCHMARK.json, are picked up by name with no existing file edited."""
import hashlib
import json

import pytest

import bench_testkit
from benchlib import harness
from benchlib.spec import load_cell

NEW = {
    "bench/graphs/ring.py": '''
import numpy as np


def make(params, seed):
    n = int(params["n_nodes"])
    u = np.arange(n - 1, dtype=np.int32)
    return n, u, u + 1
''',
    "bench/systems/plain.py": '''
from collections import deque

from benchlib.spec import load_module


class System:
    def __init__(self, config, workload):
        self.workload = workload
        self.q = deque()
        self.setup_info = {"ready": 1.0}

    def setup(self):
        import pathlib
        self.ref = load_module(pathlib.Path(__file__).parent.parent
                               / "references" / "mis.py")

    def submit(self, i):
        self.q.append(i)

    def pending(self):
        return len(self.q)

    def step(self):
        i = self.q.popleft()
        n, u, v = self.workload.graph(i)
        return [(i, self.ref.solve(n, u, v, seed=self.workload.key(i)), {})]
''',
    "bench/metrics/answered.py": '''
def read(run):
    return float(sum(r.done is not None for r in run.window.requests))
''',
    "bench/configs/ring-64.json": json.dumps({
        "name": "ring-64", "reduced": [],
        "graph": {"generator": "ring", "seed": 1, "n_nodes": 64},
        "keys": {"seed": 3, "block": 4, "blocks": 2},
        "system": {"entry": "plain"}, "reference": "mis",
        "limits": {k: {"at_most": 0} for k in
                   ("missing", "bad_shape", "both_in", "uncovered", "repeats")},
    }),
    # an open loop: one request due every `period_s`, whether or not the
    # last one has been answered
    "bench/traffic/paced.py": '''
import time


def drive(loop, params, seconds):
    period, k = float(params["period_s"]), 0
    while loop.live():
        while k * period < seconds and k * period <= loop.now():
            loop.send(due=k * period)
            k += 1
        if loop.pending():
            loop.step()
        elif k * period >= seconds:
            return
        else:
            time.sleep(max(0.0, k * period - loop.now()))
''',
    "bench/traffic/paced-20.json": json.dumps({"pattern": "paced", "period_s": 0.05}),
}


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_cell_is_found_by_name(tmp_path):
    root = bench_testkit.toy_copy(tmp_path)
    before = _digest(root)
    for rel, text in NEW.items():
        (root / rel).write_text(text)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "ring-64", "source": "https://example.org",
                            "file": "bench/configs/ring-64.json", "reduced": [],
                            "why": "a ring"})
    spec["workloads"].append({"name": "ring.paced", "config": "ring-64",
                              "traffic": "paced-20", "chips": 1, "why": "a test"})
    spec["per_layer"].append({"name": "answered", "unit": "requests",
                              "better": "higher", "source": "program_counter",
                              "layer": "service", "moves": "setup_s",
                              "workloads": ["ring.paced"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = load_cell("ring.paced", root)
    assert cell.config["name"] == "ring-64" and cell.traffic["period_s"] == 0.05
    correct, checks, run = bench_testkit.run_cell(cell, seconds=0.2)
    assert correct, checks
    # due at 0, 0.05, 0.1 and 0.15, each answered
    assert [r.due for r in run.window.requests] == pytest.approx([0.0, 0.05, 0.1, 0.15])
    layer = harness.metrics_of(run, "per_layer")
    assert layer["answered"]["value"] == len(run.window.requests) > 0
    assert set(harness.metrics_of(run, "end_to_end")) == {"setup_s"}
    after = _digest(root)
    assert all(after[p] == h for p, h in before.items())
