"""The Kronecker deployment (`kron-g500-s17`): its graph, made the same
from the same seed as the program's generator makes it, and the
`solver_plan` system reading the plan build's own names, at toy scale on
the CPU."""
import json

import numpy as np
import pytest

import bench_testkit
from benchlib import harness
from benchlib.spec import load_cell, load_module

kron = load_module(bench_testkit.BENCH / "graphs" / "kronecker.py")

PARAMS = {"scale": 12, "edge_factor": 48, "a": 0.57, "b": 0.19, "c": 0.19}


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_kronecker_edges_are_canonical_and_fixed_by_the_seed(seed):
    n, u, v = kron.make(PARAMS, seed)
    assert n == 4096 and u.dtype == np.int32 and v.dtype == np.int32
    assert np.all(u < v) and v.max() < n
    key = u.astype(np.int64) * n + v
    assert np.unique(key).shape[0] == key.shape[0]
    _, u2, v2 = kron.make(PARAMS, seed)
    assert np.array_equal(u, u2) and np.array_equal(v, v2)
    _, u3, _ = kron.make(PARAMS, seed + 1)
    assert not np.array_equal(u, u3[: u.shape[0]])


def test_kronecker_is_the_program_generator():
    from repro.graphs.generators import rmat

    g = rmat(12, edge_factor=48, seed=3)
    n, u, v = kron.make(PARAMS, 3)
    assert (n, g.n_edges) == (g.n_nodes, 2 * u.shape[0])
    s = np.asarray(g.senders)[: g.n_edges]
    r = np.asarray(g.receivers)[: g.n_edges]
    up = s < r
    assert np.array_equal(s[up], u) and np.array_equal(r[up], v)


def test_solver_plan_reads_the_plan_build(tmp_path):
    root = bench_testkit.toy_copy(tmp_path)
    path = root / "bench" / "configs" / "kron-g500-s17.json"
    doc = json.loads(path.read_text())
    # toy scale; T=16, the tile size the full-scale plan takes
    doc["graph"]["scale"] = 8
    doc["system"]["options"] = {"tile_size": 16}
    path.write_text(json.dumps(doc))

    cell = load_cell("kron-solve", root)
    _, checks, run = bench_testkit.run_cell(cell, seconds=0.5)
    # every answer valid.  `repeats` is left out here: a 256-vertex
    # Kronecker graph is small enough that H3 gives the same set under
    # distinct keys (9 sets from 40 keys on the CPU); at scale 17 the 48
    # keys of the pool and the 2 warm-up keys give 50 distinct sets
    for k in ("missing", "bad_shape", "both_in", "uncovered"):
        assert checks[k]["value"] == 0, checks
    info = run.setup_info
    assert info["plan_s"] >= info["plan_tail_s"] > 0
    assert 0 < info["tail_entries"] <= info["tail_capacity"]
    assert info["plan_dense_tiles"] >= 0 and info["plan_device_bytes"] > 0
    layer = harness.metrics_of(run, "per_layer")
    assert layer["plan_tail_s.setup"]["value"] == info["plan_tail_s"]
    assert 0 < layer["tail_fill.solve"]["value"] <= 100
    assert layer["plan_device_mb.setup"]["value"] == info["plan_device_bytes"] / 1e6
    assert {"rounds.solve", "plan_s.setup"} <= set(layer)
