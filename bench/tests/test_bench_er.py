"""The ER-[700-800] deployment (`er-700-800`): its set of graphs, made the
same from the same seed, and the `solver_batch` system restarting the
whole set through `Solver.solve_many`, at toy size on the CPU."""
import json

import numpy as np
import pytest

import bench_testkit
from benchlib import harness
from benchlib.spec import load_cell, load_module

er = load_module(bench_testkit.BENCH / "graphs" / "er_set.py")

FULL = {"graphs": 128, "n_min": 700, "n_max": 800, "p": 0.15}
TOY = {"graphs": 5, "n_min": 40, "n_max": 90, "p": 0.15}


def _members_ok(n, u, v, sizes):
    """u < v, unique, and every edge inside one member's id range."""
    assert sizes.sum() == n and u.dtype == np.int32 and v.dtype == np.int32
    assert np.all(u < v) and v.max() < n
    key = u.astype(np.int64) * n + v
    assert np.unique(key).shape[0] == key.shape[0]
    bounds = np.cumsum(sizes)
    assert np.array_equal(np.searchsorted(bounds, u, side="right"),
                          np.searchsorted(bounds, v, side="right"))


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_er_set_is_fixed_by_the_seed(seed):
    n, u, v = er.make(TOY, seed)
    _members_ok(n, u, v, er.member_sizes(TOY, seed))
    _, u2, v2 = er.make(TOY, seed)
    assert np.array_equal(u, u2) and np.array_equal(v, v2)
    _, u3, _ = er.make(TOY, seed + 1)
    assert not np.array_equal(u, u3[: u.shape[0]])


def test_er_set_at_full_size_has_the_published_shape():
    sizes = er.member_sizes(FULL, 1)
    assert sizes.shape == (128,) and sizes.min() >= 700 and sizes.max() <= 800
    n, u, v = er.make(FULL, 1)
    _members_ok(n, u, v, sizes)
    pairs = (sizes * (sizes - 1) // 2).sum()
    mean, sd = pairs * 0.15, np.sqrt(pairs * 0.15 * 0.85)
    assert abs(u.shape[0] - mean) < 6 * sd


def test_solver_batch_restarts_the_set(tmp_path):
    root = bench_testkit.toy_copy(tmp_path)
    path = root / "bench" / "configs" / "er-700-800.json"
    doc = json.loads(path.read_text())
    doc["graph"].update(TOY)
    doc["system"]["options"] = {"tile_size": 16}
    path.write_text(json.dumps(doc))

    cell = load_cell("er-batch", root)
    correct, checks, run = bench_testkit.run_cell(cell, seconds=0.5)
    assert correct, checks
    info = run.setup_info
    assert info["plan_s"] > 0 and 0 < info["edge_fill"] <= 1
    assert 0 < info["tile_fill"] <= 1
    layer = harness.metrics_of(run, "per_layer")
    assert layer["edge_fill.solve"]["value"] == 100 * info["edge_fill"]
    assert layer["pack_ms.solve"]["value"] > 0
    assert "rounds.solve" in layer
    answered = [r for r in run.window.requests if r.done is not None]
    assert answered and all(r.answer.shape == (run.workload.graph()[0],)
                            for r in answered)


def test_solver_batch_stops_at_once_without_a_pack_cache(monkeypatch):
    from repro.obs.metrics import MetricsRegistry

    cell = load_cell("er-batch", bench_testkit.ROOT)
    system = load_module(cell.bench / "systems" / "solver_batch.py")
    monkeypatch.setattr(MetricsRegistry, "snapshot", lambda self: {})
    with pytest.raises(RuntimeError, match="caches packed batches"):
        system.System(cell.config, None)
