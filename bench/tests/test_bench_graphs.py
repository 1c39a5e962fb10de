"""The benchmark's inputs: the road graph at its published sizes, made the
same from the same seed, and the priority keys."""
import numpy as np
import pytest

import bench_testkit
from benchlib.spec import load_cell, load_module
from benchlib.workload import Workload, half_edges

road = load_module(bench_testkit.BENCH / "graphs" / "road_lattice.py")

ROAD = {"n_nodes": 1_090_920, "n_edges": 1_541_898, "diag_frac": 0.05}


def _edges_ok(n, u, v):
    assert np.all(u < v) and v.max() < n
    key = u.astype(np.int64) * n + v
    assert np.unique(key).shape[0] == key.shape[0]


def test_road_graph_has_the_published_sizes():
    n, u, v = road.make(ROAD, 1)
    assert n == 1_090_920
    assert u.shape[0] == 1_541_898
    _edges_ok(n, u, v)


def test_road_graph_is_deterministic_in_the_seed():
    small = {"n_nodes": 2_000, "n_edges": 2_800, "diag_frac": 0.05}
    a, b, c = road.make(small, 5), road.make(small, 5), road.make(small, 6)
    assert np.array_equal(a[1], b[1]) and np.array_equal(a[2], b[2])
    assert not np.array_equal(a[1], c[1])


def test_keys_are_a_fixed_pool_in_blocks_ordered_by_the_seed():
    cell = load_cell("road-solve", bench_testkit.ROOT)
    a, b = Workload(cell, 2**31 + 1), Workload(cell, 7)
    block = cell.config["keys"]["block"]
    size = block * cell.config["keys"]["blocks"]
    ka = [a.key(i) for i in range(size)]
    kb = [b.key(i) for i in range(size)]
    # each block asks for the same keys under every seed, in another order
    for i in range(0, size, block):
        assert sorted(ka[i:i + block]) == sorted(kb[i:i + block])
    assert ka != kb
    assert len(set(ka)) == size and all(0 <= k < 2**31 for k in ka)
    assert [a.key(i + size) for i in range(size)] == ka
    assert ka == [Workload(cell, 2**31 + 1).key(i) for i in range(size)]
    assert a.warm_keys() == b.warm_keys() and not set(a.warm_keys()) & set(ka)


def test_the_graph_is_fixed_by_the_configuration():
    cell = load_cell("road-solve", bench_testkit.ROOT)
    cell.config["graph"].update(n_nodes=900, n_edges=1260)
    a, b = Workload(cell, 1), Workload(cell, 2)
    assert a.graph(0) is a.graph(5)
    assert np.array_equal(a.graph()[1], b.graph()[1])


@pytest.mark.parametrize("n", [50, 5_000])
def test_half_edges_are_both_directions_sorted_by_sender(n):
    rng = np.random.default_rng(0)
    u = rng.integers(0, n - 1, 200)
    v = u + 1 + rng.integers(0, n - 1 - u)
    key = np.unique(u * n + v)
    g = (n, (key // n).astype(np.int32), (key % n).astype(np.int32))
    s, r = half_edges(g)
    assert s.shape[0] == 2 * key.shape[0]
    order = np.lexsort((r, s))
    assert np.array_equal(order, np.arange(s.shape[0]))
    both = set(zip(s.tolist(), r.tolist()))
    assert all((b, a) in both for a, b in both)
