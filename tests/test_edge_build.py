"""The edge-sized hybrid plan build (DESIGN.md §16) and the Graph500
Kronecker graph (G8) it exists for.

A hybrid plan counts each tile's cells from the half-edges, packs only the
tiles at or above the threshold and lowers the rest straight to the COO
tail; the full tile list is never built and never reaches the device.  The
contract: the same dense sub-tiling and tail as partitioning a full tiling
(`partition_tiles`, the tile-list route), every engine × storage ×
frontier answering as the `segment` engine does, and a patched plan equal
to a rebuild of the mutated graph.
"""
import importlib.util
import pathlib

import jax
import numpy as np
import pytest

from repro.api import SolveOptions, Solver
from repro.api.plan import build_plan
from repro.core.tiling import (
    attach_partition,
    build_block_tiles,
    full_tiling,
    partition_tiles,
    tail_capacity,
)
from repro.dyngraph import apply_graph_delta, random_delta
from repro.graphs.generators import erdos_renyi, grid2d, rmat

_REF = pathlib.Path(__file__).resolve().parents[1] / "bench" / "references" / "mis.py"


def _reference():
    spec = importlib.util.spec_from_file_location("bench_ref_mis", _REF)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _check(g, in_mis):
    """`bench/references/mis.py`'s verdict on an answer, original ids."""
    s = np.asarray(g.senders)[: g.n_edges]
    r = np.asarray(g.receivers)[: g.n_edges]
    up = s < r
    found = _reference().check(g.n_nodes, s[up], r[up], np.asarray(in_mis))
    return found["bad_shape"], found["both_in"], found["uncovered"]


GRAPHS = {
    "road": lambda: grid2d(48, 50, seed=1),
    "kron10": lambda: rmat(10, edge_factor=48, seed=1),
    "kron11": lambda: rmat(11, edge_factor=48, seed=2),
    "kron12": lambda: rmat(12, edge_factor=48, seed=3),
    "er": lambda: erdos_renyi(3000, avg_deg=6.0, seed=4),
}
_built = {}


def _graph(name):
    if name not in _built:
        _built[name] = GRAPHS[name]()
    return _built[name]


def _pairs(rows, cols, nnz):
    p = np.stack([np.asarray(rows)[:nnz], np.asarray(cols)[:nnz]], axis=1)
    return p[np.lexsort((p[:, 1], p[:, 0]))]


def _same_tiling(a, b):
    for f in ("tiles", "tile_rows", "tile_cols", "row_starts"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)
    assert (a.n_tiles, a.n_block_rows, a.storage) == \
        (b.n_tiles, b.n_block_rows, b.storage)


@pytest.mark.parametrize("storage", ["int8", "bitpack"])
@pytest.mark.parametrize("thr", [4, 68])
@pytest.mark.parametrize("mode", ["auto", "forced", "off"])
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_edge_build_matches_the_tile_list_partition(name, mode, thr, storage):
    g = _graph(name)
    new = build_plan(g, 16, None, "k", storage=storage, hybrid=mode,
                     hybrid_threshold=thr).tiled
    old = attach_partition(build_block_tiles(g, 16, storage=storage),
                           mode=mode, threshold=thr)
    assert (new.partition is None) == (old.partition is None)
    if new.partition is None:
        _same_tiling(new, old)
        return
    pn, po = new.partition, old.partition
    _same_tiling(pn.dense, po.dense)
    assert (pn.n_dense_tiles, pn.n_sparse_tiles, pn.sp_nnz) == \
        (po.n_dense_tiles, po.n_sparse_tiles, po.sp_nnz)
    np.testing.assert_array_equal(_pairs(pn.sp_rows, pn.sp_cols, pn.sp_nnz),
                                  _pairs(po.sp_rows, po.sp_cols, po.sp_nnz))
    assert pn.sp_rows.shape == po.sp_rows.shape \
        == (tail_capacity(pn.sp_nnz),)
    # no device array holds the full tile list
    assert new.tiles.shape[0] == new.tile_rows.shape[0] == 0
    assert new.n_tiles == pn.n_dense_tiles + pn.n_sparse_tiles
    _same_tiling(full_tiling(new), build_block_tiles(g, 16, storage=storage))


@pytest.mark.parametrize("name", ["kron12", "road"])
def test_plan_device_bytes_are_the_partition_and_the_edge_list(name):
    g = _graph(name)
    plan = build_plan(g, 16, None, "k", storage="bitpack", hybrid="forced",
                      hybrid_threshold=68)
    part = plan.tiled.partition
    tail = part.sp_rows.nbytes + part.sp_cols.nbytes
    edges = plan.g.senders.nbytes + plan.g.receivers.nbytes
    assert plan.device_bytes == part.dense.memory_bytes() + tail + edges
    full = build_block_tiles(g, 16, storage="bitpack")
    assert plan.device_bytes < full.memory_bytes() + tail + edges


@pytest.mark.parametrize("engine", ["tiled_ref", "tiled_pallas", "fused_pallas"])
@pytest.mark.parametrize("storage,frontier", [
    ("int8", "dense"), ("bitpack", "dense"), ("bitpack", "bitwise"),
])
def test_kronecker_answers_as_the_segment_engine(engine, storage, frontier):
    g = _graph("kron10")
    ref = Solver(SolveOptions(engine="segment")).solve(g).in_mis
    s = Solver(SolveOptions(engine=engine, storage=storage, frontier=frontier,
                            tile_size=16))
    assert s.plan(g).tiled.partition is not None     # auto partitions G8
    got = s.solve(g).in_mis
    np.testing.assert_array_equal(got, ref)
    assert _check(g, got) == (0, 0, 0)


@pytest.mark.parametrize("repair", ["incremental", "cold"])
@pytest.mark.parametrize("storage", ["int8", "bitpack"])
def test_update_on_a_partitioned_kronecker_plan_equals_a_rebuild(repair, storage):
    g = _graph("kron10")
    s = Solver(SolveOptions(engine="fused_pallas", storage=storage,
                            tile_size=16, repair=repair))
    prior = s.solve(g)
    delta = random_delta(g, n_add=40, n_remove=40, seed=7)
    res = s.update(prior, delta)
    g2 = apply_graph_delta(g, delta)
    patched = res.plan.tiled
    rebuilt = build_plan(g2, 16, None, "k", storage=storage, hybrid="auto",
                         hybrid_threshold=prior.plan.hybrid_threshold).tiled
    assert patched.partition is not None and rebuilt.partition is not None
    _same_tiling(patched.partition.dense, rebuilt.partition.dense)
    for f in ("sp_rows", "sp_cols"):
        np.testing.assert_array_equal(
            np.asarray(getattr(patched.partition, f)),
            np.asarray(getattr(rebuilt.partition, f)))
    assert patched.n_tiles == rebuilt.n_tiles
    assert _check(g2, res.in_mis) == (0, 0, 0)
    if repair == "cold":   # a cold re-solve is the segment engine's answer
        cold = Solver(SolveOptions(engine="segment")).solve(
            g2, key=jax.random.key(0))
        np.testing.assert_array_equal(res.in_mis, cold.in_mis)


def test_partition_survives_the_plan_cache(tmp_path):
    from repro.api import PlanCache

    g = _graph("kron11")
    kw = dict(cache_dir=str(tmp_path), tile_size=16, storage="bitpack",
              hybrid="auto")
    a, st_a = PlanCache(**kw).plan(g)
    b, st_b = PlanCache(**kw).plan(g)
    assert (st_a, st_b) == ("built", "disk")
    _same_tiling(a.tiled.partition.dense, b.tiled.partition.dense)
    for f in ("sp_rows", "sp_cols"):
        np.testing.assert_array_equal(np.asarray(getattr(a.tiled.partition, f)),
                                      np.asarray(getattr(b.tiled.partition, f)))
    assert b.tiled.n_tiles == a.tiled.n_tiles and b.tiled.tiles.shape[0] == 0
    assert b.device_bytes == a.device_bytes


def test_partition_tiles_is_the_tile_list_route():
    # the oracle above partitions a FULL tile list; its result wraps a
    # tiling that holds no full list either
    g = _graph("er")
    full = build_block_tiles(g, 16)
    part = partition_tiles(full, 4)
    wrapped = attach_partition(full, mode="forced", threshold=4)
    assert wrapped.partition.sp_nnz == part.sp_nnz
    assert wrapped.tiles.shape[0] == 0
    _same_tiling(full_tiling(wrapped), full)
