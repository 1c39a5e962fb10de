"""Per-tile hybrid execution (DESIGN.md §16): nnz classification, compacted
dense/sparse routing, and its plumbing through every front-door route.

The load-bearing contract is BIT-IDENTITY: routing is an execution-plan
choice, so `hybrid="forced"` must return exactly the dense-only solution for
every engine × storage × frontier combination — partitioning never changes
what is computed, only where.  On top of that: partition invariants (the two
compacted lists tile the stored nonzeros exactly), plan-cache v3 persistence
(policy re-attached on load, off-mode keys byte-identical to v2), the auto
gate, delta-driven reclassification (tiles crossing the nnz threshold in
either direction), and the batched / repair routes.
"""
import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.api import Plan, PlanCache, SolveOptions, Solver, patch_plan
from repro.api.plan import (
    _PLAN_VERSION,
    build_plan,
    plan_cache_key,
    resolve_hybrid_threshold,
)
from repro.core.tiling import (
    attach_partition,
    build_block_tiles,
    full_tiling,
    partition_tiles,
    tile_nnz,
)
from repro.core.validate import is_valid_mis_jit
from repro.dyngraph import EdgeDelta, apply_delta, apply_graph_delta
from repro.graphs.generators import erdos_renyi, powerlaw
from repro.perf import hybrid_density_threshold
from repro.serve_mis.batcher import pack_batch


def _mis(g, **kw):
    return np.asarray(Solver(options=SolveOptions(**kw)).solve(g).in_mis)


# ---------------------------------------------------------------------------
# bit-identity: forced routing == dense-only, everywhere
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["tiled_ref", "tiled_pallas", "fused_pallas"])
@pytest.mark.parametrize("storage,frontier", [
    ("int8", "dense"), ("bitpack", "dense"), ("bitpack", "bitwise"),
])
def test_hybrid_bit_identity(engine, storage, frontier):
    g = powerlaw(384, avg_deg=6.0, seed=11)
    kw = dict(engine=engine, storage=storage, frontier=frontier, tile_size=32)
    ref = _mis(g, hybrid="off", **kw)
    for thr in (2, 64):       # mixed partition and (nearly) all-sparse
        got = _mis(g, hybrid="forced", hybrid_threshold=thr, **kw)
        np.testing.assert_array_equal(got, ref)


def test_hybrid_all_sparse_and_all_dense_extremes():
    # threshold 1: every non-empty tile is dense; huge threshold: all sparse
    g = erdos_renyi(300, avg_deg=5.0, seed=3)
    ref = _mis(g, engine="tiled_ref", tile_size=32, hybrid="off")
    for thr in (1, 10**6):
        got = _mis(g, engine="tiled_ref", tile_size=32,
                   hybrid="forced", hybrid_threshold=thr)
        np.testing.assert_array_equal(got, ref)


def test_segment_engine_never_partitions():
    g = erdos_renyi(200, avg_deg=4.0, seed=1)
    s = Solver(options=SolveOptions(engine="segment", hybrid="forced",
                                    hybrid_threshold=4))
    assert s.plan(g).tiled.partition is None
    np.testing.assert_array_equal(
        np.asarray(s.solve(g).in_mis), _mis(g, engine="segment", hybrid="off"))


# ---------------------------------------------------------------------------
# partition invariants
# ---------------------------------------------------------------------------


def test_partition_tiles_exactly_covers_stored_nonzeros():
    g = powerlaw(256, avg_deg=8.0, seed=7)
    tiled = build_block_tiles(g, tile_size=32)
    nnz = np.asarray(tile_nnz(tiled))[: tiled.n_tiles]
    thr = 16
    part = partition_tiles(tiled, thr)

    # counts: every stored tile with nnz >= thr is dense, 0 < nnz < thr sparse
    assert part.threshold == thr
    assert part.n_dense_tiles == int((nnz >= thr).sum())
    assert part.n_sparse_tiles == int(((nnz > 0) & (nnz < thr)).sum())
    assert part.sp_nnz == int(nnz[(nnz > 0) & (nnz < thr)].sum())

    # dense sub-tiling holds exactly the dense tiles' payload
    dn = np.asarray(tile_nnz(part.dense))[: part.dense.n_tiles]
    assert part.dense.n_tiles == part.n_dense_tiles
    assert (dn >= thr).all()

    # COO tail: real pairs scatter inside the graph, padding is the sentinel
    sp_r = np.asarray(part.sp_rows)
    sp_c = np.asarray(part.sp_cols)
    n_pad = tiled.n_padded
    real = sp_r[: part.sp_nnz]
    assert (real < n_pad).all() and (sp_c[: part.sp_nnz] < n_pad).all()
    assert (sp_r[part.sp_nnz:] == n_pad).all()
    assert (sp_c[part.sp_nnz:] == n_pad).all()

    # dense payload nnz + COO nnz == every stored nonzero
    assert int(dn.sum()) + part.sp_nnz == int(nnz.sum())


def test_partition_deterministic_and_padding_excluded():
    g = erdos_renyi(200, avg_deg=6.0, seed=5)
    tiled = build_block_tiles(g, tile_size=32)
    p1 = partition_tiles(tiled, 8)
    p2 = partition_tiles(tiled, 8)
    np.testing.assert_array_equal(np.asarray(p1.sp_rows), np.asarray(p2.sp_rows))
    np.testing.assert_array_equal(
        np.asarray(p1.dense.tiles), np.asarray(p2.dense.tiles))
    # padding tiles are all-zero -> in neither list
    stored = tiled.tiles.shape[0]
    assert p1.n_dense_tiles + p1.n_sparse_tiles <= tiled.n_tiles <= stored


# ---------------------------------------------------------------------------
# options / threshold resolution / auto gate
# ---------------------------------------------------------------------------


def test_invalid_hybrid_options_rejected():
    with pytest.raises(ValueError, match="hybrid"):
        SolveOptions(hybrid="sometimes")
    with pytest.raises(ValueError, match="hybrid_threshold"):
        SolveOptions(hybrid_threshold=0)


def test_threshold_resolution_prefers_override():
    assert resolve_hybrid_threshold(64, "int8", 7) == 7
    auto = resolve_hybrid_threshold(64, "int8", None)
    assert auto == hybrid_density_threshold(64, "int8")
    assert auto > 0


def test_auto_gate_skips_tiny_tilings():
    # a tiling with a handful of tiles never routes hybrid under "auto"
    g = erdos_renyi(64, avg_deg=4.0, seed=2)
    tiled = build_block_tiles(g, tile_size=32)
    assert attach_partition(tiled, mode="auto", threshold=8).partition is None
    # "forced" overrides the gate on the same tiling
    assert attach_partition(
        tiled, mode="forced", threshold=8).partition is not None


# ---------------------------------------------------------------------------
# plan cache v3
# ---------------------------------------------------------------------------


def test_off_mode_cache_key_is_byte_identical_to_legacy():
    g = erdos_renyi(100, avg_deg=4.0, seed=1)
    legacy = plan_cache_key(g, 32, "none", "int8")
    assert plan_cache_key(
        g, 32, "none", "int8", hybrid="off", hybrid_threshold=0) == legacy
    hy = plan_cache_key(
        g, 32, "none", "int8", hybrid="forced", hybrid_threshold=8)
    assert hy != legacy
    assert plan_cache_key(
        g, 32, "none", "int8", hybrid="forced", hybrid_threshold=9) != hy
    assert plan_cache_key(
        g, 32, "none", "int8", hybrid="forced", hybrid_threshold=8) == hy


def test_plan_cache_v3_roundtrip_reattaches_partition(tmp_path):
    g = powerlaw(300, avg_deg=6.0, seed=4)
    cache = PlanCache(cache_dir=str(tmp_path), tile_size=32,
                      hybrid="forced", hybrid_threshold=8)
    pa, st_a = cache.plan(g)
    assert st_a == "built" and pa.tiled.partition is not None

    fresh = PlanCache(cache_dir=str(tmp_path), tile_size=32,
                      hybrid="forced", hybrid_threshold=8)
    pb, st_b = fresh.plan(g)
    assert st_b == "disk"
    assert (pb.hybrid, pb.hybrid_threshold) == ("forced", 8)
    part_a, part_b = pa.tiled.partition, pb.tiled.partition
    assert part_b is not None and part_b.threshold == 8
    np.testing.assert_array_equal(
        np.asarray(part_a.dense.tiles), np.asarray(part_b.dense.tiles))
    np.testing.assert_array_equal(
        np.asarray(part_a.sp_rows), np.asarray(part_b.sp_rows))
    np.testing.assert_array_equal(
        np.asarray(part_a.sp_cols), np.asarray(part_b.sp_cols))


def test_plan_cache_off_entries_unaffected_by_hybrid_misses(tmp_path):
    # a live current-version off-mode entry must survive a hybrid-mode miss
    g = erdos_renyi(120, avg_deg=4.0, seed=6)
    off = PlanCache(cache_dir=str(tmp_path), tile_size=32)
    off.plan(g)
    _, st = off.plan(g)
    assert st == "mem"
    hy = PlanCache(cache_dir=str(tmp_path), tile_size=32,
                   hybrid="forced", hybrid_threshold=4)
    hy.plan(g)      # miss on the hybrid key; may probe the legacy path
    again = PlanCache(cache_dir=str(tmp_path), tile_size=32)
    _, st2 = again.plan(g)
    assert st2 == "disk"        # off entry still on disk, not evicted


# ---------------------------------------------------------------------------
# dyngraph: delta-driven reclassification
# ---------------------------------------------------------------------------


def test_apply_delta_reclassifies_across_threshold():
    # tile (0,0) starts below the threshold; the delta pushes it above
    T, thr = 8, 6
    g = erdos_renyi(64, avg_deg=3.0, seed=9)
    tiled = attach_partition(
        build_block_tiles(g, tile_size=T), mode="forced", threshold=thr)
    # a partitioned tiling holds no full tile list: count tile 0 on the
    # full list its partition rebuilds
    nnz0 = int(np.asarray(tile_nnz(full_tiling(tiled)))[0])

    # add intra-tile-0 edges until its nnz (2 per undirected edge) crosses
    have = set()
    sn = np.asarray(g.senders)[: g.n_edges]
    rc = np.asarray(g.receivers)[: g.n_edges]
    for a, b in zip(sn, rc):
        have.add((min(int(a), int(b)), max(int(a), int(b))))
    adds = [(u, v) for u in range(T) for v in range(u + 1, T)
            if (u, v) not in have][: thr]
    delta = EdgeDelta.make([u for u, _ in adds], [v for _, v in adds], [], [])
    out = apply_delta(tiled, delta)

    nnz1 = int(np.asarray(tile_nnz(full_tiling(out)))[0])
    assert nnz0 < thr <= nnz1        # the crossing actually happened
    assert out.partition is not None
    assert out.partition.threshold == thr
    assert out.partition.n_dense_tiles == tiled.partition.n_dense_tiles + 1

    # bit-exact with partitioning a from-scratch rebuild of the mutated graph
    oracle = partition_tiles(
        build_block_tiles(apply_graph_delta(g, delta), tile_size=T), thr)
    np.testing.assert_array_equal(
        np.asarray(out.partition.dense.tiles), np.asarray(oracle.dense.tiles))
    np.testing.assert_array_equal(
        np.asarray(out.partition.sp_rows), np.asarray(oracle.sp_rows))

    # and back down: the inverse delta restores the original classification
    back = apply_delta(out, delta.inverse())
    assert back.partition.n_dense_tiles == tiled.partition.n_dense_tiles
    np.testing.assert_array_equal(
        np.asarray(back.partition.sp_rows), np.asarray(tiled.partition.sp_rows))


def _absent_edge(g):
    have = set()
    sn = np.asarray(g.senders)[: g.n_edges]
    rc = np.asarray(g.receivers)[: g.n_edges]
    for a, b in zip(sn, rc):
        have.add((min(int(a), int(b)), max(int(a), int(b))))
    for u in range(g.n_nodes):
        for v in range(u + 1, g.n_nodes):
            if (u, v) not in have:
                return u, v
    raise AssertionError("complete graph")


def test_patch_plan_keeps_hybrid_policy():
    g = powerlaw(300, avg_deg=6.0, seed=12)
    plan = build_plan(g, 32, None, "k0", hybrid="forced", hybrid_threshold=8)
    u, v = _absent_edge(g)
    patched = patch_plan(plan, EdgeDelta.make([u], [v], [], []))
    assert patched.tiled.partition is not None
    assert patched.tiled.partition.threshold == 8
    assert (patched.hybrid, patched.hybrid_threshold) == ("forced", 8)


def test_update_route_repairs_hybrid_bit_identically():
    # incremental repair warm-starts from the prior solution, so the oracle
    # is the SAME update under hybrid="off" — routing must not change it
    g = powerlaw(400, avg_deg=6.0, seed=13)
    u, v = _absent_edge(g)
    delta = EdgeDelta.make([u], [v], [], [])
    results = {}
    for mode in ("off", "forced"):
        s = Solver(options=SolveOptions(engine="tiled_ref", tile_size=32,
                                        hybrid=mode, hybrid_threshold=8))
        r1 = s.update(s.solve(g), delta)
        results[mode] = np.asarray(r1.in_mis)
        assert bool(is_valid_mis_jit(
            apply_graph_delta(g, delta), r1.in_mis))
    np.testing.assert_array_equal(results["forced"], results["off"])


# ---------------------------------------------------------------------------
# batched route
# ---------------------------------------------------------------------------


def test_batched_hybrid_bit_identical_and_signed():
    graphs = [powerlaw(200, avg_deg=5.0, seed=i) for i in range(3)]
    runs = {}
    for mode in ("off", "forced"):
        s = Solver(options=SolveOptions(engine="tiled_ref", tile_size=32,
                                        hybrid=mode, hybrid_threshold=8))
        runs[mode] = [np.asarray(r.in_mis) for r in s.solve_many(graphs)]
    for a, b in zip(runs["off"], runs["forced"]):
        np.testing.assert_array_equal(a, b)

    s = Solver(options=SolveOptions(engine="tiled_ref", tile_size=32,
                                    hybrid="forced", hybrid_threshold=8))
    plans = [s.plan(g) for g in graphs]
    pb = pack_batch(plans)
    assert pb.tiled.partition is not None
    assert ".h8:" in pb.signature()

    s_off = Solver(options=SolveOptions(engine="tiled_ref", tile_size=32,
                                        hybrid="off"))
    pb_off = pack_batch([s_off.plan(g) for g in graphs])
    assert pb_off.tiled.partition is None
    assert ".h" not in pb_off.signature()
    assert pb.signature() != pb_off.signature()


def test_batched_mixed_modes_falls_back_dense():
    graphs = [erdos_renyi(150, avg_deg=4.0, seed=i) for i in range(2)]
    s_h = Solver(options=SolveOptions(engine="tiled_ref", tile_size=32,
                                      hybrid="forced", hybrid_threshold=8))
    s_o = Solver(options=SolveOptions(engine="tiled_ref", tile_size=32,
                                      hybrid="off"))
    plans = [s_h.plan(graphs[0]), s_o.plan(graphs[1])]
    pb = pack_batch(plans)
    assert pb.tiled.partition is None       # incoherent pack -> dense-only


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------


def test_telemetry_reports_routing_split():
    g = powerlaw(300, avg_deg=6.0, seed=14)
    s = Solver(options=SolveOptions(engine="tiled_ref", tile_size=32,
                                    hybrid="forced", hybrid_threshold=8,
                                    telemetry=True))
    res = s.solve(g)
    part = s.plan(g).tiled.partition
    rt = res.telemetry
    assert rt.rounds == res.rounds
    assert len(rt.tiles_sparse) == rt.rounds
    n_dense_pad = int(part.dense.tiles.shape[0])
    for dense_n, sparse_n in zip(rt.tiles_dense, rt.tiles_sparse):
        assert sparse_n == part.n_sparse_tiles
        assert 0 <= dense_n <= n_dense_pad

    ref = _mis(g, engine="tiled_ref", tile_size=32, hybrid="off")
    np.testing.assert_array_equal(np.asarray(res.in_mis), ref)
