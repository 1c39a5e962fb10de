"""Serving layer: ingestion parsers, tile-plan cache, block-diagonal
batching (the solo-equivalence contract), and the request-queue service.

The load-bearing property: a packed batch is block-diagonal with per-member
priorities, so every member's solution is BIT-IDENTICAL to a solo `tc_mis`
run of that member with the same key — not merely a valid MIS.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from _hypothesis_compat import given, settings, st

from repro.core import (
    TCMISConfig,
    build_block_tiles,
    cardinality,
    is_valid_mis,
    is_valid_mis_jit,
    tc_mis,
)
from repro.graphs.graph import Graph, from_edges, pad_graph
from repro.graphs.generators import erdos_renyi, grid2d, powerlaw
from repro.serve_mis import (
    GraphParseError,
    MISService,
    PlanCache,
    ServeConfig,
    bucket_for,
    detect_format,
    load_graph,
    pack_batch,
    plan_cache_key,
    request_key,
)
from repro.serve_mis.__main__ import main as serve_main

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
FIX_MTX = os.path.join(FIXTURES, "tiny.mtx")
FIX_EDGES = os.path.join(FIXTURES, "tiny.edges")
FIX_DIMACS = os.path.join(FIXTURES, "tiny.dimacs")


def _hetero_graphs(n_graphs=8, seed=0):
    """A deliberately mixed batch: meshes, hubs, empty and singleton graphs."""
    out = [
        grid2d(4, 5, seed=seed),
        powerlaw(40, avg_deg=3.0, seed=seed),
        erdos_renyi(25, avg_deg=4.0, seed=seed),
        from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), 7),  # no edges
        from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), 1),  # singleton
        load_graph(FIX_DIMACS),
        erdos_renyi(33, avg_deg=2.0, seed=seed + 1),
        grid2d(3, 3, seed=seed),
    ]
    while len(out) < n_graphs:
        out.append(erdos_renyi(10 + len(out), avg_deg=3.0, seed=seed + len(out)))
    return out[:n_graphs]


# --------------------------------------------------------------------------
# io: format detection + parsers
# --------------------------------------------------------------------------

def test_detect_format():
    assert detect_format("a/b.mtx") == "mtx"
    assert detect_format("x.col") == "dimacs"
    assert detect_format("snap.txt") == "edgelist"
    assert detect_format("noext", "%%MatrixMarket matrix coordinate") == "mtx"
    assert detect_format("noext", "p edge 5 3") == "dimacs"
    assert detect_format("noext", "0 1") == "edgelist"
    # unambiguous content markers beat a generic/wrong extension
    assert detect_format("saved_as.txt", "%%MatrixMarket matrix coordinate") == "mtx"
    assert detect_format("saved_as.csv", "c DIMACS comment") == "dimacs"


def test_load_mtx_fixture():
    g = load_graph(FIX_MTX)
    assert g.n_nodes == 12
    assert g.n_edges == 28  # 14 undirected edges, both directions
    with pytest.raises(GraphParseError, match="references vertex"):
        load_graph(FIX_MTX, n_nodes=5)  # override below the file's ids


def test_load_edgelist_fixture():
    g = load_graph(FIX_EDGES)
    assert g.n_nodes == 15
    assert g.n_edges == 2 * 23


def test_load_dimacs_fixture_is_petersen():
    g = load_graph(FIX_DIMACS)
    assert g.n_nodes == 10
    assert g.n_edges == 30
    assert bool(jnp.all(g.degrees() == 3))  # Petersen is 3-regular


def test_parsers_reject_malformed(tmp_path):
    bad_mtx = tmp_path / "bad.mtx"
    bad_mtx.write_text("%%MatrixMarket matrix array real general\n2 2\n1.0\n")
    with pytest.raises(GraphParseError, match="coordinate"):
        load_graph(str(bad_mtx))
    bad_dimacs = tmp_path / "bad.col"
    bad_dimacs.write_text("e 1 2\n")
    with pytest.raises(GraphParseError, match="problem line"):
        load_graph(str(bad_dimacs))
    bad_el = tmp_path / "bad.edges"
    bad_el.write_text("0 1\n2 notanid\n")
    with pytest.raises(GraphParseError, match="line 2"):
        load_graph(str(bad_el))
    float_el = tmp_path / "float.edges"
    float_el.write_text("0 1.9\n")   # must not silently truncate to (0, 1)
    with pytest.raises(GraphParseError, match="non-integer"):
        load_graph(str(float_el))
    empty_el = tmp_path / "empty.edges"
    empty_el.write_text("# a truncated upload, nothing but comments\n")
    with pytest.raises(GraphParseError, match="no edges"):
        load_graph(str(empty_el))
    bad_p = tmp_path / "badp.col"
    bad_p.write_text("p edge ten 15\ne 1 2\n")
    with pytest.raises(GraphParseError, match="non-numeric"):
        load_graph(str(bad_p))


def test_edge_list_n_nodes_override_adds_isolated_tail():
    g = load_graph(FIX_EDGES, n_nodes=20)
    assert g.n_nodes == 20
    assert int(g.degrees()[19]) == 0


# --------------------------------------------------------------------------
# zero-edge / singleton round-tripping (satellite fix)
# --------------------------------------------------------------------------

def test_zero_edge_graph_pad_roundtrip():
    g = from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), 5, pad_to=8)
    assert (g.n_edges, g.e_pad) == (0, 8)
    shrunk = pad_graph(g, 4)          # crashed before the fix
    assert (shrunk.n_edges, shrunk.e_pad) == (0, 4)
    grown = pad_graph(shrunk, 16)
    assert grown.e_pad == 16
    assert bool(jnp.all(grown.senders == 5))  # pure sentinel rows
    assert not bool(jnp.any(grown.edge_mask))


def test_pad_graph_shrink_keeps_real_edges():
    g = from_edges(np.array([0, 1]), np.array([1, 2]), 3, pad_to=64)
    shrunk = pad_graph(g, g.n_edges)
    assert shrunk.e_pad == g.n_edges == 4
    assert bool(jnp.all(shrunk.senders == g.senders[: g.n_edges]))
    with pytest.raises(ValueError, match="real edges"):
        pad_graph(g, 2)


# --------------------------------------------------------------------------
# planner: content-hashed plan cache
# --------------------------------------------------------------------------

def test_plan_cache_memory_and_disk_layers(tmp_path):
    cache = PlanCache(tile_size=8, cache_dir=str(tmp_path))
    g = load_graph(FIX_MTX)
    plan, status = cache.plan(g)
    assert status == "built"
    assert cache.plan(g)[1] == "mem"
    # a *different load of the same content* (fresh arrays) also hits
    assert cache.plan(load_graph(FIX_MTX))[1] == "mem"
    # a fresh process (new cache object, same dir) hits the disk layer
    cache2 = PlanCache(tile_size=8, cache_dir=str(tmp_path))
    plan2, status2 = cache2.plan(g)
    assert status2 == "disk"
    assert plan2.tiled.n_tiles == plan.tiled.n_tiles
    assert bool(jnp.all(plan2.tiled.tiles == plan.tiled.tiles))
    assert cache2.stats == {
        "mem_hits": 0, "disk_hits": 1, "misses": 0, "evicted_stale": 0,
    }


def test_plan_cache_key_depends_on_build_params():
    g = load_graph(FIX_MTX)
    k = plan_cache_key(g, 8, None)
    assert plan_cache_key(g, 16, None) != k
    assert plan_cache_key(g, 8, "rcm") != k
    assert plan_cache_key(load_graph(FIX_MTX), 8, None) == k


def test_plan_cache_memory_layer_is_bounded_lru():
    cache = PlanCache(tile_size=8, max_mem_entries=2)
    gs = [erdos_renyi(10 + i, avg_deg=2.0, seed=i) for i in range(3)]
    for g in gs:
        cache.plan(g)
    assert len(cache._mem) == 2
    assert cache.plan(gs[0])[1] == "built"  # evicted (no disk layer to catch it)
    assert cache.plan(gs[2])[1] == "mem"    # most-recent entries survive


def test_rcm_plan_results_map_back_to_original_ids():
    cache = PlanCache(tile_size=8, reorder="rcm")
    g = grid2d(6, 6, seed=0)
    plan, _ = cache.plan(g)
    assert plan.perm is not None
    res = tc_mis(plan.g, plan.tiled, jax.random.key(0), TCMISConfig(backend="ref"))
    in_mis = plan.to_original(np.asarray(res.in_mis))
    assert is_valid_mis(g, jnp.asarray(in_mis))  # valid in ORIGINAL numbering


# --------------------------------------------------------------------------
# batcher: block-diagonal packing == solo runs, bit for bit
# --------------------------------------------------------------------------

def _solo_vs_packed(graphs, backend, tile_size, heuristic="h3"):
    cache = PlanCache(tile_size=tile_size)
    plans = [cache.plan(g)[0] for g in graphs]
    base = jax.random.key(7)
    keys = [request_key(base, p) for p in plans]
    batch = pack_batch(plans)
    cfg = TCMISConfig(heuristic=heuristic, backend=backend)
    res = tc_mis(
        batch.g, batch.tiled, base, cfg,
        priorities=batch.priorities(plans, keys, heuristic),
        alive0=batch.alive0, col_gate=batch.col_gate,
    )
    assert bool(res.converged)
    slices = batch.unpack(res.in_mis)
    for g, plan, key, got in zip(graphs, plans, keys, slices):
        solo = tc_mis(plan.g, plan.tiled, key, cfg)
        np.testing.assert_array_equal(got, np.asarray(solo.in_mis))
        assert is_valid_mis(plan.g, jnp.asarray(got))
        assert cardinality(jnp.asarray(got)) == cardinality(solo.in_mis)


def test_packed_batch_of_8_matches_solo_oracle_engine():
    _solo_vs_packed(_hetero_graphs(8), backend="tiled_ref", tile_size=16)


def test_packed_batch_of_only_empty_graphs_fused():
    """Zero real tiles in the whole batch: the declared bucket tile count
    must still route every slot through the trivial rule correctly."""
    graphs = [
        from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), n)
        for n in (3, 1, 9)
    ]
    _solo_vs_packed(graphs, backend="fused_pallas", tile_size=8)


def test_packed_batch_of_8_matches_solo_fused_pallas():
    """The acceptance contract: ≥8 heterogeneous graphs, ONE fused_pallas
    dispatch, every member bit-equal to its solo solve on the same engine."""
    tiny = [
        grid2d(3, 4, seed=1),
        erdos_renyi(14, avg_deg=3.0, seed=2),
        from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), 5),
        load_graph(FIX_DIMACS),
        powerlaw(16, avg_deg=3.0, seed=3),
        erdos_renyi(11, avg_deg=2.0, seed=4),
        from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), 1),
        grid2d(2, 6, seed=5),
    ]
    _solo_vs_packed(tiny, backend="fused_pallas", tile_size=8)


def test_bucket_rounding_is_stable_across_similar_batches():
    """Same bucket ⇒ identical STATIC fields on the packed containers —
    the property that makes one compiled program serve both batches (the
    static pytree fields n_edges/n_tiles are jit cache keys)."""
    cache = PlanCache(tile_size=16)
    a = [cache.plan(g)[0] for g in _hetero_graphs(8, seed=0)]
    b = [cache.plan(g)[0] for g in _hetero_graphs(8, seed=3)]
    assert bucket_for(a, 16) == bucket_for(b, 16)
    pa = pack_batch(a)
    pb = pack_batch(b)
    assert pa.n_real_edges != pb.n_real_edges  # genuinely different content
    assert (pa.g.n_nodes, pa.g.n_edges, pa.g.e_pad) == (
        pb.g.n_nodes, pb.g.n_edges, pb.g.e_pad)
    assert (pa.tiled.n_tiles, pa.tiled.n_tiles_pad) == (
        pb.tiled.n_tiles, pb.tiled.n_tiles_pad)
    assert pa.signature() == pb.signature()


def test_packed_batch_rejects_mixed_tile_sizes():
    g = grid2d(3, 3)
    p8 = PlanCache(tile_size=8).plan(g)[0]
    p16 = PlanCache(tile_size=16).plan(g)[0]
    with pytest.raises(ValueError, match="tile_size"):
        pack_batch([p8, p16])


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 5))
def test_property_packed_members_valid_and_match_solo(seed, n_graphs):
    rng = np.random.default_rng(seed)
    graphs = []
    for i in range(n_graphs):
        n = int(rng.integers(1, 40))
        m = int(rng.integers(0, 3 * n))
        graphs.append(from_edges(rng.integers(0, n, m), rng.integers(0, n, m), n))
    _solo_vs_packed(graphs, backend="tiled_ref", tile_size=8)


# --------------------------------------------------------------------------
# engine hook: the static col_gate must be result-neutral
# --------------------------------------------------------------------------

def test_col_gate_all_ones_is_identity():
    g = erdos_renyi(60, avg_deg=4.0, seed=9)
    tiled = build_block_tiles(g, tile_size=16)
    key = jax.random.key(1)
    want = tc_mis(g, tiled, key, TCMISConfig(backend="tiled_ref"))
    got = tc_mis(
        g, tiled, key, TCMISConfig(backend="tiled_ref"),
        col_gate=jnp.ones((tiled.n_block_cols,), jnp.int32),
    )
    assert bool(jnp.all(want.in_mis == got.in_mis))


# --------------------------------------------------------------------------
# validate: the fused jitted post-condition
# --------------------------------------------------------------------------

def test_is_valid_mis_jit_verdicts():
    g = load_graph(FIX_DIMACS)
    res = tc_mis(g, build_block_tiles(g, tile_size=8), jax.random.key(0),
                 TCMISConfig(backend="ref"))
    assert is_valid_mis_jit(g, res.in_mis) == (True, True)
    empty = jnp.zeros((g.n_nodes,), bool)
    assert is_valid_mis_jit(g, empty) == (True, False)   # independent, not maximal
    everything = jnp.ones((g.n_nodes,), bool)
    assert is_valid_mis_jit(g, everything) == (False, True)


def test_is_valid_mis_jit_compiles_per_shape_bucket_not_per_graph():
    """The validator's jit cache must be keyed on pow2 shape buckets, so a
    stream of similar-but-distinct graph sizes shares one compiled program."""
    from repro.core.validate import _fused_checks_masked

    graphs = [erdos_renyi(17 + i, avg_deg=4.0, seed=i) for i in range(3)]
    results = []
    for g in graphs:
        res = tc_mis(g, build_block_tiles(g, tile_size=8), jax.random.key(0),
                     TCMISConfig(backend="ref"))
        results.append((g, res.in_mis))
    if not hasattr(_fused_checks_masked, "_cache_size"):
        pytest.skip("jit cache introspection unavailable on this jax")
    before = _fused_checks_masked._cache_size()
    for g, in_mis in results:
        assert is_valid_mis_jit(g, in_mis) == (True, True)
    grown = _fused_checks_masked._cache_size() - before
    assert grown <= 1  # all three graphs land in one (n_pad, e_pad) bucket


# --------------------------------------------------------------------------
# service: queue → batch → validated responses, cache + compile reuse
# --------------------------------------------------------------------------

def test_service_end_to_end_with_cache_and_compile_reuse(tmp_path):
    svc = MISService(ServeConfig(
        tile_size=16, engine="tiled_ref", max_batch=8,
        cache_dir=str(tmp_path), seed=7,
    ))
    graphs = _hetero_graphs(8)
    for g in graphs:
        svc.submit(g)
    first = svc.drain()
    assert len(first) == 8
    assert all(r.valid for r in first)
    assert all(r.stats["plan_cache"] == "built" for r in first)
    assert all(r.stats["batch_size"] == 8 for r in first)
    assert svc.stats == {"requests": 8, "batches": 1, "compiles": 1}

    # the solo-match guarantee, through the full service path
    cfg = TCMISConfig(heuristic="h3", backend="tiled_ref")
    for g, r in zip(graphs, first):
        plan, status = svc.planner.plan(g)
        assert status == "mem"
        solo = tc_mis(plan.g, plan.tiled, request_key(svc._base_key, plan), cfg)
        assert r.mis_size == cardinality(solo.in_mis)

    # second wave: same graphs ⇒ plan-cache hits, same bucket ⇒ no recompile
    for g in graphs:
        svc.submit(g)
    second = svc.drain()
    assert all(r.stats["plan_cache"] == "mem" for r in second)
    assert all(r.stats["compile"] == "reused" for r in second)
    assert svc.stats["compiles"] == 1
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a.in_mis, b.in_mis)  # content-keyed PRNG

    # third wave: DIFFERENT graphs, same bucket ⇒ still no recompile — the
    # packed statics are bucket-determined, and jax's own jit cache agrees
    for g in _hetero_graphs(8, seed=3):
        svc.submit(g)
    third = svc.drain()
    assert all(r.valid for r in third)
    assert all(r.stats["compile"] == "reused" for r in third)
    if hasattr(svc._solve, "_cache_size"):
        assert svc._solve._cache_size() == 1


def test_service_rejects_unknown_engine_at_construction():
    with pytest.raises(ValueError, match="unknown engine"):
        MISService(ServeConfig(engine="cuda_warp"))


def test_service_partial_batch_and_file_sources():
    # seed=1: h3 under the graph-content request_key derivation finds
    # Petersen's maximum (4) — keeps the quality assertion below strong
    svc = MISService(ServeConfig(tile_size=8, engine="tiled_ref", max_batch=8,
                                 seed=1))
    svc.submit(FIX_MTX)
    svc.submit(FIX_EDGES)
    svc.submit(FIX_DIMACS)
    out = svc.drain()
    assert [r.source for r in out] == [FIX_MTX, FIX_EDGES, FIX_DIMACS]
    assert all(r.valid for r in out)
    assert out[2].mis_size == 4  # Petersen's maximum independent set


def test_unconverged_member_does_not_poison_batchmates():
    """Batch-global `converged` must not flip valid for members whose own
    invariants hold; a cut-off member fails maximality on its own."""
    svc = MISService(ServeConfig(
        tile_size=8, engine="tiled_ref", max_batch=2, max_rounds=1,
    ))
    svc.submit(from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), 1))
    big = erdos_renyi(40, avg_deg=6.0, seed=0)
    svc.submit(big)
    plan, _ = svc.planner.plan(big)
    solo = tc_mis(plan.g, plan.tiled, request_key(svc._base_key, plan),
                  TCMISConfig(backend="tiled_ref"))
    assert int(solo.rounds) > 1, "fixture must need more than one round"
    iso_resp, big_resp = svc.drain()
    assert not iso_resp.converged            # batch-global flag is False...
    assert iso_resp.valid                    # ...but the member is done & valid
    assert not big_resp.maximal and not big_resp.valid


def test_service_reports_per_member_rounds():
    """ROADMAP item: a batch member reports ITS OWN convergence round, not
    the global round count of the batch's slowest member."""
    svc = MISService(ServeConfig(tile_size=8, engine="tiled_ref", max_batch=4))
    fast = from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), 4)
    slow = erdos_renyi(48, avg_deg=6.0, seed=0)
    svc.submit(fast)
    svc.submit(slow)
    r_fast, r_slow = svc.drain()
    plan, _ = svc.planner.plan(slow)
    solo = tc_mis(plan.g, plan.tiled, request_key(svc._base_key, plan),
                  TCMISConfig(backend="tiled_ref"))
    assert int(solo.rounds) > 1, "fixture must need more than one round"
    assert r_slow.rounds == int(solo.rounds)   # == its solo round count
    assert r_fast.rounds == 1                  # edgeless: done in one round
    assert r_fast.stats["bucket"] == r_slow.stats["bucket"]  # same dispatch


def test_cli_survives_bad_request_path(capsys):
    rc = serve_main([
        "--once", "--tile-size", "8", "--engine", "tiled_ref",
        FIX_MTX, "definitely_missing.edges",
    ])
    assert rc == 1  # the bad request counts as a failure...
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    records = [json.loads(l) for l in lines]
    errors = [r for r in records if "error" in r]
    served = [r for r in records if "error" not in r]
    assert len(errors) == 1 and not errors[0]["valid"]
    assert len(served) == 1 and served[0]["valid"]  # ...without killing the stream


def test_cli_once_smoke(tmp_path, capsys):
    rc = serve_main([
        "--once", "--tile-size", "8", "--engine", "tiled_ref",
        "--repeat", "2", "--cache-dir", str(tmp_path),
        FIX_MTX, FIX_EDGES, FIX_DIMACS,
    ])
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert len(lines) == 6
