"""The `repro.api` front door: Plan/SolveOptions/Solver.

Acceptance contract of the API redesign: for each routing target (local,
batched, sharded) `Solver.solve` returns a bit-identical `in_mis` to the
pre-redesign direct call on the same graph/seed; `solve_many` never builds a
bucket for nothing/a singleton; and the legacy entry points warn but keep
working.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_multidevice
from repro.api import (
    Plan,
    PlanCache,
    Solver,
    SolveOptions,
    choose_tile_size,
    fit_tile_size,
)
from repro.core import (
    TCMISConfig,
    build_block_tiles,
    engine_names,
    get_engine,
    is_valid_mis,
    tc_mis,
)
from repro.graphs.generators import erdos_renyi, grid2d, powerlaw
from repro.graphs.graph import from_edges

ALL_ENGINES = ("segment", "tiled_ref", "tiled_pallas", "fused_pallas")


def _legacy(fn, *args, **kwargs):
    """Call a deprecated shim without polluting the warning log."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return fn(*args, **kwargs)


def _hetero(n=6, seed=0):
    return [
        grid2d(3 + seed, 4),
        powerlaw(40 + seed, avg_deg=3.0, seed=seed + 1),
        erdos_renyi(25 + seed, avg_deg=4.0, seed=seed + 2),
        from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), 7),
        erdos_renyi(33 + seed, avg_deg=2.0, seed=seed + 3),
        from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), 1),
    ][:n]


# --------------------------------------------------------------------------
# routing target: local — bit-identical to the direct tc_mis call
# --------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ALL_ENGINES)
def test_solve_local_bit_identical_to_direct_call(engine):
    g = erdos_renyi(90, avg_deg=5.0, seed=3)
    res = Solver(SolveOptions(engine=engine, tile_size=16, seed=0)).solve(g)
    direct = _legacy(
        tc_mis, g, build_block_tiles(g, tile_size=16), jax.random.key(0),
        TCMISConfig(heuristic="h3", backend=engine),
    )
    assert res.placement == "local"
    np.testing.assert_array_equal(res.in_mis, np.asarray(direct.in_mis))
    assert res.rounds == int(direct.rounds)
    assert res.converged == bool(direct.converged)


def test_solve_accepts_plan_and_respects_explicit_key():
    g = powerlaw(64, avg_deg=4.0, seed=1)
    solver = Solver(SolveOptions(engine="tiled_ref", tile_size=8, seed=5))
    plan = solver.plan(g)
    res = solver.solve(plan, key=jax.random.key(42))
    direct = _legacy(
        tc_mis, plan.g, plan.tiled, jax.random.key(42),
        TCMISConfig(backend="tiled_ref"),
    )
    np.testing.assert_array_equal(res.in_mis, np.asarray(direct.in_mis))


# --------------------------------------------------------------------------
# routing target: batched — members bit-identical to solo runs, own rounds
# --------------------------------------------------------------------------

def test_solve_many_members_bit_identical_to_solo_with_own_rounds():
    graphs = _hetero(6)
    solver = Solver(SolveOptions(engine="tiled_ref", tile_size=8))
    results = solver.solve_many(graphs)
    assert [r.placement for r in results] == ["batched"] * 6
    assert len({r.stats["bucket"] for r in results}) == 1  # ONE dispatch
    for g, res in zip(graphs, results):
        solo = _legacy(
            tc_mis, res.plan.g, res.plan.tiled, solver.request_key(res.plan),
            TCMISConfig(heuristic="h3", backend="tiled_ref"),
        )
        np.testing.assert_array_equal(res.in_mis, np.asarray(solo.in_mis))
        # the satellite contract: each member reports its OWN convergence
        # round, not the batch-slowest
        assert res.rounds == int(solo.rounds)
        assert is_valid_mis(g, jnp.asarray(res.in_mis))
    assert len({r.rounds for r in results}) > 1, "fixture should span rounds"


def test_solve_many_empty_and_singleton_build_no_bucket():
    solver = Solver(SolveOptions(engine="tiled_ref", tile_size=8))
    assert solver.solve_many([]) == []
    assert solver.stats["batches"] == 0

    # singleton: routed through the single-graph path (no bucket), and the
    # batcher's hard cases — zero-edge and 1-vertex graphs — must survive it
    for g in (
        erdos_renyi(20, avg_deg=3.0, seed=0),
        from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), 5),
        from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), 1),
    ):
        [res] = solver.solve_many([g])
        assert res.placement == "local"
        assert "bucket" not in res.stats
        assert is_valid_mis(g, jnp.asarray(res.in_mis))
        assert res.converged
    assert solver.stats["batches"] == 0

    # the singleton result equals the same member inside a real batch
    g = erdos_renyi(20, avg_deg=3.0, seed=0)
    [single] = solver.solve_many([g])
    batched = solver.solve_many([g, grid2d(4, 4)])[0]
    np.testing.assert_array_equal(single.in_mis, batched.in_mis)
    assert single.rounds == batched.rounds


def test_solve_many_honours_custom_keys_despite_priority_cache():
    """Regression: the content-keyed priority cache must be bypassed when
    the caller supplies explicit keys, or custom-key members would silently
    get the cached default-key priorities."""
    g = erdos_renyi(40, avg_deg=4.0, seed=1)
    h = erdos_renyi(36, avg_deg=4.0, seed=2)
    solver = Solver(SolveOptions(engine="tiled_ref", tile_size=8))
    solver.solve_many([g, h])   # warms the priority cache under default keys
    k1, k2 = jax.random.key(101), jax.random.key(202)
    custom = solver.solve_many([g, h], keys=[k1, k2])
    for res, key in zip(custom, (k1, k2)):
        solo = _legacy(
            tc_mis, res.plan.g, res.plan.tiled, key,
            TCMISConfig(heuristic="h3", backend="tiled_ref"),
        )
        np.testing.assert_array_equal(res.in_mis, np.asarray(solo.in_mis))
    # ...and the default-key path still reuses its cache afterwards
    again = solver.solve_many([g, h])
    for res in again:
        solo = _legacy(
            tc_mis, res.plan.g, res.plan.tiled, solver.request_key(res.plan),
            TCMISConfig(heuristic="h3", backend="tiled_ref"),
        )
        np.testing.assert_array_equal(res.in_mis, np.asarray(solo.in_mis))


def test_solve_many_keeps_input_order_and_compile_reuse():
    solver = Solver(SolveOptions(engine="tiled_ref", tile_size=8))
    graphs = _hetero(4, seed=0)
    first = solver.solve_many(graphs)
    assert all(r.stats["compile"] == "compiled" for r in first)
    second = solver.solve_many(graphs)
    assert all(r.stats["compile"] == "reused" for r in second)
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a.in_mis, b.in_mis)
    assert [r.plan.n_nodes for r in first] == [g.n_nodes for g in graphs]
    assert solver.stats["batches"] == 2
    if hasattr(solver._jit_packed, "_cache_size"):
        assert solver._jit_packed._cache_size() == 1  # same bucket, one program


# --------------------------------------------------------------------------
# routing target: sharded — bit-identical to the direct shard_map call
# --------------------------------------------------------------------------

def test_solve_sharded_bit_identical_to_direct_call():
    out = run_multidevice("""
        import jax, numpy as np
        from repro.api import Solver, SolveOptions
        from repro.core import (build_block_tiles, shard_tiled,
                                build_distributed_mis, DistConfig,
                                make_priorities, is_valid_mis)
        from repro.graphs.generators import powerlaw

        g = powerlaw(2000, avg_deg=5.0, seed=2)
        solver = Solver(SolveOptions(heuristic="h3", tile_size=64,
                                     placement="sharded", seed=0))
        plan = solver.plan(g)
        assert solver.route(plan) == "sharded"
        res = solver.solve(g)
        assert res.placement == "sharded"
        assert res.stats["n_shards"] == 8
        assert is_valid_mis(g, jax.numpy.asarray(res.in_mis))

        # pre-redesign direct call, same graph/seed
        tiled = build_block_tiles(g, tile_size=64)
        sharded = shard_tiled(tiled, n_shards=8)
        mesh = jax.make_mesh((8,), ("shard",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        pri = make_priorities("h3", jax.random.key(0), g.n_nodes, g.degrees())
        direct = build_distributed_mis(sharded, mesh, DistConfig())(pri)
        assert bool(np.all(res.in_mis == np.asarray(direct.in_mis)[:g.n_nodes]))
        assert res.rounds == int(direct.rounds)

        # the auto policy routes big graphs to shards, small ones locally
        auto = Solver(SolveOptions(heuristic="h3", tile_size=64,
                                   placement="auto", shard_threshold=1024))
        assert auto.route(plan) == "sharded"
        small = auto.plan(powerlaw(100, avg_deg=3.0, seed=0))
        assert auto.route(small) == "local"
        auto_res = auto.solve(g)
        assert bool(np.all(auto_res.in_mis == res.in_mis))
        print("API_SHARDED_OK")
    """)
    assert "API_SHARDED_OK" in out


# --------------------------------------------------------------------------
# Plan + auto-T policy
# --------------------------------------------------------------------------

def test_plan_build_through_cache_and_auto_tile_size():
    g = erdos_renyi(50, avg_deg=3.0, seed=0)
    cache = PlanCache(tile_size=8)
    a = Plan.build(g, cache=cache)
    b = Plan.build(g, cache=cache)
    assert a is b                       # content hit, zero work
    assert cache.stats["mem_hits"] == 1
    assert Plan.build(a) is a           # plans pass through

    auto = Plan.build(g)                # no cache: SAME auto-T, same key —
    assert auto.tile_size == choose_tile_size(g.n_nodes, g.n_edges)
    assert auto.key == a.key            # the cache never changes the plan
    assert auto.tile_size == a.tile_size

    explicit = Plan.build(g, tile_size=8, cache=cache)
    assert explicit.tile_size == 8
    assert explicit.key != a.key        # T is part of the content key

    # budget policy: shrinking the budget shrinks T, floor at 16
    big_n, big_e = 1 << 20, 8 << 20
    assert choose_tile_size(big_n, big_e, budget=1 << 40) == 128
    assert choose_tile_size(big_n, big_e, budget=1 << 20) == 16
    # tiny graphs never take tiles wider than their padded range
    assert choose_tile_size(20, 40) <= 32
    assert fit_tile_size(lambda T: T * T, budget=64 * 64) == 64


def test_solve_options_validation_and_engine_failfast():
    with pytest.raises(ValueError, match="placement"):
        SolveOptions(placement="cloud")
    with pytest.raises(ValueError, match="unknown engine"):
        Solver(SolveOptions(engine="cuda_warp"))


def test_rcm_plans_return_original_ids():
    g = grid2d(6, 6, seed=0)
    solver = Solver(SolveOptions(
        engine="tiled_ref", tile_size=8, reorder="rcm",
    ))
    res = solver.solve(g)
    assert res.plan.perm is not None
    assert is_valid_mis(g, jnp.asarray(res.in_mis))   # ORIGINAL numbering
    # in_mis_plan maps back into the permuted plan space
    assert is_valid_mis(res.plan.g, jnp.asarray(res.in_mis_plan))


# --------------------------------------------------------------------------
# deprecation surface
# --------------------------------------------------------------------------

def test_legacy_entry_points_emit_deprecation_warnings():
    g = erdos_renyi(30, avg_deg=3.0, seed=0)
    tiled = build_block_tiles(g, tile_size=8)
    with pytest.warns(DeprecationWarning, match="repro.api"):
        tc_mis(g, tiled, jax.random.key(0), TCMISConfig(backend="tiled_ref"))
    with pytest.warns(DeprecationWarning, match="tiled_ref"):
        get_engine("ref")
    with pytest.warns(DeprecationWarning, match="tiled_pallas"):
        get_engine("pallas")


def test_legacy_shims_match_the_front_door():
    g = powerlaw(60, avg_deg=4.0, seed=7)
    res = Solver(SolveOptions(engine="fused_pallas", tile_size=16)).solve(g)
    shim = _legacy(
        tc_mis, g, build_block_tiles(g, tile_size=16), jax.random.key(0),
        TCMISConfig(backend="fused_pallas"),
    )
    np.testing.assert_array_equal(res.in_mis, np.asarray(shim.in_mis))
