"""The COO tail's capacity (`core/tiling.tail_capacity`): real entries
rounded up to a multiple of 1024, a power of two below that.

The capacity only decides how many sentinel entries follow the real ones.
Sentinels scatter into the dropped segment `n_padded`, so a tail padded to
the next power of two must answer bit for bit as the tail a plan builds,
on both frontiers and through a repair, and the plan cache must rebuild a
tail stored at the old capacity.  That both partition routes give
`tail_capacity`-long tails is checked in `test_edge_build.py`.
"""
import dataclasses
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import PlanCache, SolveOptions, Solver
from repro.api.plan import _PLAN_VERSION
from repro.core.tiling import (
    TAIL_QUANTUM,
    next_pow2,
    partitioned_tiling,
    tail_capacity,
)
from repro.dyngraph import random_delta
from repro.dyngraph.repair import dirty_mask, repair_mis
from repro.graphs.generators import erdos_renyi, powerlaw, rmat

ALL_SPARSE = 10**6   # a cut no T=16 tile reaches: every tile in the tail


@pytest.mark.parametrize(
    "n", [0, 7, 8, 1000, 1023, 1024, 1025, 2048, 1_048_577, 3_083_796,
          4_194_304, 10_139_906])
def test_tail_capacity(n):
    cap = tail_capacity(n)
    assert cap >= n and cap >= 8
    if n < TAIL_QUANTUM:
        assert cap == next_pow2(max(n, 8))   # small tails keep their shapes
    else:
        assert cap % TAIL_QUANTUM == 0 and cap - n < TAIL_QUANTUM
    if n > 1 << 20:
        assert (cap - n) / n < 1e-3


def _pow2_twin(tiled):
    """`tiled` with its tail re-padded to the next power of two, sentinels
    and all, as plans before `tail_capacity` built it."""
    part = tiled.partition
    n, cap = part.sp_nnz, next_pow2(max(part.sp_nnz, 8))
    assert cap > part.sp_rows.shape[0]      # the twin really is longer

    def pad(a):
        return np.concatenate([np.asarray(a)[:n],
                               np.full(cap - n, tiled.n_padded, np.int32)])

    return partitioned_tiling(
        part.dense, (pad(part.sp_rows), pad(part.sp_cols), n),
        part.threshold, part.n_sparse_tiles)


_FRONTIERS = [("int8", "dense"), ("bitpack", "bitwise")]


@pytest.mark.parametrize("storage,frontier", _FRONTIERS)
@pytest.mark.parametrize("engine,thr", [
    ("fused_pallas", ALL_SPARSE), ("tiled_ref", 4),
])
@pytest.mark.parametrize("graph", [
    lambda: erdos_renyi(3000, avg_deg=6.0, seed=4),
    lambda: powerlaw(1500, avg_deg=6.0, seed=11),
], ids=["er", "powerlaw"])
def test_a_pow2_tail_answers_as_the_planned_tail(graph, engine, thr,
                                                 storage, frontier):
    g = graph()
    s = Solver(SolveOptions(engine=engine, storage=storage, frontier=frontier,
                            tile_size=16, hybrid="forced",
                            hybrid_threshold=thr))
    plan = s.plan(g)
    twin = dataclasses.replace(plan, tiled=_pow2_twin(plan.tiled))
    for k in range(3):
        a = s.solve(plan, key=jax.random.key(k))
        b = s.solve(twin, key=jax.random.key(k))
        np.testing.assert_array_equal(a.in_mis, b.in_mis)
        assert a.rounds == b.rounds


@pytest.mark.parametrize("storage,frontier", _FRONTIERS)
def test_a_pow2_tail_repairs_as_the_planned_tail(storage, frontier):
    g = rmat(9, edge_factor=16, seed=1)
    opts = SolveOptions(engine="fused_pallas", storage=storage,
                        frontier=frontier, tile_size=16, hybrid="forced",
                        hybrid_threshold=ALL_SPARSE, repair="incremental")
    s = Solver(opts)
    prior = s.solve(g)
    delta = random_delta(g, n_add=20, n_remove=20, seed=3)
    res = s.update(prior, delta)
    assert res.stats["repair"] == "incremental"
    plan2 = res.plan
    dirty = jnp.asarray(dirty_mask(plan2.n_nodes, delta.touched()))
    seed_set = jnp.asarray(plan2.to_plan_ids(prior.in_mis).astype(bool))
    run = jax.jit(lambda tiled: repair_mis(
        plan2.g, tiled, jax.random.key(opts.seed), opts, seed_set, dirty))
    a, b = run(plan2.tiled), run(_pow2_twin(plan2.tiled))
    np.testing.assert_array_equal(np.asarray(a.in_mis), np.asarray(b.in_mis))
    assert int(a.rounds) == int(b.rounds) == res.rounds
    np.testing.assert_array_equal(np.asarray(a.in_mis)[: plan2.n_nodes],
                                  res.in_mis_plan)


def test_a_v4_plan_cache_entry_is_rebuilt_with_the_new_capacity(tmp_path):
    assert _PLAN_VERSION == 5
    g = erdos_renyi(3000, avg_deg=6.0, seed=4)
    kw = dict(cache_dir=str(tmp_path), tile_size=16, storage="bitpack",
              hybrid="forced", hybrid_threshold=ALL_SPARSE)
    plan, status = PlanCache(**kw).plan(g)
    assert status == "built"
    path = PlanCache(**kw)._path(plan.key)
    # rewrite the entry as a v4 writer left it: power-of-two tail
    twin = _pow2_twin(plan.tiled).partition
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    arrays["meta"][6] = 4
    arrays["sp_rows"] = np.asarray(twin.sp_rows)
    arrays["sp_cols"] = np.asarray(twin.sp_cols)
    with open(path, "wb") as f:
        np.savez(f, **arrays)

    fresh = PlanCache(**kw)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        rebuilt, status = fresh.plan(g)
    assert status == "built" and fresh.stats["evicted_stale"] == 1
    assert any("format v4" in str(w.message) for w in caught)
    part = rebuilt.tiled.partition
    assert part.sp_rows.shape == (tail_capacity(part.sp_nnz),)
    np.testing.assert_array_equal(np.asarray(part.sp_rows),
                                  np.asarray(plan.tiled.partition.sp_rows))
    assert os.path.exists(path)
    with np.load(path) as z:
        assert int(z["meta"][6]) == _PLAN_VERSION
        assert z["sp_rows"].shape == (tail_capacity(part.sp_nnz),)
