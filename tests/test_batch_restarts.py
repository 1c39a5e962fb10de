"""Best-of-K restarts of a fixed graph set through `Solver.solve_many`: the
pack is built once and cached, H3's member priorities are built inside the
packed program, and every member keeps its solo set and rounds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api.solver as solver_mod
from repro.api import SolveOptions, Solver
from repro.core.heuristics import h3_priorities
from repro.graphs.graph import from_edges
from repro.obs import Trace
from repro.serve_mis.batcher import pack_batch


def er_set(n_graphs, seed, n_min=40, n_max=90, p=0.15, sizes=None):
    """G(n, p) graphs, n uniform in [n_min, n_max] (or drawn from `sizes`):
    a small ER-[700-800]."""
    rng = np.random.default_rng(seed)
    ns = rng.integers(n_min, n_max + 1, n_graphs) if sizes is None \
        else rng.choice(sizes, n_graphs)
    out = []
    for n in ns:
        iu, iv = np.triu_indices(int(n), 1)
        keep = rng.random(iu.shape[0]) < p
        out.append(from_edges(iu[keep], iv[keep], int(n)))
    return out


def member_keys(k, m):
    return jax.vmap(lambda j: jax.random.fold_in(jax.random.key(k), j))(
        jnp.arange(m))


_solo_h3 = jax.jit(h3_priorities, static_argnums=1)
SIZES = (1, 5, 8, 13, 31, 64, 77, 90)   # most not a multiple of T = 8


@pytest.mark.parametrize("n_graphs", range(2, 10))
def test_packed_h3_equals_each_members_h3_priorities(n_graphs):
    rng = np.random.default_rng(n_graphs)
    graphs = er_set(n_graphs, n_graphs, sizes=SIZES)
    solver = Solver(SolveOptions(tile_size=8, hybrid="off"))
    plans = [solver.plan(g) for g in graphs]
    keys = [jax.random.key(int(k)) for k in rng.integers(0, 2**31, n_graphs)]
    batch = pack_batch(plans)
    pri = batch.priorities(plans, keys, "h3")
    sel, res = np.asarray(pri.select), np.asarray(pri.resolve)
    covered = np.zeros(sel.shape[0], bool)
    for plan, key, off in zip(plans, keys, batch.offsets):
        solo = _solo_h3(key, plan.n_nodes, plan.g.degrees())
        np.testing.assert_array_equal(sel[off:off + plan.n_nodes], solo.select)
        np.testing.assert_array_equal(res[off:off + plan.n_nodes], solo.resolve)
        covered[off:off + plan.n_nodes] = True
    neg = int(np.iinfo(np.int32).min) // 2
    assert np.all(sel[~covered] == neg) and np.all(res[~covered] == neg)


@pytest.mark.parametrize("tile_size", [8, 16])
def test_restarts_give_every_member_its_solo_segment_solve(tile_size):
    graphs = er_set(6, 1)
    solver = Solver(SolveOptions(tile_size=tile_size))
    plans = [solver.plan(g) for g in graphs]
    parted = [p.tiled.partition is not None for p in plans]
    # T = 8 partitions every member (a hybrid pack), T = 16 some (dense)
    assert all(parted) if tile_size == 8 else any(parted) and not all(parted)
    seg = Solver(SolveOptions(engine="segment", tile_size=tile_size))
    for call, k in enumerate((11, 2**31 - 7)):
        keys = member_keys(k, len(graphs))
        trace = Trace()
        got = solver.solve_many(plans, keys=keys, trace=trace)
        built = [s.name for s in trace.spans].count("pack.structure")
        assert built == (1 if call == 0 else 0)
        for j, (g, r) in enumerate(zip(graphs, got)):
            solo = seg.solve(g, key=keys[j])
            np.testing.assert_array_equal(r.in_mis, solo.in_mis)
            assert r.rounds == solo.rounds
    m = solver.metrics.snapshot()
    assert (m["solver.pack_cache.misses"], m["solver.pack_cache.hits"]) == (1, 1)
    assert 0 < m["batch.edge_fill"] <= 1 and 0 < m["batch.tile_fill"] <= 1


@pytest.mark.parametrize("bound", ["entries", "bytes"])
def test_pack_cache_evicts_at_its_bound(monkeypatch, bound):
    sets = [er_set(2, s, n_min=20, n_max=30) for s in (1, 2, 3)]
    solver = Solver(SolveOptions(tile_size=8, engine="segment"))
    plans = [[solver.plan(g) for g in gs] for gs in sets]
    one = pack_batch(plans[0]).device_bytes
    if bound == "entries":
        monkeypatch.setattr(solver_mod, "_PACK_CACHE_ENTRIES", 2)
    else:
        monkeypatch.setattr(solver_mod, "_PACK_CACHE_BYTES", 2 * one + one // 2)
    for ps in plans:
        solver.solve_many(ps)
    assert len(solver._packs) == 2
    misses = lambda: solver.metrics.snapshot()["solver.pack_cache.misses"]
    solver.solve_many(plans[2])          # the newest is kept
    assert misses() == 3
    solver.solve_many(plans[0])          # the oldest went first
    assert misses() == 4


def test_batch_program_scopes_tag_the_packed_program():
    graphs = er_set(3, 4)
    solver = Solver(SolveOptions(tile_size=16, engine="segment"))
    scopes = solver.batch_program_scopes(graphs)
    assert "mis.priorities" in set(scopes.values())
    assert {"mis.p1/edge", "mis.p3"} <= set(scopes.values())
    with pytest.raises(ValueError, match="one local batch"):
        solver.batch_program_scopes(graphs[:1])
