"""Phase ①'s edge-list neighbour max, `neighbor_max_segment`.

The mask folds into the priority at the vertex, so a pass gathers once per
half-edge.  Checked three ways: against a plain numpy per-vertex max on
degenerate inputs, by the gathers in its compiled HLO, and by whole solves
that must match the two-gather formula it replaced bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.spmv as spmv
from repro.api import Solver, SolveOptions
from repro.core.spmv import neighbor_max_segment
from repro.graphs.generators import erdos_renyi, grid2d
from repro.graphs.graph import from_edges, pad_graph

NEG = int(spmv._NEG)
EMPTY = int(np.iinfo(np.int32).min)   # segment_max of a vertex with no edge


def _two_gather_max(g, p, mask):
    """The formula `neighbor_max_segment` replaced: a mask gather and a
    priority gather per half-edge."""
    contrib = jnp.where(g.edge_mask & mask[g.senders], p[g.senders], spmv._NEG)
    return jax.ops.segment_max(
        contrib, g.receivers, num_segments=g.n_nodes + 1
    )[: g.n_nodes]


def _numpy_max(g, p, mask):
    """Per-vertex max over the real half-edges: `NEG` for a neighbour
    outside the mask, the int32 minimum where a vertex has no edge."""
    s = np.asarray(g.senders)[: g.n_edges]
    r = np.asarray(g.receivers)[: g.n_edges]
    out = np.full(g.n_nodes, EMPTY, dtype=np.int64)
    for u, v in zip(s, r):
        out[v] = max(out[v], int(p[u]) if mask[u] else NEG)
    return out


def _random(n, m, seed):
    rng = np.random.default_rng(seed)
    return from_edges(rng.integers(0, n, m), rng.integers(0, n, m), n)


def _case(name, seed):
    rng = np.random.default_rng(1000 + seed)
    n = 57
    g = _random(n, 90, seed)
    p = rng.integers(-(1 << 20), 1 << 20, n).astype(np.int32)
    mask = rng.random(n) < 0.5
    if name == "padded":
        g = pad_graph(g, g.n_edges + 13)
    elif name == "empty_mask":
        mask = np.zeros(n, bool)
    elif name == "full_mask":
        mask = np.ones(n, bool)
    elif name == "isolated":
        # edges only among the first third: the rest have no neighbour
        g = from_edges(rng.integers(0, n // 3, 40), rng.integers(0, n // 3, 40),
                       n, pad_to=128)
    elif name == "ties":
        p = rng.integers(0, 3, n).astype(np.int32)
    return g, p, mask


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize(
    "name", ["random", "padded", "empty_mask", "full_mask", "isolated", "ties"]
)
def test_neighbor_max_segment_matches_numpy(name, seed):
    g, p, mask = _case(name, seed)
    if name in ("padded", "isolated"):
        assert g.e_pad > g.n_edges
    got = np.asarray(jax.jit(neighbor_max_segment)(g, jnp.asarray(p),
                                                   jnp.asarray(mask)))
    np.testing.assert_array_equal(got, _numpy_max(g, p, mask))


@pytest.mark.parametrize("padded", [False, True])
def test_neighbor_max_segment_gathers_once(padded):
    g = _random(64, 120, 0)
    if padded:
        g = pad_graph(g, g.n_edges + 9)
    p = jnp.zeros(g.n_nodes, jnp.int32)
    mask = jnp.ones(g.n_nodes, bool)

    def hlo(fn):
        return jax.jit(fn).lower(g, p, mask).compile().as_text()

    text = hlo(neighbor_max_segment)
    assert text.count(" gather(") == 1
    assert text.count(" scatter(") == 1
    # the count sees the mask gather where there is one
    assert hlo(_two_gather_max).count(" gather(") == 2


def _solve_all(options, graph, seeds):
    solver = Solver(options)
    plan = solver.plan(graph)
    return [solver.solve(plan, key=jax.random.key(s)) for s in seeds]


@pytest.mark.parametrize("engine", [None, "segment"])
@pytest.mark.parametrize("graph", ["lattice", "er"])
def test_solver_same_answers_as_two_gather_formula(graph, engine, monkeypatch):
    g = grid2d(12, 14, seed=3) if graph == "lattice" else erdos_renyi(150, 5.0, seed=4)
    options = SolveOptions() if engine is None else SolveOptions(engine=engine)
    seeds = (0, 7, 2718281828)
    new = _solve_all(options, g, seeds)

    traced = []

    def oracle(*args):
        traced.append(1)
        return _two_gather_max(*args)

    monkeypatch.setattr(spmv, "neighbor_max_segment", oracle)
    old = _solve_all(options, g, seeds)
    assert traced, "the solve never reached the edge-list phase ①"
    for a, b in zip(new, old):
        np.testing.assert_array_equal(a.in_mis, b.in_mis)
        assert a.rounds == b.rounds
        assert a.converged and b.converged
