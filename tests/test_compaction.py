"""The compaction ladder (DESIGN.md §18) against a numpy transcription of
the H3 round: the same MIS, rounds and telemetry rows, bit for bit, while
the rounds after the first stream only the live half-edges.

The toys are a road lattice and an R-MAT graph, each long enough that one
solve passes at least two rung boundaries.  The routes cover every stream
a round body reads: the edge list (segment engine; the dense route's
phase ①), the hybrid tail with dense tiles beside it (two ladders), the
tail of a partition with no dense tile (one shared list), the tail alone
(tiled phase ①) and the tail on packed frontiers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Solver, SolveOptions
from repro.core import compaction
from repro.core.compaction import LADDER_FLOOR, LADDER_STEP, ladder_capacities
from repro.core.heuristics import Priorities, make_priorities
from repro.core.tc_mis import _tc_mis_impl
from repro.dyngraph import EdgeDelta
from repro.graphs.generators import grid2d, rmat
from repro.graphs.graph import from_edges

NEG = -(1 << 30)
INT_MIN = np.iinfo(np.int32).min
KEYS = (3, 12345)

ROUTES = {
    "segment": dict(engine="segment"),
    "dense": dict(engine="tiled_ref", hybrid="off", storage="int8"),
    "hybrid": dict(engine="tiled_ref", hybrid="forced", hybrid_threshold=24,
                   storage="int8"),
    "shared": dict(engine="tiled_ref", hybrid="forced",
                   hybrid_threshold=1 << 20, storage="int8"),
    "tail": dict(engine="tiled_ref", hybrid="forced", hybrid_threshold=24,
                 storage="int8", phase1="tiled"),
    "tail_bits": dict(engine="tiled_ref", hybrid="forced", hybrid_threshold=24,
                      storage="bitpack", frontier="bitwise", phase1="tiled"),
}


TOYS = {"road": grid2d(380, 380, seed=1), "rmat": rmat(14, 32, seed=1)}


def _opts(route, **kw):
    return SolveOptions(tile_size=16, placement="local", **ROUTES[route], **kw)


# -- the oracle ---------------------------------------------------------------

def _nbr_max(s, r, n, p, mask):
    """① as the program computes it: a masked sender gives NEG, a vertex
    with no half-edge the int32 minimum."""
    out = np.full(n, INT_MIN, np.int64)
    np.maximum.at(out, r, np.where(mask[s], p[s], NEG))
    return out


def oracle(g, pri, alive=None, in_mis=None, max_rounds=1024):
    """The H3 round in numpy on `g`'s edge list, from an optional warm
    state.  Returns (in_mis, rounds, rows of (alive, frontier, selected),
    the alive mask at the start of each round)."""
    n = g.n_nodes
    s = np.asarray(g.senders)[: g.n_edges]
    r = np.asarray(g.receivers)[: g.n_edges]
    sel = np.asarray(pri.select, np.int64)
    res = None if pri.resolve is None else np.asarray(pri.resolve, np.int64)
    alive = np.ones(n, bool) if alive is None else np.asarray(alive, bool).copy()
    in_mis = np.zeros(n, bool) if in_mis is None else np.asarray(in_mis, bool).copy()
    rows, states = [], []
    while alive.any() and len(rows) < max_rounds:
        states.append(alive.copy())
        if res is None:
            cand = alive & (sel > _nbr_max(s, r, n, sel, alive))
        else:
            pending = alive & (sel >= _nbr_max(s, r, n, sel, alive))
            cand = pending & (res > _nbr_max(s, r, n, res, pending))
        hit = np.zeros(n, bool)
        hit[r[cand[s]]] = True
        rows.append((int(alive.sum()), int(cand.sum()), int(cand.sum())))
        in_mis |= cand
        alive &= ~cand & ~hit
    return in_mis, len(rows), rows, states


def _priorities(plan, key):
    g = plan.g
    return make_priorities("h3", jax.random.key(key), g.n_nodes, g.degrees())


def _stream_widths(plan, opts, states):
    """The width each round streams, by the ladder's rule, from the live
    half-edges the oracle leaves after each round."""
    from repro.core.engine import EngineContext, get_engine

    engine = get_engine(opts.engine)
    ctx = EngineContext(g=plan.g, tiled=plan.tiled, cfg=opts)
    ladder = compaction.plan_ladder(engine, ctx)
    full = {k: np.asarray(v[0]) for k, v in compaction.full_streams(ctx).items()}
    full_dst = {k: np.asarray(v[1]) for k, v in compaction.full_streams(ctx).items()}

    def width(k):
        caps = ladder.rungs[k]
        w = 0
        for name in engine.streams(ctx):
            if name in caps:
                w += caps[name]
            else:   # the shared tail reads the compacted edge list below rung 0
                w += full[name].shape[0] if k == 0 else caps[compaction.EDGES]
        return w

    def live(alive, name):
        a = np.append(alive, np.zeros(plan.tiled.n_padded + 1 - alive.size, bool))
        return int((a[full[name]] & a[full_dst[name]]).sum())

    k, out = 0, []
    for i, alive in enumerate(states):
        if i:   # the rung loop left after round i-1 if every stream fits
            while k + 1 < len(ladder.rungs) and all(
                live(alive, name) <= ladder.rungs[k + 1][name]
                for name in ladder.rungs[k]
                if ladder.rungs[k + 1][name] < ladder.rungs[k][name]
            ):
                k += 1
        out.append(width(k))
    return out


# -- the ladder's shape ------------------------------------------------------

def test_ladder_capacities_of_the_cells():
    road = ladder_capacities(3_083_796)
    kron = ladder_capacities(10_139_906)
    assert road == (3_083_796, 97_280, 3_072)
    assert kron == (10_139_906, 317_440, 10_240)
    for caps in (road, kron):
        assert caps[-1] <= LADDER_FLOOR < caps[-2]
        assert all(c % 1024 == 0 for c in caps[1:])
        assert all(LADDER_STEP * b >= a for a, b in zip(caps, caps[1:]))
    assert ladder_capacities(LADDER_FLOOR) == (LADDER_FLOOR,)
    assert ladder_capacities(0) == (0,)


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_plan_ladder_streams_per_route(route):
    from repro.core.engine import EngineContext, get_engine

    opts = _opts(route)
    plan = Solver(opts).plan(TOYS["road"])
    ctx = EngineContext(g=plan.g, tiled=plan.tiled, cfg=opts)
    engine = get_engine(opts.engine)
    ladder = compaction.plan_ladder(engine, ctx)
    want = {"segment": ("edges",), "dense": ("edges",),
            "hybrid": ("edges", "tail"), "shared": ("edges", "tail"),
            "tail": ("tail",), "tail_bits": ("tail",)}[route]
    assert engine.streams(ctx) == want
    assert ladder.shared == (route == "shared")
    assert tuple(ladder.rungs[0]) == (("edges",) if ladder.shared else want)
    if route == "hybrid":
        assert plan.tiled.partition.n_dense_tiles > 0
    # the lattice's tail beside its dense tiles is one rung short; on the
    # R-MAT toy every route passes two rung boundaries
    assert len(ladder.rungs) >= (2 if want == ("tail",) else 3)
    other = Solver(opts).plan(TOYS["rmat"])
    ctx = EngineContext(g=other.g, tiled=other.tiled, cfg=opts)
    assert len(compaction.plan_ladder(engine, ctx).rungs) >= 3


# -- answers, rounds and rows against the oracle -------------------------------

@pytest.mark.parametrize("toy", sorted(TOYS))
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_in_mis_and_rounds_match_the_oracle(toy, route):
    solver = Solver(_opts(route))
    plan = solver.plan(TOYS[toy])
    for key in KEYS:
        res = solver.solve(plan, key=jax.random.key(key))
        in_mis, rounds, _, _ = oracle(plan.g, _priorities(plan, key))
        np.testing.assert_array_equal(res.in_mis_plan, in_mis)
        assert res.rounds == rounds and res.converged
        assert res.stats["stream_share"] < 0.5


@pytest.mark.parametrize("toy,route", [
    ("road", "segment"), ("road", "hybrid"), ("road", "shared"),
    ("road", "tail_bits"), ("rmat", "dense"), ("rmat", "shared"),
])
def test_telemetry_rows_match_the_oracle(toy, route, monkeypatch):
    opts = _opts(route, telemetry=True)
    solver = Solver(opts)
    plan = solver.plan(TOYS[toy])
    key = KEYS[1]
    res = solver.solve(plan, key=jax.random.key(key))
    in_mis, rounds, rows, states = oracle(plan.g, _priorities(plan, key))
    rt = res.telemetry
    np.testing.assert_array_equal(res.in_mis_plan, in_mis)
    assert rt.rounds == rounds
    assert list(zip(rt.alive, rt.frontier, rt.selected)) == rows
    widths = _stream_widths(plan, opts, states)
    assert rt.stream == widths
    assert widths[-1] < widths[0]
    share = sum(widths) / (rounds * widths[0])
    assert res.stats["stream_share"] == pytest.approx(share, rel=1e-6)

    # the tile columns are those of the single loop on the full streams
    monkeypatch.setattr(compaction, "LADDER_FLOOR", 1 << 40)
    flat = Solver(opts).solve(plan, key=jax.random.key(key)).telemetry
    assert flat.stream == [widths[0]] * rounds
    for col in ("alive", "frontier", "selected", "tiles_skipped",
                "tiles_dense", "tiles_sparse"):
        assert getattr(rt, col) == getattr(flat, col), col


def test_two_rung_boundaries_on_both_toys():
    """Each toy's solve ends two or more rungs below the full stream."""
    for toy in TOYS:
        opts = _opts("segment", telemetry=True)
        solver = Solver(opts)
        plan = solver.plan(TOYS[toy])
        rt = solver.solve(plan, key=jax.random.key(KEYS[0])).telemetry
        caps = ladder_capacities(plan.g.e_pad)
        assert rt.stream[0] == caps[0]
        assert rt.stream[-1] <= caps[2], (toy, rt.stream, caps)


# -- warm starts and batches ---------------------------------------------------

@pytest.mark.parametrize("route", ["segment", "shared", "tail_bits"])
def test_warm_start_matches_the_oracle(route):
    """`alive0` + `in_mis0`, as `dyngraph.repair` hands them over: a prior
    MIS with a dirty patch taken out; the masks are computed before rung 0."""
    opts = _opts(route)
    plan = Solver(opts).plan(TOYS["road"])
    pri = _priorities(plan, KEYS[0])
    prior, _, _, _ = oracle(plan.g, pri)
    n = plan.g.n_nodes
    dirty = np.zeros(n, bool)
    dirty[: n // 3] = True
    in_mis0 = prior & ~dirty
    s = np.asarray(plan.g.senders)[: plan.g.n_edges]
    r = np.asarray(plan.g.receivers)[: plan.g.n_edges]
    covered = np.zeros(n, bool)
    covered[r[in_mis0[s]]] = True
    alive0 = ~in_mis0 & ~covered
    want, rounds, _, _ = oracle(plan.g, pri, alive0, in_mis0)
    res = jax.jit(lambda g, t: _tc_mis_impl(
        g, t, jax.random.key(0), opts, priorities=pri,
        alive0=jnp.asarray(alive0), in_mis0=jnp.asarray(in_mis0),
    ))(plan.g, plan.tiled)
    np.testing.assert_array_equal(np.asarray(res.in_mis), want)
    assert int(res.rounds) == rounds > 0
    assert float(res.stream_share) < 0.5


@pytest.mark.parametrize("seam", ["batch", "repair"])
def test_only_a_warm_start_masks_before_rung_0(seam, monkeypatch):
    """The batcher's `alive0` kills only padding slots, which hold no edge,
    so its rung 0 starts from the all-true mask and masks once per rung
    body; the repair seam (`in_mis0` given) masks once more, before rung 0."""
    opts = _opts("segment")
    plan = Solver(opts).plan(TOYS["road"])
    n = plan.g.n_nodes
    warm = dict(in_mis0=jnp.zeros((n,), bool)) if seam == "repair" else {}
    calls = []
    live_masks = compaction.live_masks
    monkeypatch.setattr(compaction, "live_masks",
                        lambda *a: calls.append(a) or live_masks(*a))
    jax.make_jaxpr(lambda g, t: _tc_mis_impl(
        g, t, jax.random.key(0), opts,
        alive0=jnp.arange(n) < n - 7, **warm,
    ))(plan.g, plan.tiled)
    rung_bodies = len(ladder_capacities(plan.g.e_pad)) - 1
    assert rung_bodies >= 1
    assert len(calls) == rung_bodies + (seam == "repair")


def test_repair_through_the_solver_matches_the_single_loop(monkeypatch):
    opts = SolveOptions(engine="segment", repair="incremental")
    n = TOYS["road"].n_nodes
    delta = EdgeDelta.make(add_src=[0, 5], add_dst=[n - 1, 7000],
                           rem_src=[0], rem_dst=[1])
    solver = Solver(opts)
    got = solver.update(solver.solve(TOYS["road"]), delta)
    assert got.stats["repair"] == "incremental"
    assert got.stats["stream_share"] < 1.0
    monkeypatch.setattr(compaction, "LADDER_FLOOR", 1 << 40)
    flat = Solver(opts)
    want = flat.update(flat.solve(TOYS["road"]), delta)
    np.testing.assert_array_equal(got.in_mis, want.in_mis)
    assert got.rounds == want.rounds
    assert want.stats["stream_share"] == 1.0


def test_solve_many_members_match_the_oracle():
    solver = Solver(SolveOptions(engine="segment", tile_size=16))
    graphs = [grid2d(120, 120, seed=s) for s in (1, 2, 3)]
    results = solver.solve_many(graphs)
    assert {r.placement for r in results} == {"batched"}
    assert results[0].stats["stream_share"] < 0.5
    for res in results:
        pri = make_priorities("h3", solver.request_key(res.plan),
                              res.plan.g.n_nodes, res.plan.g.degrees())
        in_mis, rounds, _, _ = oracle(res.plan.g, pri)
        np.testing.assert_array_equal(res.in_mis_plan, in_mis)
        assert res.rounds == rounds


# -- the share and its gauge ---------------------------------------------------

def test_stream_share_is_one_below_the_floor():
    rng = np.random.default_rng(0)
    g = from_edges(rng.integers(0, 300, 900), rng.integers(0, 300, 900), 300)
    assert g.e_pad <= LADDER_FLOOR
    for route in ("segment", "shared"):
        solver = Solver(_opts(route))
        res = solver.solve(g)
        assert res.stats["stream_share"] == 1.0
        assert solver.metrics.gauge("solve.stream_share").value == 1.0


def test_stream_share_gauge_reads_the_last_solve():
    solver = Solver(_opts("segment"))
    res = solver.solve(TOYS["rmat"])
    share = res.stats["stream_share"]
    assert 0.0 < share < 0.5
    assert solver.metrics.gauge("solve.stream_share").value == share


# -- a priority at or below the mask value keeps the full stream ---------------

def test_low_priority_vertex_keeps_the_rung(monkeypatch):
    """A hub whose resolve rank is at the mask value NEG outlives all its
    neighbours: on the full stream their dead senders give NEG, so the hub
    is never a candidate and the solve runs to `max_rounds`.  Dropped
    half-edges would leave it no neighbour at all; the rung holds while
    such a vertex lives, so the answers stay those of the single loop."""
    leaves = 6000
    hub, leaf = 0, np.arange(1, leaves + 1)
    block = leaf + leaves
    n = 2 * leaves + 1
    g = from_edges(np.concatenate([np.full(leaves, hub), leaf]),
                   np.concatenate([leaf, block]), n)
    select = np.zeros(n, np.int32)
    select[leaf], select[block] = 1, 2     # blockers beat leaves beat the hub
    resolve = -np.arange(n, dtype=np.int32)
    resolve[hub] = NEG
    pri = Priorities(select=jnp.asarray(select), resolve=jnp.asarray(resolve))
    opts = _opts("segment", max_rounds=6)
    plan = Solver(opts).plan(g)
    assert plan.g.e_pad > LADDER_FLOOR

    def run():
        return jax.jit(lambda g, t: _tc_mis_impl(
            g, t, jax.random.key(0), opts, priorities=pri))(plan.g, plan.tiled)

    got = run()
    in_mis, rounds, _, _ = oracle(plan.g, pri, max_rounds=6)
    np.testing.assert_array_equal(np.asarray(got.in_mis), in_mis)
    assert int(got.rounds) == rounds == 6 and not bool(got.converged)
    monkeypatch.setattr(compaction, "LADDER_FLOOR", 1 << 40)
    want = run()
    np.testing.assert_array_equal(np.asarray(got.in_mis), np.asarray(want.in_mis))
