"""Telemetry's zero-cost contract (DESIGN.md §14), read off the traced
program: with `telemetry=False` the solve holds no value of the round
buffer's shape and its round loop carries exactly the `MISRoundState`
leaves; with `telemetry=True` the buffer rides in that carry.  One case per
engine × route (dense or packed frontier, whole tiling or hybrid
partition), on a graph too small for the compaction ladder and on one
whose streams ladder (DESIGN.md §18): there every rung loop carries the
state, the buffer only with telemetry on, and the live masks only on the
rungs that compact.  Traces only — nothing compiles."""
import jax
import numpy as np
import pytest
from jax.extend.core import Jaxpr

from repro.api import Solver, SolveOptions
from repro.core.engine import MISRoundState, engine_names, get_engine
from repro.core.tc_mis import _setup, _tc_mis_impl
from repro.graphs.graph import from_edges
from repro.obs import TELEMETRY_COLS

MAX_ROUNDS = 37   # a row count no other value of the program shares

TILED = tuple(e for e in engine_names() if get_engine(e).supports_hybrid)
CASES = [(e, "dense", "off") for e in engine_names()] + [
    (engine, frontier, hybrid)
    for frontier, hybrid in (("bitwise", "off"), ("dense", "forced"),
                             ("bitwise", "forced"))
    for engine in TILED
]


def _graph(n=160, m=320):
    """Sparse random edges plus one dense cluster: at T=16 and a hybrid
    threshold of 24, a forced partition keeps dense tiles and a sparse
    tail both."""
    rng = np.random.default_rng(7)
    u = rng.integers(0, n, m)
    v = rng.integers(0, n, m)
    cu, cv = np.triu_indices(24, 1)
    u, v = np.concatenate([u, cu]), np.concatenate([v, cv])
    keep = u != v
    return from_edges(u[keep], v[keep], n)


def _laddered_graph():
    """The same shape with streams well past the ladder's floor."""
    return _graph(n=6000, m=12_000)


def _eqns(jaxpr):
    """Every equation of `jaxpr` and of the jaxprs nested in its params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                inner = getattr(sub, "jaxpr", sub)
                if isinstance(inner, Jaxpr):
                    yield from _eqns(inner)


def _sig(aval):
    return tuple(aval.shape), str(aval.dtype)


def _trace_loops(engine, frontier, hybrid, telemetry, graph):
    """(shapes of every value in the traced solve, each round loop's carry,
    the `MISRoundState` leaves) as (shape, dtype) signatures."""
    opts = SolveOptions(
        engine=engine, frontier=frontier, hybrid=hybrid, phase1="tiled",
        storage="bitpack" if frontier == "bitwise" else "int8",
        tile_size=16, hybrid_threshold=24, placement="local",
        max_rounds=MAX_ROUNDS, telemetry=telemetry,
    )
    plan = Solver(opts).plan(graph)
    part = plan.tiled.partition
    if hybrid == "forced":
        assert part.n_dense_tiles > 0 and part.n_sparse_tiles > 0
    else:
        assert part is None
    args = (plan.g, plan.tiled, jax.random.key(0))
    state = jax.eval_shape(lambda g, t, k: _setup(g, t, k, opts)[3], *args)
    jaxpr = jax.make_jaxpr(lambda g, t, k: _tc_mis_impl(g, t, k, opts))(*args).jaxpr
    loops = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    carries = []
    for loop in loops:
        n_consts = loop.params["cond_nconsts"] + loop.params["body_nconsts"]
        carries.append([_sig(v.aval) for v in loop.invars[n_consts:]])
    shapes = {
        tuple(v.aval.shape)
        for eqn in _eqns(jaxpr)
        for v in (*eqn.invars, *eqn.outvars)
        if hasattr(v.aval, "shape")
    }
    return shapes, carries, [_sig(x) for x in jax.tree.leaves(state)]


def _trace(engine, frontier, hybrid, telemetry):
    """`_trace_loops` on the small graph, whose solve is one round loop."""
    shapes, carries, state = _trace_loops(
        engine, frontier, hybrid, telemetry, _graph())
    assert len(carries) == 1
    return shapes, carries[0], state


@pytest.mark.parametrize("engine,frontier,hybrid", CASES)
def test_telemetry_off_traces_no_buffer(engine, frontier, hybrid):
    buf = ((MAX_ROUNDS, TELEMETRY_COLS), "int32")
    shapes, carry, state = _trace(engine, frontier, hybrid, telemetry=False)
    assert len(state) == len(MISRoundState._fields)
    assert carry == state
    assert buf[0] not in shapes

    shapes, carry, state_on = _trace(engine, frontier, hybrid, telemetry=True)
    assert state_on == state
    assert carry == state + [buf]
    assert buf[0] in shapes   # the walk reaches the values it looks through


@pytest.mark.parametrize("engine,frontier,hybrid", CASES)
def test_every_rung_loop_keeps_the_contract(engine, frontier, hybrid):
    """A route that reads a per-half-edge stream runs one loop per rung;
    each carries the state first, then the buffer only with telemetry on,
    then the live masks — (capacity,) bools — only on a rung that
    compacts, which every rung but the last does."""
    buf = ((MAX_ROUNDS, TELEMETRY_COLS), "int32")
    streams = engine == "segment" or hybrid == "forced"
    graph = _laddered_graph()
    for telemetry in (False, True):
        shapes, carries, state = _trace_loops(
            engine, frontier, hybrid, telemetry, graph)
        assert (len(carries) > 1) == streams
        extra = [buf] if telemetry else []
        for k, carry in enumerate(carries):
            assert carry[: len(state) + len(extra)] == state + extra
            masks = carry[len(state) + len(extra):]
            assert bool(masks) == (k + 1 < len(carries))
            assert all(len(m[0]) == 1 and m[1] == "bool" for m in masks)
        assert (buf[0] in shapes) == telemetry
