"""Telemetry's zero-cost contract (DESIGN.md §14), read off the traced
program: with `telemetry=False` the solve holds no value of the round
buffer's shape and its round loop carries exactly the `MISRoundState`
leaves; with `telemetry=True` the buffer rides in that carry.  One case per
engine × route (dense or packed frontier, whole tiling or hybrid
partition).  Traces only — nothing compiles."""
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


def _graph():
    """Sparse random edges plus one dense cluster: at T=16 and a hybrid
    threshold of 24, a forced partition keeps dense tiles and a sparse
    tail both."""
    rng = np.random.default_rng(7)
    u = rng.integers(0, 160, 320)
    v = rng.integers(0, 160, 320)
    cu, cv = np.triu_indices(24, 1)
    u, v = np.concatenate([u, cu]), np.concatenate([v, cv])
    keep = u != v
    return from_edges(u[keep], v[keep], 160)


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


def _trace(engine, frontier, hybrid, telemetry):
    """(shapes of every value in the traced solve, the round loop's carry,
    the `MISRoundState` leaves) as (shape, dtype) signatures."""
    opts = SolveOptions(
        engine=engine, frontier=frontier, hybrid=hybrid, phase1="tiled",
        storage="bitpack" if frontier == "bitwise" else "int8",
        tile_size=16, hybrid_threshold=24, placement="local",
        max_rounds=MAX_ROUNDS, telemetry=telemetry,
    )
    plan = Solver(opts).plan(_graph())
    part = plan.tiled.partition
    if hybrid == "forced":
        assert part.n_dense_tiles > 0 and part.n_sparse_tiles > 0
    else:
        assert part is None
    args = (plan.g, plan.tiled, jax.random.key(0))
    state = jax.eval_shape(lambda g, t, k: _setup(g, t, k, opts)[3], *args)
    jaxpr = jax.make_jaxpr(lambda g, t, k: _tc_mis_impl(g, t, k, opts))(*args).jaxpr
    loops = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    assert len(loops) == 1
    n_consts = loops[0].params["cond_nconsts"] + loops[0].params["body_nconsts"]
    carry = [_sig(v.aval) for v in loops[0].invars[n_consts:]]
    shapes = {
        tuple(v.aval.shape)
        for eqn in _eqns(jaxpr)
        for v in (*eqn.invars, *eqn.outvars)
        if hasattr(v.aval, "shape")
    }
    return shapes, carry, [_sig(x) for x in jax.tree.leaves(state)]


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
