"""The solve program's named scopes and the spans around it (DESIGN.md §14):
every op of every round body compiles under a `mis.p1|p2|p3` scope, the
HLO parser maps ops to scopes, and the plan build and the dispatch record
their spans — into a `Trace`, and into a profiler capture."""
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.api import Solver, SolveOptions
from repro.core.engine import engine_names, get_engine
from repro.graphs.graph import from_edges
from repro.obs.trace import Trace, hlo_scopes, scope_of

# instructions that move no data: the test does not ask them for a scope
FREE = ("parameter", "tuple", "get-tuple-element", "constant", "bitcast")

TILED = tuple(e for e in engine_names() if get_engine(e).supports_hybrid)
CASES = [("segment", "dense", "off", "segment")] + [
    (engine, frontier, hybrid, phase1)
    for engine in TILED
    for frontier in ("dense", "bitwise")
    for hybrid in ("off", "forced")
    for phase1 in ("segment", "tiled")
]


def _graph():
    """A sparse random graph with one dense cluster: at T=16 and a hybrid
    threshold of 24, the forced partition keeps dense tiles and a sparse
    tail both."""
    rng = np.random.default_rng(5)
    u = rng.integers(0, 160, 320)
    v = rng.integers(0, 160, 320)
    cu, cv = np.triu_indices(24, 1)
    u, v = np.concatenate([u, cu]), np.concatenate([v, cv])
    keep = u != v
    return from_edges(u[keep], v[keep], 160)


def _loop_body(hlo: str):
    """(name, opcode) of each top-level instruction of the round loop's body:
    the entry's `while` whose own op_name lies in no `mis.*` scope."""
    lines = hlo.splitlines()
    entry = next(i for i, line in enumerate(lines) if line.startswith("ENTRY"))
    body = None
    for line in lines[entry:]:
        if " while(" in line and "mis." not in line.split("op_name=")[-1]:
            body = re.search(r"body=%([^\s,]+)", line).group(1)
            break
    assert body is not None
    start = next(i for i, line in enumerate(lines)
                 if line.startswith(f"%{body} "))
    out = []
    for line in lines[start + 1:]:
        if line.startswith("}"):
            break
        m = re.match(r"^\s*(?:ROOT )?%([^\s=]+) = (.*)$", line)
        opcode = re.search(r"(?<![\w.\-])([a-z][a-z0-9\-]*)\(", m.group(2)).group(1)
        out.append((m.group(1), opcode))
    return out


@pytest.mark.parametrize("engine,frontier,hybrid,phase1", CASES)
def test_every_round_body_op_has_a_phase_scope(engine, frontier, hybrid, phase1):
    opts = SolveOptions(
        engine=engine, frontier=frontier, hybrid=hybrid, phase1=phase1,
        storage="bitpack" if frontier == "bitwise" else "int8",
        tile_size=16, hybrid_threshold=24,
    )
    solver = Solver(opts)
    plan = solver.plan(_graph())
    if hybrid == "forced":
        part = plan.tiled.partition
        assert part.n_dense_tiles > 0 and part.n_sparse_tiles > 0
    hlo = solver._jit_single.lower(
        plan.g, plan.tiled, jax.random.key(0)).compile().as_text()
    scopes = hlo_scopes(hlo)
    body = [(n, op) for n, op in _loop_body(hlo) if op not in FREE]
    assert body
    unscoped = [n for n, _ in body if n not in scopes]
    assert not unscoped, unscoped
    assert {scopes[n].split("/")[0] for n, _ in body} <= {
        "mis.p1", "mis.p2", "mis.p3"}
    paths = {scopes[n].split("/")[-1] for n, _ in body}
    if engine != "segment":
        assert "tile" in paths
    if hybrid == "forced":
        assert {"edge", "tile"} <= paths


def test_program_scopes_maps_the_program_solve_runs():
    solver = Solver(SolveOptions(engine="segment"))
    plan = solver.plan(_graph())
    res = solver.solve(plan)
    scopes = solver.program_scopes(plan)
    hlo = solver._jit_single.lower(
        plan.g, plan.tiled, jax.random.key(0)).compile().as_text()
    assert scopes == hlo_scopes(hlo)
    assert {"mis.init", "mis.p1/edge", "mis.p2/edge", "mis.p3",
            "mis.result"} <= set(scopes.values())
    assert res.rounds > 0


HLO = """\
HloModule m, entry_computation_layout={()->f32[8]}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %inner = f32[8]{0} negate(%param_0), metadata={op_name="jit(f)/while/body/mis.p3/neg"}
}

%add_reducer (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(f)/while/body/mis.p2/edge/add"}
}

%nested_body (t: (f32[8])) -> (f32[8]) {
  %t = (f32[8]{0}) parameter(0)
  %g = f32[8]{0} get-tuple-element(%t), index=0
  %k = f32[8]{0} add(%g, %g), metadata={op_name="jit(f)/while/body/mis.p2/tile/pallas/add"}
  ROOT %r = (f32[8]{0}) tuple(%k)
}

%body (p: (f32[8])) -> (f32[8]) {
  %p = (f32[8]{0}) parameter(0)
  %x = f32[8]{0} get-tuple-element(%p), index=0
  %c = f32[] constant(0)
  %bc = f32[8]{0} fusion(%c), kind=kLoop, calls=%fused_computation
  %gather.1 = f32[8]{0} gather(%x, %bc), metadata={op_name="jit(f)/while/body/mis.p1/edge/jit(nbr)/gather"}
  %sum.2 = f32[] reduce(%gather.1, %c), dimensions={0}, to_apply=%add_reducer, metadata={op_name="jit(f)/while/body/mis.p1/edge/reduce_sum"}
  %copy.3 = f32[8]{0} copy(%x)
  %tup = (f32[8]{0}) tuple(%copy.3)
  %while.4 = (f32[8]{0}) while(%tup), condition=%cond, body=%nested_body, metadata={op_name="jit(f)/while/body/mis.p2/tile/while"}
  %w = f32[8]{0} get-tuple-element(%while.4), index=0
  %fusion.5 = f32[8]{0} fusion(%w), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/while/body/mis.p3/neg"}
  %odd.6 = f32[8]{0} abs(%fusion.5), metadata={op_name="jit(f)/mis.p1/edge/mis.p3/abs"}
  %plain.7 = f32[8]{0} abs(%odd.6), metadata={op_name="jit(f)/while/body/abs"}
  ROOT %out = (f32[8]{0}) tuple(%plain.7)
}

%cond (q: (f32[8])) -> pred[] {
  %q = (f32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(true), metadata={op_name="jit(f)/while/lt"}
}

ENTRY %main () -> f32[8] {
  %z = f32[8]{0} constant({0, 0, 0, 0, 0, 0, 0, 0})
  %t0 = (f32[8]{0}) tuple(%z)
  %while.8 = (f32[8]{0}) while(%t0), condition=%cond, body=%body, metadata={op_name="jit(f)/while"}
  %prio = f32[8]{0} negate(%z), metadata={op_name="jit(f)/mis.init/jit(_uniform)/neg"}
  %called = f32[8]{0} call(%prio), to_apply=%nested_body, metadata={op_name="jit(f)/mis.result/call"}
  ROOT %res = f32[8]{0} get-tuple-element(%while.8), index=0
}
"""


def test_hlo_scopes_on_a_fixed_module():
    scopes = hlo_scopes(HLO)
    assert scopes == {
        # a fusion takes its own metadata; its body is not top-level
        "fusion.5": "mis.p3",
        # the innermost `mis.*` scope wins, with its path sub-scope
        "gather.1": "mis.p1/edge",
        "sum.2": "mis.p1/edge",
        "odd.6": "mis.p3",
        "while.4": "mis.p2/tile",
        # a call's computation runs: its ops are top-level
        "k": "mis.p2/tile",
        "prio": "mis.init",
        "called": "mis.result",
        # no op_name: the scope of the consumer, through a tuple ...
        "copy.3": "mis.p2/tile",
        # ... or of the op it feeds
        "bc": "mis.p1/edge",
    }
    # reducer and fusion bodies are nested, plain and loop ops unscoped
    assert not {"add.9", "inner", "plain.7", "while.8", "lt"} & set(scopes)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/while/body/mis.p2/edge/scatter-add", "mis.p2/edge"),
    ("jit(f)/while/body/mis.p2/tile", "mis.p2/tile"),
    ("jit(f)/while/body/mis.p3/or", "mis.p3"),
    ("jit(f)/mis.init/h3_priorities/xor", "mis.init"),
    ("jit(f)/while/body/select_n", None),
])
def test_scope_of(op_name, scope):
    assert scope_of(op_name) == scope


def _plan_opts():
    return SolveOptions(engine="tiled_ref", tile_size=16, hybrid="forced",
                        hybrid_threshold=24)


def test_plan_miss_records_its_stages_inside_solver_plan():
    tr = Trace("plan")
    Solver(_plan_opts()).plan(_graph(), trace=tr)
    spans = {s.name: s for s in tr.spans}
    assert set(spans) == {"solver.plan", "plan.key", "plan.tiles",
                          "plan.tail"}
    outer = spans.pop("solver.plan")
    assert outer.depth == 0 and all(s.depth == 1 for s in spans.values())
    assert sum(s.dur_ms for s in spans.values()) <= outer.dur_ms
    for s in spans.values():
        assert outer.start_ms <= s.start_ms
        assert s.start_ms + s.dur_ms <= outer.start_ms + outer.dur_ms + 1e-6
    assert spans["plan.key"].start_ms + spans["plan.key"].dur_ms \
        <= spans["plan.tiles"].start_ms + 1e-6


def test_plan_hit_records_no_stages():
    solver = Solver(_plan_opts())
    g = _graph()
    solver.plan(g)
    tr = Trace("hit")
    solver.plan(g, trace=tr)
    assert [s.name for s in tr.spans] == ["solver.plan"]
    assert solver.plans.stats["mem_hits"] == 1


def test_profiler_capture_holds_the_solve_spans(tmp_path):
    from jax.profiler import ProfileData

    solver = Solver(SolveOptions(engine="segment"))
    plan = solver.plan(_graph())
    solver.solve(plan)
    jax.profiler.start_trace(str(tmp_path))
    try:
        solver.solve(plan)   # untraced: the spans are annotations alone
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    host = {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    assert {"solver.solve", "solver.plan", "solver.execute",
            "solver.fetch"} <= host


def test_ladder_rungs_and_compaction_carry_their_scopes():
    """On a graph whose edge list ladders (DESIGN.md §18) every rung's body
    op lies under a phase scope or `mis.compact` (its live mask), and the
    compaction between rungs is named `mis.compact` too."""
    from repro.graphs.generators import grid2d

    solver = Solver(SolveOptions(engine="segment"))
    plan = solver.plan(grid2d(100, 100, seed=2))
    scopes = solver.program_scopes(plan)
    assert "mis.compact" in set(scopes.values())
    hlo = solver._jit_single.lower(
        plan.g, plan.tiled, jax.random.key(0)).compile().as_text()
    lines = hlo.splitlines()
    bodies = {re.search(r"body=%([^\s,]+)", line).group(1) for line in lines
              if " while(" in line and "mis." not in line.split("op_name=")[-1]}
    assert len(bodies) >= 2
    for body in bodies:
        start = next(i for i, line in enumerate(lines)
                     if line.startswith(f"%{body} "))
        for line in lines[start + 1:]:
            if line.startswith("}"):
                break
            m = re.match(r"^\s*(?:ROOT )?%([^\s=]+) = (.*)$", line)
            op = re.search(r"(?<![\w.\-])([a-z][a-z0-9\-]*)\(", m.group(2))
            if op.group(1) in FREE:
                continue
            assert scopes[m.group(1)].split("/")[0] in {
                "mis.p1", "mis.p2", "mis.p3", "mis.compact"}, line
