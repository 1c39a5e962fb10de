"""The comparison that decides `correct` catches each fault a cell can have.

Each run goes past the harness's look for a chip and drives the rest of a
run of the cell at toy size, on the CPU, with the program's answers broken
underneath (`bench/control.py`'s `Faulty`): one vertex flipped where the
answer is produced, and a solve that returns the previous answer (the state
left unchanged).  The program on its uniform random priorities, which give
valid but smaller sets, fails the configuration's size floor.  A sound run
of the same cell comes out correct.  The cell
answers one request per step, so it has no batch to leave half of, and on
one chip no exchange between chips to leave out.
"""
import pytest

import bench_testkit
from benchlib.spec import load_cell, load_module

control = load_module(bench_testkit.BENCH / "control.py")

CASES = {"road-solve": [None, "flip", "stale", "uniform"]}


@pytest.fixture(scope="module")
def cells(tmp_path_factory):
    """One toy copy, and one program instance per cell, reused by every
    case so the CPU compiles each program once."""
    root = bench_testkit.toy_copy(tmp_path_factory.mktemp("faults"))
    out = {}
    for name in CASES:
        cell = load_cell(name, root)
        own = load_module(cell.bench / "systems" /
                          f"{cell.config['system']['entry']}.py").System
        out[name] = (cell, own, {})
    return out


def _system(own, kept, fault):
    def make(config, workload):
        uniform = fault == "uniform"
        if uniform not in kept:
            kept[uniform] = own(control.uniform(config) if uniform else config,
                                workload)
        inner = kept[uniform]
        inner.workload = workload
        inner._queue.clear()
        return inner if fault in (None, "uniform") else control.Faulty(inner, fault)
    return make


@pytest.mark.parametrize("name,fault", [(c, f) for c, fs in CASES.items() for f in fs])
def test_fault_makes_the_run_incorrect(cells, name, fault):
    cell, own, kept = cells[name]
    correct, checks, run = bench_testkit.run_cell(cell, _system(own, kept, fault))
    assert len(run.window.requests) > 1
    if fault is None:
        assert correct, checks
        return
    assert not correct, checks
    if fault == "uniform":
        assert checks["mis_size"]["value"] < checks["mis_size"]["limit"], checks
        return
    caught = {"flip": ("both_in", "uncovered"), "stale": ("repeats",)}[fault]
    assert any(checks[k]["value"] > 0 for k in caught), checks
