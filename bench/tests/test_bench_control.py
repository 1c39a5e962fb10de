"""The control: the plain reference in the program's place, one round short
of converging, fails the comparison.  The same reference run to convergence
gives valid sets, and fails only the size floor: its priorities are
uniform, as the program's `h1`, not the configured H3."""
import pytest

import bench_testkit
from benchlib.spec import load_cell, load_module

control = load_module(bench_testkit.BENCH / "control.py")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_testkit.toy_copy(tmp_path_factory.mktemp("control"))


@pytest.mark.parametrize("rounds_short", [1, 0])
def test_control_fails_and_the_sound_reference_passes(root, rounds_short):
    cell = load_cell("road-solve", root)

    def make(config, workload):
        return control.ReferenceSystem(config, workload, cell.bench,
                                       rounds_short=rounds_short)

    correct, checks, run = bench_testkit.run_cell(cell, make, seconds=0.5)
    assert len(run.window.requests) > 0
    assert not correct
    valid = {k: c for k, c in checks.items() if k != "mis_size"}
    assert all(c["value"] <= c["limit"] for c in valid.values()) == \
        (rounds_short == 0), checks
    if rounds_short:
        assert checks["uncovered"]["value"] > 0
    else:
        assert checks["mis_size"]["value"] < checks["mis_size"]["limit"]


def test_control_factory_uses_the_reference():
    cell = load_cell("road-solve", bench_testkit.ROOT)
    system = control.factory(cell, "control")(cell.config, None)
    assert isinstance(system, control.ReferenceSystem) and system.rounds_short == 1


def test_uniform_control_changes_only_the_priorities():
    cell = load_cell("road-solve", bench_testkit.ROOT)
    config = control.uniform(cell.config)
    assert config["system"]["options"]["heuristic"] == "h1"
    assert "heuristic" not in cell.config["system"].get("options", {})
    assert {k: v for k, v in config.items() if k != "system"} == \
        {k: v for k, v in cell.config.items() if k != "system"}
