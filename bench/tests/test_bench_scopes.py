"""Device time by the program's own names (`benchlib.scopes`): self time of
nested ops, sums by scope with the `unscoped` rest, and idle gaps named by
program spans; on plain events and on the recorded TPU trace, which it
must read as `benchlib.trace` does."""
import pathlib

import pytest

import bench_testkit  # noqa: F401
from benchlib import scopes, trace

FIXTURE = pathlib.Path(__file__).parent / "data" / "tpu_trace.xplane.pb"

MS = 1_000_000  # ns


def test_self_time_takes_nested_ops_out_of_their_parent():
    events = [("while.1", 0, 10 * MS), ("fusion.2", 1 * MS, 3 * MS),
              ("fusion.3", 5 * MS, 4 * MS), ("copy.4", 6 * MS, 1 * MS),
              ("after", 12 * MS, 2 * MS)]
    got = dict(scopes.self_times(events, 0, 20 * MS))
    assert got == {"while.1": 3 * MS, "fusion.2": 3 * MS,
                   "fusion.3": 3 * MS, "copy.4": 1 * MS, "after": 2 * MS}
    # clipped to the window: the parts outside count nowhere
    got = dict(scopes.self_times(events, 2 * MS, 11 * MS))
    assert got["while.1"] == 8 * MS - 2 * MS - 4 * MS
    assert got["fusion.2"] == 2 * MS and got["after"] == 0


def test_reduce_scopes_sums_by_scope_and_names_gaps_by_program_spans():
    host = [
        ("bench.window", 0, 100 * MS),
        ("bench.solve", 0, 100 * MS),
        ("solver.execute", 10 * MS, 55 * MS),
        ("solver.fetch", 65 * MS, 25 * MS),
        ("not.ours", 0, 100 * MS),
    ]
    ops = {0: [("while.1", 10 * MS, 40 * MS),
               ("fusion.2", 12 * MS, 20 * MS),
               ("fusion.3", 32 * MS, 10 * MS),
               ("copy.4", 70 * MS, 5 * MS)]}
    op_scopes = {"fusion.2": "mis.p1/edge", "fusion.3": "mis.p2/tile",
                 "copy.4": "mis.result"}
    s = scopes.reduce_scopes(ops, host, op_scopes)
    assert s.scope_s == pytest.approx({
        "mis.p1/edge": 0.02, "mis.p2/tile": 0.01, "mis.result": 0.005,
        "unscoped": 0.01})   # the loop's own 10 ms outside its body's ops
    assert sum(s.scope_s.values()) == pytest.approx(
        trace.reduce_events(ops, host).busy_s)
    assert s.seconds("mis.p1") == pytest.approx(0.02)
    assert s.path_seconds("tile") == pytest.approx(0.01)
    assert s.path_seconds("edge") == pytest.approx(0.02)
    # gaps, named at their midpoints: 75..100 inside solver.fetch, 50..70
    # inside solver.execute, 0..10 under bench.solve alone
    assert s.idle_gaps == [("solver.fetch", pytest.approx(0.025)),
                           ("solver.execute", pytest.approx(0.02)),
                           ("bench.solve", pytest.approx(0.01))]


def test_without_a_scope_map_everything_is_unscoped():
    host = [("bench.window", 0, 10 * MS)]
    ops = {0: [("a", 0, 4 * MS)], 1: [("a", 0, 2 * MS)]}
    s = scopes.reduce_scopes(ops, host)
    assert s.scope_s == pytest.approx({"unscoped": 0.003})


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        scopes.reduce_scopes({0: []}, [("solver.execute", 0, 1)])


def test_recorded_tpu_trace_reads_as_benchlib_trace_does():
    # the benchmark's own reduction of the same file stays what it was
    want = trace.reduce_file(str(FIXTURE))
    assert want.busy_s == pytest.approx(7.4618e-05)
    assert want.window_s == pytest.approx(0.009777369)
    device_ops, host_spans = scopes.read_xplane(str(FIXTURE))
    s = scopes.reduce_scopes(device_ops, host_spans)
    assert set(s.scope_s) == {"unscoped"}
    assert s.scope_s["unscoped"] == pytest.approx(want.busy_s)
    # the recorded run opened no program span: gaps are named as before
    assert s.idle_gaps == want.idle_gaps
