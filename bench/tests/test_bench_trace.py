"""The reduction from profiler trace to busy time, idle share, per-op time
and idle gaps: on plain events, and on a small trace recorded on a TPU v5e
(`data/tpu_trace.xplane.pb`, made by `data/record_trace.py`)."""
import pathlib

import pytest

import bench_testkit  # noqa: F401
from benchlib import trace

FIXTURE = pathlib.Path(__file__).parent / "data" / "tpu_trace.xplane.pb"

MS = 1_000_000  # ns


def test_merge_clip_and_gaps():
    busy = trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert trace.clip(busy, 2, 6) == [(2, 3), (5, 6)]
    assert trace.gaps(busy, -1, 10) == [(-1, 0), (3, 5), (8, 10)]


def test_reduce_events_busy_idle_ops_and_gaps():
    host = [
        ("bench.window", 0, 100 * MS),
        ("bench.submit", 0, 30 * MS),
        ("bench.step", 30 * MS, 70 * MS),
        ("bench.other", 95 * MS, 5 * MS),
        ("not.ours", 0, 100 * MS),
    ]
    # device 0 busy 30..60 (two overlapping ops) and 90..110 (clipped at 100)
    ops = {0: [("fusion.1", 30 * MS, 20 * MS), ("while", 40 * MS, 20 * MS),
               ("fusion.1", 90 * MS, 20 * MS)]}
    s = trace.reduce_events(ops, host)
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.04)
    assert s.idle_pct == pytest.approx(60.0)
    assert dict(s.device_ops) == pytest.approx({"fusion.1": 0.03, "while": 0.02})
    # idle 0..30 under bench.submit, 60..90 under bench.step (midpoint 75)
    assert s.idle_gaps == [("bench.submit", pytest.approx(0.03)),
                           ("bench.step", pytest.approx(0.03))]


def test_busy_is_averaged_over_devices():
    host = [("bench.window", 0, 10 * MS)]
    ops = {0: [("a", 0, 10 * MS)], 1: [("a", 0, 5 * MS)]}
    s = trace.reduce_events(ops, host)
    assert s.n_devices == 2 and s.busy_s == pytest.approx(0.0075)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace.reduce_events({0: []}, [("bench.step", 0, 1)])


def test_op_names_are_cut_from_the_hlo_text():
    assert trace.op_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") \
        == "fusion.3"
    assert trace.op_name("while") == "while"


def test_recorded_tpu_trace():
    # three steps of tanh(x @ x) @ x on 1024x1024 f32, recorded on a v5e
    s = trace.reduce_file(str(FIXTURE))
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.009777369)
    assert s.busy_s == pytest.approx(7.4618e-05)
    assert s.idle_pct == pytest.approx(100 * (1 - 7.4618e-05 / 0.009777369))
    assert [n for n, _ in s.device_ops[:2]] == ["fusion", "convolution_tanh_fusion"]
    assert sum(t for _, t in s.device_ops) <= s.busy_s * 3
    assert s.idle_gaps[0] == ("bench.step", pytest.approx(0.003318079))
    assert {n for n, _ in s.idle_gaps} <= {"bench.submit", "bench.step"}
