"""Percentile, rate, spread and idle arithmetic on fixed inputs."""
import math
import statistics

import pytest

import bench_testkit  # noqa: F401
from benchlib.stats import mean, per_item_ms, percentile, spread
from benchlib.trace import TraceSummary


@pytest.mark.parametrize("q,want", [(50, 5), (95, 10), (90, 9), (10, 1), (100, 10)])
def test_percentile_nearest_rank(q, want):
    assert percentile(list(range(10, 0, -1)), q) == want


def test_percentile_counts_failures_as_infinitely_late():
    values = [1.0] * 95 + [math.inf] * 5
    assert percentile(values, 95) == 1.0
    assert percentile(values + [math.inf], 95) == math.inf
    assert percentile([], 50) is None
    with pytest.raises(ValueError):
        percentile([1.0], 0)


def test_rate_and_mean():
    assert per_item_ms(2.5, 10) == pytest.approx(250.0)
    assert per_item_ms(2.5, 0) is None
    assert mean([1, 2, 6]) == 3
    assert mean([]) is None


def test_spread_is_interquartile_over_median():
    values = [10.0, 10.2, 9.8, 10.1, 9.9, 10.0]
    q1, q2, q3 = statistics.quantiles(values, n=4)
    assert spread(values) == pytest.approx((q3 - q1) / q2)


@pytest.mark.parametrize("busy,window,want", [(0.25, 1.0, 75.0), (1.0, 1.0, 0.0),
                                              (0.0, 2.0, 100.0)])
def test_idle_share(busy, window, want):
    s = TraceSummary(busy_s=busy, window_s=window, n_devices=1,
                     device_ops=[], idle_gaps=[])
    assert s.idle_pct == pytest.approx(want)
