"""The byte floor behind `solve_roofline`."""
import pytest

import bench_testkit  # noqa: F401
from benchlib.peaks import PEAKS, peaks_for
from benchlib.roofline import least_time_s, round_bytes


def test_round_bytes_counts_a_bit_per_half_edge_and_the_vertex_words():
    # roadNet-PA at its published size: 1,090,920 vertices, 3,083,796 half-edges
    n, h = 1_090_920, 3_083_796
    assert round_bytes(n, h) == pytest.approx(h / 8 + n * 4.25)
    assert round_bytes(n, h) == pytest.approx(5_021_884.5)


def test_least_time_is_memory_bound_and_linear_in_rounds():
    v5e = peaks_for("TPU v5 lite")
    one = least_time_s(1, 1_090_920, 3_083_796, v5e)
    assert one == pytest.approx(5_021_884.5 / 819e9)
    assert least_time_s(6, 1_090_920, 3_083_796, v5e) == pytest.approx(6 * one)


def test_unknown_device_kind_is_an_error():
    assert "TPU v5 lite" in PEAKS
    with pytest.raises(ValueError):
        peaks_for("TPU v9 imaginary")
