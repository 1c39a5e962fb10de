"""Roofline machinery: HLO collective parser, per-device cost semantics."""
import numpy as np
import pytest

from repro.perf.roofline import (
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS,
    RooflineTerms,
    parse_collective_bytes,
)


def test_parser_basic_ops():
    hlo = """
    %ag = bf16[16,512]{1,0} all-gather(%x), replica_groups={}
    %ar = f32[1024]{0} all-reduce(%y), to_apply=%add
    %rs = f32[64,32]{1,0} reduce-scatter(%z), dimensions={0}
    %a2a = s32[128]{0} all-to-all(%w)
    %cp = u8[256]{0} collective-permute(%v)
    """
    out = parse_collective_bytes(hlo)
    assert out["all-gather"] == 16 * 512 * 2
    assert out["all-reduce"] == 1024 * 4
    assert out["reduce-scatter"] == 64 * 32 * 4
    assert out["all-to-all"] == 128 * 4
    assert out["collective-permute"] == 256


def test_parser_async_start_not_double_counted():
    hlo = """
    %ags = (bf16[8,8]{1,0}, bf16[32,8]{1,0}) all-gather-start(%x)
    %agd = bf16[32,8]{1,0} all-gather-done(%ags)
    """
    out = parse_collective_bytes(hlo)
    # counted once, from the -start tuple payload
    assert out["all-gather"] == (8 * 8 + 32 * 8) * 2
    assert len(out) == 1


def test_parser_tuple_allreduce():
    hlo = "%t = (f32[10]{0}, f32[20]{0}) all-reduce(%a, %b), to_apply=%add"
    out = parse_collective_bytes(hlo)
    assert out["all-reduce"] == (10 + 20) * 4


def test_parser_ignores_non_collectives():
    hlo = "%d = f32[128,128]{1,0} dot(%a, %b), lhs_contracting_dims={1}"
    assert parse_collective_bytes(hlo) == {}


def test_terms_dominance_and_mfu():
    t = RooflineTerms(
        compute_s=1.0, memory_s=2.0, collective_s=0.5,
        flops=PEAK_FLOPS, bytes_accessed=2 * HBM_BW,
        collective_bytes=int(0.5 * ICI_BW), collectives={},
        model_flops=PEAK_FLOPS / 2,
    )
    assert t.dominant == "memory"
    assert t.step_time_s == 2.0
    assert t.mfu == pytest.approx(0.25)
    assert t.useful_flop_fraction == pytest.approx(0.5)


def test_per_device_cost_semantics():
    """cost_analysis of an SPMD-compiled program reports PER-DEVICE numbers
    (the roofline denominators assume this)."""
    from conftest import run_multidevice

    out = run_multidevice("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = jax.make_mesh((8,), ("d",), axis_types=(jax.sharding.AxisType.Auto,))
        M, K, N = 256, 128, 64
        def f(a, b):
            return a @ b
        with mesh:
            c = jax.jit(f, in_shardings=(NamedSharding(mesh, P("d", None)),
                                         NamedSharding(mesh, P(None, None)))) \\
                .lower(jax.ShapeDtypeStruct((M, K), jnp.float32),
                       jax.ShapeDtypeStruct((K, N), jnp.float32)).compile()
        flops = c.cost_analysis()["flops"]
        expect_per_dev = 2 * M * K * N / 8
        ratio = flops / expect_per_dev
        assert 0.9 < ratio < 1.1, f"not per-device: {ratio}"
        print("PER_DEVICE_OK")
    """)
    assert "PER_DEVICE_OK" in out


def test_affine_extrapolation_math():
    from repro.launch.dryrun import _affine

    a = dict(flops=10.0, bytes_accessed=100.0, collectives={"all-reduce": 4})
    b = dict(flops=16.0, bytes_accessed=130.0, collectives={"all-reduce": 10})
    out = _affine(a, b, la=2, lb=4, lfull=10)
    assert out["flops"] == pytest.approx(10 + 3 * 8)       # +3/layer × 8 layers
    assert out["bytes_accessed"] == pytest.approx(100 + 15 * 8)
    assert out["collectives"]["all-reduce"] == 4 + 3 * 8


def test_peaks_are_keyed_by_device_kind_with_a_source():
    from repro.perf.roofline import DEVICE_PEAKS, V5E, peaks_for_kind

    assert peaks_for_kind("TPU v5 lite") is V5E
    assert (V5E.flops_bf16, V5E.hbm_bw, V5E.ici_bw) == (197e12, 819e9, 50e9)
    assert (PEAK_FLOPS, HBM_BW, ICI_BW) == (197e12, 819e9, 50e9)
    for kind, peaks in DEVICE_PEAKS.items():
        assert kind.startswith("TPU") and peaks.source


def test_unknown_device_kind_is_an_error():
    from repro.perf.roofline import peaks_for_kind

    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for_kind("TPU v99")


def test_device_peaks_off_tpu_is_none_and_unknown_tpu_raises():
    from types import SimpleNamespace

    from repro.perf.roofline import V5E, device_peaks

    assert device_peaks(SimpleNamespace(platform="cpu", device_kind="cpu")) \
        is None
    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert device_peaks(v5e) is V5E
    with pytest.raises(ValueError):
        device_peaks(SimpleNamespace(platform="tpu", device_kind="TPU v99"))


def test_roofline_gauges_absent_off_chip(monkeypatch):
    """The Solver scores its roofline gauges only on a chip with a peak
    entry; a solve with telemetry on, on a device without one, records
    none."""
    from repro.api import SolveOptions, Solver
    from repro.api import solver as solver_mod
    from repro.graphs.generators import erdos_renyi

    monkeypatch.setattr(solver_mod, "device_peaks", lambda: None)
    solver = Solver(SolveOptions(engine="tiled_ref", telemetry=True))
    res = solver.solve(erdos_renyi(96, avg_deg=4.0, seed=3))
    assert res.telemetry is not None and res.telemetry.rounds > 0
    snap = solver.metrics.snapshot()
    assert not any(k.startswith("perf.roofline") for k in snap)
