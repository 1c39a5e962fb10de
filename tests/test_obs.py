"""Observability subsystem (DESIGN.md §14): round telemetry bit-neutrality
and invariants across engine × storage × frontier, span tracing with the compile/execute split, batched solve_ms attribution,
metrics-registry views, the JSONL report CLI, and Guard 5 (host-silent hot
loop)."""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.api import SolveOptions, Solver
from repro.core.engine import engine_names, get_engine
from repro.graphs.generators import erdos_renyi
from repro.obs import (
    COL_ALIVE,
    COL_FRONTIER,
    COL_SELECTED,
    COL_TILES_SKIPPED,
    REGISTRY,
    MetricsRegistry,
    RoundTrace,
    TELEMETRY_COLS,
    TELEMETRY_FILL,
    Trace,
    trace_span,
)
from repro.obs.report import main as report_main
from repro.serve_mis.service import MISService, ServeConfig

ROOT = pathlib.Path(__file__).resolve().parent.parent
ENGINES = engine_names()
STORAGES = ("int8", "bitpack")
FRONTIERS = ("dense", "bitwise")


def _graph(n=128, seed=0):
    return erdos_renyi(n, avg_deg=6.0, seed=seed)


def _opts(engine, storage, frontier, telemetry, **kw):
    return SolveOptions(
        engine=engine, storage=storage, frontier=frontier,
        telemetry=telemetry, tile_size=32, placement="local", **kw,
    )


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

def test_metrics_registry_basics():
    reg = MetricsRegistry("t")
    reg.counter("a").inc()
    reg.counter("a").inc(2)
    reg.gauge("g").set(7)
    reg.histogram("h").observe(1.0)
    reg.histogram("h").observe(3.0)
    snap = reg.snapshot()
    assert snap["a"] == 3
    assert snap["g"] == 7.0
    h = snap["h"]
    assert (h["count"], h["total"], h["min"], h["max"], h["mean"]) == (
        2, 4.0, 1.0, 3.0, 2.0,
    )
    # quantiles are bucket upper edges clamped to the observed max
    assert h["p50"] == 1.0 and h["p95"] == 3.0 and h["p99"] == 3.0
    # cumulative bucket counts, +Inf last
    assert h["buckets"][-1] == ["+Inf", 2]
    # first registration fixes the kind
    with pytest.raises(TypeError):
        reg.gauge("a")


def test_stats_properties_are_metrics_views():
    """The legacy dicts survive as read-only views — same keys, same ints —
    so nothing downstream re-learns a spelling."""
    solver = Solver(SolveOptions(engine="tiled_ref", placement="local"))
    assert solver.stats == {"solves": 0, "batches": 0, "compiles": 0}
    solver.solve(_graph())
    assert solver.stats["solves"] == 1
    assert solver.stats["compiles"] == 1
    with pytest.raises(AttributeError):
        solver.stats = {}
    assert set(solver.plans.stats) == {
        "mem_hits", "disk_hits", "misses", "evicted_stale",
    }
    assert solver.plans.stats["misses"] == 1


# --------------------------------------------------------------------------
# RoundTrace: construction, JSONL round-trip, validation
# --------------------------------------------------------------------------

def _fake_buffer(rows):
    buf = np.full((8, TELEMETRY_COLS), TELEMETRY_FILL, np.int32)
    for i, (a, f, s, k) in enumerate(rows):
        buf[i, COL_ALIVE] = a
        buf[i, COL_FRONTIER] = f
        buf[i, COL_SELECTED] = s
        buf[i, COL_TILES_SKIPPED] = k
    return buf


def test_roundtrace_roundtrip_and_summary():
    buf = _fake_buffer([(10, 4, 3, 1), (5, 2, 2, 2), (1, 1, 1, 3)])
    rt = RoundTrace.from_buffer(buf, 3, tiles_total=4, meta={"engine": "x"})
    rt.check_invariants()
    assert rt.rounds == 3 and list(rt.alive) == [10, 5, 1]
    line = rt.to_jsonl_line()
    assert json.loads(line)["kind"] == "rounds"
    rt2 = RoundTrace.from_jsonl_line(line)
    assert rt2.to_dict() == rt.to_dict()
    s = rt.summary()
    assert s["alive0"] == 10 and s["selected_total"] == 6
    assert s["frontier_peak"] == 4


def test_roundtrace_rejects_bad_buffers():
    with pytest.raises(ValueError):
        RoundTrace.from_buffer(np.zeros((4, TELEMETRY_COLS + 1), np.int32), 2)
    # a used row still holding the fill value = the loop never wrote it
    buf = _fake_buffer([(10, 4, 3, 0)])
    with pytest.raises(ValueError):
        RoundTrace.from_buffer(buf, 2)
    # alive must be non-increasing
    rt = RoundTrace.from_buffer(_fake_buffer([(5, 2, 2, 0), (9, 1, 1, 0)]), 2)
    with pytest.raises(AssertionError):
        rt.check_invariants()


# --------------------------------------------------------------------------
# telemetry: bit-neutral, invariant-clean, across every combination
# --------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
def test_telemetry_bit_identity_and_invariants(engine):
    """Telemetry on/off must trace to the same solution for every storage ×
    frontier, and the recorded series must satisfy the round invariants."""
    g = _graph(n=128, seed=3)
    for storage in STORAGES:
        for frontier in FRONTIERS:
            off = Solver(_opts(engine, storage, frontier, False)).solve(g)
            on = Solver(_opts(engine, storage, frontier, True)).solve(g)
            assert np.array_equal(
                np.asarray(off.in_mis), np.asarray(on.in_mis)
            ), (engine, storage, frontier)
            assert off.rounds == on.rounds
            rt = on.telemetry
            assert rt is not None and off.telemetry is None
            rt.check_invariants()
            # the buffer's trimmed length IS the convergence round count,
            # and the series opens on the full vertex set
            assert rt.rounds == on.rounds
            assert rt.alive[0] == g.n_nodes
            # a cold solve never evicts: selections accumulate to |MIS|
            assert sum(rt.selected) == on.mis_size
            assert rt.meta["engine"] == engine
            assert rt.meta["frontier"] in ("dense", "bitwise")


def test_telemetry_tiles_skipped_bounded():
    g = _graph(n=256, seed=5)
    res = Solver(_opts("tiled_ref", "bitpack", "auto", True)).solve(g)
    rt = res.telemetry
    assert rt.tiles_total > 0
    assert min(rt.tiles_skipped) >= 0
    assert max(rt.tiles_skipped) <= rt.tiles_total


# --------------------------------------------------------------------------
# span tracing + the compile/execute split
# --------------------------------------------------------------------------

def test_trace_span_tree_and_noop():
    tr = Trace("t")
    with trace_span(tr, "outer", k=1):
        with trace_span(tr, "inner"):
            pass
    names = [(s.name, s.depth) for s in tr.spans]
    assert ("outer", 0) in names and ("inner", 1) in names
    d = json.loads(tr.to_jsonl_line())
    assert d["kind"] == "trace" and len(d["spans"]) == 2
    # trace=None is a no-op seam, not an error
    with trace_span(None, "ignored"):
        pass


def test_traced_solve_splits_compile_from_execute():
    g = _graph(n=128, seed=9)
    solver = Solver(_opts("tiled_ref", "int8", "auto", False))
    tr = Trace("cold")
    res = solver.solve(g, trace=tr)
    names = [s.name for s in tr.spans]
    assert "solver.plan" in names and "solver.compile" in names
    assert "solver.execute" in names
    assert res.stats["compile_ms"] > 0 and res.stats["execute_ms"] >= 0
    assert res.stats["solve_ms"] >= res.stats["execute_ms"]
    # warm re-dispatch: AOT cache hit, no compile span, identical bits
    tr2 = Trace("warm")
    res2 = solver.solve(g, trace=tr2)
    assert "solver.compile" not in [s.name for s in tr2.spans]
    assert "compile_ms" not in res2.stats
    assert np.array_equal(np.asarray(res.in_mis), np.asarray(res2.in_mis))
    # traced and untraced dispatches agree bit-for-bit too
    res3 = Solver(_opts("tiled_ref", "int8", "auto", False)).solve(g)
    assert np.array_equal(np.asarray(res.in_mis), np.asarray(res3.in_mis))


def test_batched_solve_ms_attribution():
    """Members report their SHARE of the batch wall plus the explicit
    `batch_ms` — the old code booked the whole batch on every member."""
    gs = [_graph(n=96, seed=s) for s in (1, 2, 3)]
    solver = Solver(_opts("tiled_ref", "int8", "auto", True))
    tr = Trace("batch")
    plans = [solver.plan(g) for g in gs]
    results = solver.solve_many(plans, trace=tr)
    assert len(results) == 3
    for r in results:
        assert r.stats["batch_size"] == 3
        assert r.stats["batch_ms"] == pytest.approx(
            r.stats["solve_ms"] * 3, rel=0.01
        )
        assert r.telemetry is not None
        assert r.telemetry.meta["batch_size"] == 3
    # batch-global series is shared, not duplicated per member
    assert len({id(r.telemetry) for r in results}) == 1


# --------------------------------------------------------------------------
# service: end-to-end JSONL through the report CLI
# --------------------------------------------------------------------------

def test_service_telemetry_trace_jsonl(tmp_path):
    trace_path = str(tmp_path / "trace.jsonl")
    svc = MISService(ServeConfig(
        engine="tiled_ref", max_batch=4,
        telemetry=True, trace_path=trace_path,
    ))
    svc.submit(_graph(n=96, seed=11))
    svc.submit(_graph(n=96, seed=12))
    responses = svc.drain()
    assert all(r.valid for r in responses)
    for r in responses:
        assert "rounds_summary" in r.stats
        # the series is BATCH-global (like `converged`): its round count
        # bounds every member's own convergence round from above
        assert r.stats["rounds_summary"]["rounds"] >= r.rounds
        assert "batch_ms" in r.stats and "execute_ms" in r.stats
    kinds = [
        json.loads(line)["kind"]
        for line in open(trace_path).read().splitlines()
    ]
    assert "trace" in kinds and "rounds" in kinds
    # the merged snapshot spans every layer's prefix
    snap = svc.metrics_snapshot()
    assert snap["service.requests"] == 2
    assert any(k.startswith("solver.") for k in snap)
    assert any(k.startswith("plan_cache.") for k in snap)
    assert svc.stats["requests"] == 2
    # the report CLI renders it (exit 0) and rejects an empty file (exit 2)
    assert report_main(["report", trace_path]) == 0
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert report_main(["report", str(empty)]) == 2


def test_service_disabled_obs_is_quiet(tmp_path):
    """No trace_path, no telemetry → no writer, no telemetry payloads, and
    the response stats keep exactly the legacy solve keys."""
    svc = MISService(ServeConfig(engine="tiled_ref", max_batch=2))
    svc.submit(_graph(n=96, seed=13))
    (r,) = svc.drain()
    assert svc._trace_writer is None
    assert "rounds_summary" not in r.stats
    assert "compile_ms" not in r.stats
    assert r.valid


# --------------------------------------------------------------------------
# repair metrics (process registry) — eager-only contract
# --------------------------------------------------------------------------

def test_update_records_repair_metrics():
    from repro.dyngraph.delta import EdgeDelta

    before = REGISTRY.snapshot().get("repair.incremental", 0)
    solver = Solver(_opts(
        "tiled_ref", "int8", "auto", True, repair="incremental",
    ))
    g = _graph(n=96, seed=15)
    res = solver.solve(g)
    res2 = solver.update(res, EdgeDelta.make([0, 7], [5, 9], [], []))
    assert res2.stats["repair"] == "incremental"
    assert res2.telemetry is not None
    assert res2.telemetry.meta["scope"] == "repair"
    assert REGISTRY.snapshot()["repair.incremental"] == before + 1


# --------------------------------------------------------------------------
# Guard 5: the hot loop stays host-silent
# --------------------------------------------------------------------------

def _guard5_findings(tree_root):
    from repro.lint.analysis import load_universe
    from repro.lint.rules import get_rules, run_rules

    ctx = load_universe([tree_root])
    return [f for f in run_rules(ctx, get_rules(["RPR005"])) if f.active]


def test_guard5_detects_host_roundtrips(tmp_path):
    bad = tmp_path / "src" / "repro" / "core" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent("""
        import jax
        from jax.experimental import io_callback
        from jax.experimental import host_callback as hcb

        def f(x):
            jax.debug.print("x = {}", x)
            io_callback(print, None, x)
            return x
    """))
    msgs = [f.message for f in _guard5_findings(tmp_path / "src")]
    assert len(msgs) == 3, msgs
    assert any("debug.print" in m for m in msgs)
    assert any("io_callback" in m for m in msgs)
    assert any("host_callback" in m for m in msgs)
    bad.write_text("import jax\n\ndef f(x):\n    return x + 1\n")
    assert _guard5_findings(tmp_path / "src") == []


def test_ci_guards_clean_on_repo():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "ci_guards.py")],
        capture_output=True, text=True, cwd=str(ROOT),
        env=dict(os.environ, PYTHONPATH=str(ROOT / "src")),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 error(s)" in proc.stdout


# --------------------------------------------------------------------------
# §17: fixed-bucket histogram quantiles
# --------------------------------------------------------------------------

def test_histogram_quantiles_monotone_and_upper_bound():
    from repro.obs.metrics import Histogram

    vals = [0.2, 0.4, 0.9, 3.0, 7.0, 40.0, 90.0, 400.0, 2000.0, 9000.0,
            20000.0]   # last one lands in the +Inf overflow bucket
    h = Histogram("lat")
    for v in vals:
        h.observe(v)
    # monotone in q
    qs = [h.quantile(q) for q in (0.1, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0)]
    assert qs == sorted(qs)
    # upper-bound property: never below the true q-th ranked observation
    s = sorted(vals)
    for q in (0.1, 0.5, 0.9, 0.95, 0.99):
        rank = max(int(-(-q * len(s) // 1)), 1)
        assert h.quantile(q) >= s[rank - 1], q
    # ... and never above the observed max (overflow reports the max)
    assert h.quantile(0.99) <= max(vals)
    assert h.quantile(1.0) == max(vals)
    # empty histogram: None quantiles, count-0 snapshot
    empty = Histogram("e")
    assert empty.quantile(0.5) is None
    snap = empty.snapshot()
    assert snap["count"] == 0 and snap["p99"] is None


def test_histogram_merge_across_registries():
    from repro.obs import MetricsRegistry
    from repro.obs.metrics import Histogram

    a, b = MetricsRegistry("a"), MetricsRegistry("b")
    for v in (1.0, 2.0):
        a.histogram("lat").observe(v)
    for v in (300.0, 700.0):
        b.histogram("lat").observe(v)
    a.counter("n").inc(2)
    b.counter("n").inc(3)
    b.gauge("depth").set(9)
    a.merge(b)
    snap = a.snapshot()
    assert snap["n"] == 5                       # counters add
    assert snap["depth"] == 9.0                 # gauges take the last value
    h = snap["lat"]
    assert h["count"] == 4 and h["min"] == 1.0 and h["max"] == 700.0
    assert h["p99"] == 700.0
    # merging a different bucket scheme would silently mis-bin: refuse
    other = Histogram("lat", buckets=(1.0, 10.0))
    with pytest.raises(ValueError):
        a.histogram("lat").merge(other)


# --------------------------------------------------------------------------
# §17: Prometheus text exposition
# --------------------------------------------------------------------------

def test_promtext_rendering_and_atomic_write(tmp_path):
    from repro.obs import MetricsRegistry, to_promtext, write_promtext

    reg = MetricsRegistry("t")
    reg.counter("svc.requests").inc(3)
    reg.gauge("svc.queue_depth").set(1.5)
    reg.histogram("svc.latency_ms").observe(2.0)
    txt = to_promtext(reg.snapshot())
    assert "# TYPE repro_svc_requests_total counter" in txt
    assert "repro_svc_requests_total 3" in txt
    assert "repro_svc_queue_depth 1.5" in txt
    assert 'repro_svc_latency_ms_bucket{le="2.5"} 1' in txt
    assert 'repro_svc_latency_ms_bucket{le="+Inf"} 1' in txt
    assert "repro_svc_latency_ms_sum 2.0" in txt
    assert "repro_svc_latency_ms_count 1" in txt
    assert 'repro_svc_latency_ms{quantile="0.99"} 2.0' in txt
    assert txt.endswith("\n")
    path = tmp_path / "metrics.prom"
    write_promtext(reg.snapshot(), str(path))
    assert path.read_text() == txt
    assert list(tmp_path.iterdir()) == [path]      # no tmp file left behind


# --------------------------------------------------------------------------
# §17: service health (SLO histograms, gauges, span stages) + drift + roofline
# --------------------------------------------------------------------------

def test_service_health_drift_and_attribution(tmp_path, monkeypatch):
    from repro.api import solver as solver_mod
    from repro.dyngraph import random_delta
    from repro.perf.roofline import V5E

    # the roofline gauges are recorded only on a chip with a peak entry:
    # steer the device lookup to the v5e entry so this CPU run scores them
    monkeypatch.setattr(solver_mod, "device_peaks", lambda: V5E)
    before_epochs = REGISTRY.snapshot().get("dyngraph.epochs", 0)
    svc = MISService(ServeConfig(
        engine="tiled_ref", max_batch=2, repair="incremental",
        telemetry=True, trace_path=str(tmp_path / "trace.jsonl"),
    ))
    svc.submit(_graph(n=96, seed=21))
    svc.submit(_graph(n=96, seed=22))
    responses = svc.drain()
    assert all(r.valid for r in responses)
    # a chained delta stream: each update targets the previous one
    target = responses[0].id
    for step in (1, 2):
        plan = svc._results[target].plan
        delta = random_delta(plan.g, n_add=4, n_remove=4, seed=step)
        target = svc.submit_update(target, delta)
        (r,) = svc.drain()
        assert r.valid

    snap = svc.metrics_snapshot()
    # per-op SLO latency histograms (enqueue -> response)
    assert snap["service.latency_ms.batched"]["count"] == 2
    assert snap["service.latency_ms.update"]["count"] == 2
    for op in ("batched", "update"):
        h = snap[f"service.latency_ms.{op}"]
        assert h["p50"] <= h["p95"] <= h["p99"] <= h["max"] * 1.0 + 1e-9 \
            or h["p99"] == h["max"]
    # health gauges settle to empty after drain
    assert snap["service.queue_depth"] == 0.0
    assert snap["service.inflight"] == 0.0
    # span-taxonomy stage histograms (traced steps only): one span per
    # worker step — the solve batch plus each update's own window
    assert snap["service.span_ms.service.step"]["count"] == 3
    assert "service.span_ms.service.batch" in snap
    assert "service.span_ms.solver.update" in snap
    # drift metrics: one epoch recorded per applied delta, via patch_plan
    assert snap["dyngraph.epochs"] == before_epochs + 2
    assert snap["dyngraph.touched_tiles"]["count"] >= 2
    assert snap["dyngraph.epoch"] == 2.0
    assert snap["dyngraph.occupancy"] > 0.0
    assert 0.0 < snap["dyngraph.dirty_frac"] <= 1.0
    assert "dyngraph.locality_decay" in snap
    # roofline attribution gauges fed from the measured solve
    assert snap["perf.roofline_predicted_us"] > 0.0
    assert snap["perf.roofline_measured_us"] > 0.0
    assert "perf.roofline_error_pct" in snap


def test_drift_helpers():
    from repro.dyngraph.delta import EdgeDelta
    from repro.dyngraph.drift import (
        dirty_vertex_frac,
        tile_occupancy,
        touched_tile_count,
    )

    # (0,1) lives in tile (0,0); (40,41) in tile (1,1) of a 2x2 block grid
    delta = EdgeDelta.make([0, 40], [1, 41], [], [])
    assert touched_tile_count(delta, tile_size=32, n_block_cols=2) == 2
    # a cross-block edge dirties both half-edge tiles
    cross = EdgeDelta.make([0], [40], [], [])
    assert touched_tile_count(cross, tile_size=32, n_block_cols=2) == 2
    assert touched_tile_count(EdgeDelta.make(), 32, 2) == 0
    assert dirty_vertex_frac(delta, 64) == pytest.approx(4 / 64)
    assert dirty_vertex_frac(EdgeDelta.make(), 64) == 0.0
    assert tile_occupancy(4, 4, 32) == pytest.approx(8 / (4 * 32 * 32))
    assert tile_occupancy(0, 4, 32) == 0.0


def test_plan_carries_occupancy0_through_patches():
    from repro.dyngraph.delta import EdgeDelta

    solver = Solver(_opts("tiled_ref", "int8", "auto", False,
                          repair="incremental"))
    g = _graph(n=96, seed=23)
    res = solver.solve(g)
    occ0 = res.plan.occupancy0
    assert occ0 > 0.0
    res2 = solver.update(res, EdgeDelta.make([0, 7], [5, 9], [], []))
    # the epoch-0 baseline rides through the patch lineage unchanged
    assert res2.plan.occupancy0 == occ0
    assert res2.plan.epoch == 1


# --------------------------------------------------------------------------
# §17: report CLI — degenerate traces and --json
# --------------------------------------------------------------------------

def test_report_handles_degenerate_traces_and_json(tmp_path, capsys):
    from repro.obs.report import report_json

    # 1-round trace with 0 alive everywhere: no div-by-zero sparklines
    rt1 = RoundTrace.from_buffer(_fake_buffer([(0, 0, 0, 0)]), 1,
                                 tiles_total=0)
    path = tmp_path / "degenerate.jsonl"
    path.write_text(rt1.to_jsonl_line() + "\n")
    assert report_main(["report", str(path)]) == 0
    capsys.readouterr()
    assert report_main(["report", "--json", str(path)]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["n_records"] == 1 and d["counts"] == {"rounds": 1}
    doc = report_json(str(path))
    assert doc["records"][0]["summary"]["rounds"] == 1
