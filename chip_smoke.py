#!/usr/bin/env python3
"""Chip smoke test: the production path of TC-MIS on a TPU, end to end.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the sharded path on four chips only

One chip runs three phases through the user entry points:

  solve    the G2 roadNet-PA stand-in at its published |V| (a 1044x1045
           lattice, 1,090,980 vertices) through `Solver(SolveOptions())`,
           cold and warm.  Valid MIS, bit-equal to the `segment` engine
           under the same key, and the compiled program holds a Mosaic
           kernel (`tpu_custom_call`), so an interpret-mode kernel fails it.
  kernels  {tiled_pallas, fused_pallas} x {int8, bitpack} x {dense,
           bitwise} at T = 16 and T = 128 on the reduced G2, with the tiled
           phase 1 and no hybrid split so every Pallas kernel runs; then
           the full G2 the same way with fused_pallas, dense and bitwise,
           whose 476,920-tile list runs as several launches per kernel.
           Each cell bit-equal to `segment`.
  service  an `MISService` with the default `ServeConfig`: one batch of
           reduced suite graphs, a re-submission that must hit the plan
           cache, and one `submit_update` with a small random delta.

`--chips 4` runs only G2 with `placement="sharded"` over all chips against
`placement="local"` on device 0, same plan and key: the two must agree bit
for bit.

Times printed here are one smoke run, not a benchmark.  The script exits
non-zero without printing a result when JAX finds no TPU, when it is run
outside a checkout of this repository, or when any phase fails.  The last
line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _ms(t0: float) -> float:
    return (time.perf_counter() - t0) * 1e3


def _resolved(solver, plan) -> dict:
    from repro.core.engine import get_engine, resolve_frontier

    t = plan.tiled
    part = t.partition
    return dict(
        engine=solver.options.engine,
        tile_size=t.tile_size,
        storage=t.storage,
        frontier=resolve_frontier(
            solver.options, get_engine(solver.options.engine),
            storage=t.storage,
        ),
        phase1=solver.options.phase1,
        n_tiles=t.n_tiles,
        hybrid_dense_tiles=None if part is None else part.n_dense_tiles,
        hybrid_sparse_tiles=None if part is None else part.n_sparse_tiles,
        route=solver.route(plan),
    )


def phase_solve(g):
    """Full-width G2 through the default Solver, cold then warm.  Returns
    the `segment` engine's result, the reference for later phases."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import SolveOptions, Solver
    from repro.core.validate import is_valid_mis
    from repro.obs.trace import Trace

    solver = Solver(SolveOptions())
    t0 = time.perf_counter()
    plan = solver.plan(g)
    log(f"[solve] plan_ms={_ms(t0):.1f} {json.dumps(_resolved(solver, plan))}")
    key = jax.random.key(solver.options.seed)

    results = []
    for run in ("cold", "warm"):
        tr = Trace(f"g2-{run}")
        t0 = time.perf_counter()
        res = solver.solve(plan, key=key, trace=tr)
        wall = _ms(t0)
        s = res.stats
        log(f"[solve] {run}: rounds={res.rounds} mis={res.mis_size} "
            f"compile_ms={s.get('compile_ms', 0.0)} "
            f"execute_ms={s.get('execute_ms')} wall_ms={wall:.1f} "
            f"compile={s.get('compile')}")
        results.append(res)
    cold, warm = results
    check(cold.converged, "G2 solve did not converge")
    check(np.array_equal(cold.in_mis, warm.in_mis), "cold and warm differ")
    check(is_valid_mis(g, jnp.asarray(cold.in_mis)), "G2 result is no MIS")

    hlo = "\n".join(c.as_text() for c in solver._aot.values())
    check("tpu_custom_call" in hlo,
          "compiled G2 solve holds no tpu_custom_call (kernel interpreted?)")
    log("[solve] compiled program holds tpu_custom_call")

    ref = Solver(SolveOptions(engine="segment"), plans=solver.plans)
    t0 = time.perf_counter()
    want = ref.solve(g, key=key)
    log(f"[solve] segment: rounds={want.rounds} mis={want.mis_size} "
        f"wall_ms={_ms(t0):.1f}")
    check(np.array_equal(cold.in_mis, want.in_mis),
          "G2 default solve differs from the segment engine")
    log("[solve] ok: valid MIS, bit-equal to segment")
    return want


def phase_kernels(g, g_full, want_full) -> None:
    """Every Pallas kernel on the chip, each cell bit-equal to segment."""
    import jax
    import numpy as np

    from repro.api import PlanCache, SolveOptions, Solver
    from repro.obs.trace import Trace

    key = jax.random.key(0)
    want = Solver(SolveOptions(engine="segment")).solve(g, key=key)
    cells = [
        (g, want, dict(engine=engine, storage=storage, frontier=frontier,
                       tile_size=T))
        for T in (16, 128)
        for engine in ("tiled_pallas", "fused_pallas")
        for storage in ("int8", "bitpack")
        for frontier in ("dense", "bitwise")
    ] + [
        (g_full, want_full, dict(engine="fused_pallas", frontier=frontier))
        for frontier in ("dense", "bitwise")
    ]
    plans = PlanCache(hybrid="off")
    for graph, ref, knobs in cells:
        solver = Solver(
            SolveOptions(phase1="tiled", hybrid="off", **knobs), plans=plans
        )
        plan = solver.plan(graph)
        r = _resolved(solver, plan)
        got = solver.solve(plan, key=key, trace=Trace("kernels"))
        cell = (f"|V|={graph.n_nodes} T={r['tile_size']} {knobs['engine']} "
                f"{r['storage']} {r['frontier']}")
        check(r["frontier"] == knobs["frontier"],
              f"{cell}: asked for {knobs['frontier']}")
        check(np.array_equal(got.in_mis, ref.in_mis),
              f"{cell}: differs from segment")
        log(f"[kernels] {cell}: n_tiles={r['n_tiles']} rounds={got.rounds} "
            f"mis={got.mis_size} compile_ms={got.stats.get('compile_ms')} "
            f"execute_ms={got.stats['execute_ms']} equal=True")


def phase_service(graphs) -> None:
    """The serving loop: a batch, a plan-cache hit, a delta update."""
    from repro.dyngraph import random_delta
    from repro.serve_mis.service import MISService, ServeConfig

    svc = MISService(ServeConfig())

    def serve(what: str):
        t0 = time.perf_counter()
        responses = svc.drain()
        wall = _ms(t0)
        for r in responses:
            log(f"[service] {what} id={r.id} {r.source} rounds={r.rounds} "
                f"mis={r.mis_size} valid={r.valid} "
                f"queue_ms={r.stats['queue_ms']} "
                f"solve_ms={r.stats['solve_ms']} "
                f"plan_cache={r.stats['plan_cache']} "
                f"batch_size={r.stats['batch_size']}")
            check(r.valid, f"service response {r.id} is invalid")
        log(f"[service] {what}: {len(responses)} responses in {wall:.1f} ms")
        return responses

    ids = [svc.submit(g) for g in graphs]
    first = serve("batch")
    check(len(first) == len(ids), "batch lost requests")
    svc.submit(graphs[0])
    (again,) = serve("resubmit")
    check(again.stats["plan_cache"] == "mem",
          f"re-submission missed the plan cache: {again.stats['plan_cache']}")
    base = first[0]
    plan = svc._results[base.id].plan
    svc.submit_update(base.id, random_delta(plan.g, n_add=8, n_remove=8,
                                            seed=1))
    (upd,) = serve("update")
    log(f"[service] update repair={upd.stats['repair']} "
        f"epoch={upd.stats['plan_epoch']}")


def phase_sharded(g) -> None:
    """G2 sharded over every chip against local on device 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import SolveOptions, Solver
    from repro.core.validate import is_valid_mis

    sharded = Solver(SolveOptions(placement="sharded"))
    local = Solver(SolveOptions(placement="local"), plans=sharded.plans)
    plan = sharded.plan(g)
    key = jax.random.key(0)
    out = {}
    for name, solver in (("sharded", sharded), ("local", local)):
        for run in ("cold", "warm"):
            t0 = time.perf_counter()
            res = solver.solve(plan, key=key)
            log(f"[sharded] {name} {run}: rounds={res.rounds} "
                f"mis={res.mis_size} placement={res.placement} "
                f"wall_ms={_ms(t0):.1f} stats={json.dumps(res.stats)}")
            out[name] = res
    check(out["sharded"].placement == "sharded", "sharded run was not sharded")
    check(out["sharded"].stats.get("n_shards") == jax.device_count(),
          "sharded run did not span every chip")
    check(is_valid_mis(g, jnp.asarray(out["sharded"].in_mis)),
          "sharded result is no MIS")
    check(np.array_equal(out["sharded"].in_mis, out["local"].in_mis),
          "sharded and local differ")
    log("[sharded] ok: valid MIS, sharded bit-equal to local")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the sharded-vs-local G2 comparison")
    args = p.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax

    devices = jax.devices()
    dev = devices[0]
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(devices))
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1

    from repro.graphs.generators import generate
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[device] {json.dumps(device)}")
    log(f"[device] compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    g2 = generate("G2", scale="full", seed=0)
    log(f"[graph] G2 full: |V|={g2.n_nodes} half-edges={g2.n_edges} "
        f"gen_ms={_ms(t0):.1f}")

    phases = []
    if args.chips == 4:
        phases.append(("sharded", lambda: phase_sharded(g2)))
    else:
        reduced = [generate(pid, seed=0) for pid in ("G2", "G3", "G4", "G8")]
        ref = {}
        phases += [
            ("solve", lambda: ref.setdefault("g2", phase_solve(g2))),
            ("kernels", lambda: phase_kernels(reduced[0], g2, ref["g2"])),
            ("service", lambda: phase_service(reduced)),
        ]
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except SmokeFailure as e:
            print(f"chip_smoke: phase {name} failed: {e}", file=sys.stderr)
            return 1
        log(f"[{name}] phase done in {_ms(t0):.1f} ms")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
