#!/usr/bin/env python3
"""Where one cell's solve time goes, by the program's own names: the plan
build's stages and the round loop's phases and paths, on a TPU.

    python3 bench/phases.py --workload <cell> --seed <n> [<n> ...] [--solves 3]

Run from the root of a checkout.  It sets the cell up as `bench/run.py`
does (same graph, options, keys and compile cache), but plans under a
`repro.obs.Trace`, then profiles `--solves` solves inside a `bench.window`
span and reduces the trace with `benchlib.scopes` under the op → scope map
of `Solver.program_scopes`.  Standard output ends with one JSON
line per seed (the seed orders the keys, as in a run); times per round
are over the rounds the traced solves ran.  It is not a run of the
benchmark: nothing here is a metric of `BENCHMARK.json`.
"""
import argparse
import glob
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import scopes, trace  # noqa: E402
from benchlib.harness import configure_compile_cache  # noqa: E402
from benchlib.spec import ROOT, load_cell, load_module  # noqa: E402
from benchlib.workload import Workload  # noqa: E402


def main(argv) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, nargs="+", required=True)
    p.add_argument("--solves", type=int, default=3)
    args = p.parse_args(argv)
    cell = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    configure_compile_cache(ROOT)

    import jax
    from repro.api import SolveOptions, Solver
    from repro.obs import Trace

    if jax.devices()[0].platform != "tpu":
        print("phases: needs a TPU", file=sys.stderr)
        return 1
    entry = cell.config["system"]["entry"]
    system = load_module(cell.bench / "systems" / f"{entry}.py")
    workload = Workload(cell, args.seed[0])
    graph = system.program_graph(workload.graph())
    solver = Solver(SolveOptions(**cell.config["system"].get("options", {})))
    plan_trace = Trace()
    plan = solver.plan(graph, trace=plan_trace)
    for k in workload.warm_keys():
        solver.solve(plan, key=jax.random.key(k))
    t0 = time.perf_counter()
    op_scopes = solver.program_scopes(plan)
    scopes_s = time.perf_counter() - t0
    plan_stages = {
        "plan_s": plan_trace.total_ms("solver.plan") / 1e3,
        "plan_key_s": plan_trace.total_ms("plan.key") / 1e3,
        "plan_tiles_s": plan_trace.total_ms("plan.tiles") / 1e3,
        "plan_partition_s": plan_trace.total_ms("plan.partition") / 1e3,
        "program_scopes_s": scopes_s, "scoped_ops": len(op_scopes),
    }
    for seed in args.seed:
        out = profile(solver, plan, op_scopes, Workload(cell, seed), args.solves)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "solves": args.solves,
                          "device": jax.devices()[0].device_kind,
                          **plan_stages, **out}), flush=True)
    return 0


def profile(solver, plan, op_scopes, workload, solves):
    """Profile `solves` solves of the workload's first keys (one more
    first, which the profiler's start-up slows) and split the window."""
    import jax

    d = tempfile.mkdtemp(prefix="bench-phases-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    rounds, walls = 0, []
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        solver.solve(plan, key=jax.random.key(workload.key(solves)))
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            for i in range(solves):
                t = time.perf_counter()
                with trace.span("solve"):
                    res = solver.solve(plan, key=jax.random.key(workload.key(i)))
                walls.append(time.perf_counter() - t)
                rounds += res.rounds
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))[0]
        summary = trace.reduce_file(path)
        device_ops, host_spans = scopes.read_xplane(path)
        split = scopes.reduce_scopes(device_ops, host_spans, op_scopes)
    finally:
        shutil.rmtree(d, ignore_errors=True)

    per_round = lambda s: 1e3 * s / max(rounds, 1)
    phases = {f"phase{i}_ms": per_round(split.seconds(f"mis.p{i}"))
              for i in (1, 2, 3)}
    busy = summary.busy_s
    return {
        "rounds": rounds, "solve_ms": [1e3 * w for w in walls],
        "busy_s": busy, "window_s": summary.window_s,
        "idle_pct": summary.idle_pct,
        **phases,
        "edge_ms": per_round(split.path_seconds("edge")),
        "tile_ms": per_round(split.path_seconds("tile")),
        "phases_share_of_busy":
            sum(phases.values()) * rounds / 1e3 / busy if busy else None,
        "unscoped_share_of_busy":
            split.scope_s.get(scopes.UNSCOPED, 0.0) / busy if busy else None,
        "scope_s": split.scope_s,
        "idle_gaps": split.idle_gaps,
    }


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
