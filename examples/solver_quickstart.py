"""The front door in one screen: `Plan` / `SolveOptions` / `Solver`.

Every MIS execution path of this repo — single graphs, batched serving
workloads, and (on multi-device hosts) the sharded path — is reached
through the same three nouns (DESIGN.md §10).

    PYTHONPATH=src python examples/solver_quickstart.py
"""

from repro.api import Plan, Solver, SolveOptions, choose_tile_size
from repro.graphs.generators import erdos_renyi, grid2d, powerlaw


def main() -> None:
    g = erdos_renyi(600, avg_deg=6.0, seed=0)

    # -- one graph, default options (auto tile size, auto placement) -------
    solver = Solver(SolveOptions(engine="tiled_ref"))   # jnp oracle: CPU-honest
    res = solver.solve(g)
    print(f"solve:       |V|={g.n_nodes} -> |MIS|={res.mis_size} "
          f"rounds={res.rounds} placement={res.placement} "
          f"T={res.plan.tile_size} (auto-T policy: "
          f"{choose_tile_size(g.n_nodes, g.n_edges)})")

    # -- a serving-style workload: ONE dispatch for the whole batch --------
    batch = [grid2d(6, 6), powerlaw(48, seed=1), erdos_renyi(64, seed=2),
             erdos_renyi(24, avg_deg=3.0, seed=3)]
    many = Solver(SolveOptions(engine="tiled_ref", tile_size=16))
    results = many.solve_many(batch)
    print(f"solve_many:  {len(results)} graphs, bucket "
          f"{results[0].stats['bucket']}, per-member rounds "
          f"{[r.rounds for r in results]}")
    assert many.solve_many([]) == []            # no bucket for nothing
    assert many.solve_many([batch[0]])[0].placement == "local"  # or a singleton

    # -- plans are immutable, content-addressed artifacts ------------------
    plan = Plan.build(g, tile_size=32)
    again = many.solve(plan)                     # a Plan routes like a Graph
    print(f"Plan.build:  key={plan.key[:12]}… T={plan.tile_size} "
          f"tiles={plan.tiled.n_tiles} |MIS|={again.mis_size}")

    # -- the compiled program names its phases (DESIGN.md §14) -------------
    scopes = solver.program_scopes(g)            # op name -> mis.* scope
    print(f"scopes:      {len(scopes)} ops in "
          f"{sorted(set(scopes.values()))}")

if __name__ == "__main__":
    main()
