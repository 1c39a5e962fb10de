"""Multi-chip TC-MIS: row-partitioned BSR + bit-packed frontier gathers,
verified bit-identical to the single-device run — both reached through the
same `Solver` front door (`placement` is the only thing that changes).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_mis.py
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import numpy as np

from repro.api import PlanCache, Solver, SolveOptions
from repro.core import is_valid_mis
from repro.graphs.generators import GRAPH_SUITE


def main() -> None:
    n_dev = len(jax.devices())
    g = GRAPH_SUITE["G5"].make(10_000, 0)  # web-Google stand-in

    plans = PlanCache(tile_size=64)        # one BSR build, both placements
    sharded = Solver(SolveOptions(heuristic="h3", tile_size=64,
                                  placement="sharded", bitpack=True),
                     plans=plans)
    plan = sharded.plan(g)
    print(f"|V|={g.n_nodes:,}; {plan.tiled.n_tiles:,} tiles over {n_dev} shards "
          f"(routing: {sharded.route(plan)})")

    res = sharded.solve(plan)
    print(f"distributed: |MIS|={res.mis_size:,} rounds={res.rounds}"
          f" valid={is_valid_mis(g, jax.numpy.asarray(res.in_mis))}"
          f" shards={res.stats['n_shards']}")

    local = Solver(SolveOptions(heuristic="h3", engine="tiled_ref",
                                tile_size=64, placement="local"),
                   plans=plans).solve(plan)
    print("matches single-device bit-for-bit:",
          bool(np.all(res.in_mis == local.in_mis)))


if __name__ == "__main__":
    main()
