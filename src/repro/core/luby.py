"""Luby's randomized parallel MIS (paper Algorithm 1) — the classical baseline.

Fresh uniform priorities every round; three phases per round exactly as the
paper states them.  Runs as a single `lax.while_loop`, so the whole algorithm
is one XLA program.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.spmv import neighbor_any_segment, neighbor_max_segment
from repro.graphs.graph import Graph


class MISResult(NamedTuple):
    in_mis: jnp.ndarray   # (n,) bool
    rounds: jnp.ndarray   # int32 — rounds to convergence
    converged: jnp.ndarray  # bool — False iff max_rounds hit
    # float32 — stream entries the round loop read over what the full
    # streams would have read in the same rounds (core.compaction); None
    # where no ladder ran
    stream_share: Optional[jnp.ndarray] = None


def luby_mis(g: Graph, key: jax.Array, *, max_rounds: int = 1024) -> MISResult:
    n = g.n_nodes

    def cond(state):
        alive, _, rnd = state
        return jnp.any(alive) & (rnd < max_rounds)

    def body(state):
        alive, in_mis, rnd = state
        # Phase 1: fresh random priorities (ties vanishingly rare; a tie only
        # delays both vertices one round, never breaks independence).
        p = jax.random.randint(
            jax.random.fold_in(key, rnd), (n,), 0, jnp.iinfo(jnp.int32).max,
            dtype=jnp.int32,
        )
        max_np = neighbor_max_segment(g, p, alive)
        cand = alive & (p > max_np)
        # Phase 2: who neighbours a candidate?
        hit = neighbor_any_segment(g, cand)
        # Phase 3: own-state-only update.
        in_mis = in_mis | cand
        alive = alive & ~cand & ~hit
        return alive, in_mis, rnd + 1

    alive0 = jnp.ones((n,), dtype=bool)
    in_mis0 = jnp.zeros((n,), dtype=bool)
    alive, in_mis, rounds = jax.lax.while_loop(
        cond, body, (alive0, in_mis0, jnp.int32(0))
    )
    return MISResult(in_mis=in_mis, rounds=rounds, converged=~jnp.any(alive))
