"""TC-MIS (paper Algorithm 2): the three-phase, tile-accelerated MIS.

Per round:

  ① priority max over live neighbours → candidate vector C
     (`phase1='segment'` is paper-faithful — the paper runs ① on CUDA cores;
      `phase1='tiled'` is our beyond-paper variant that reuses the BSR
      schedule, DESIGN.md §6.1)
  ② N_c = A × C as block-tiled SpMV — the paper's tensor-core kernel.
     Lane-packing puts C in lane 0 and the alive mask in lane 1 of the
     (T, L) right-hand side, so one MXU pass also yields live-neighbour
     counts (free on TPU; DESIGN.md §6.2).
  ③ own-state-only update: candidates join Δm, their neighbours (N_c>0) die.
     Lock-free by construction — here that is literal: it is one elementwise
     `where`, fused by XLA into the SpMV epilogue (DESIGN.md §6.3), or run
     INSIDE the kernel epilogue by the `fused_pallas` engine.

How a round executes is delegated to a `RoundEngine` (core.engine): the
`backend` config field names an engine from the registry.  Both drivers here
— the jitted `lax.while_loop` production entry and the python-stepped
profiler twin — run the SAME engine round body.

**Public entry points live in `repro.api`** (DESIGN.md §10): `Solver.solve`
wraps `_tc_mis_impl`, `Solver.profile` wraps `_run_phases_impl`.  The
module-level `tc_mis` / `run_phases` and `TCMISConfig` remain as thin
deprecated shims for pre-API callers.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.engine import (
    EngineContext,
    MISRoundState,
    get_engine,
    make_bitwise_context,
    phase3_update,
    phase3_update_bits,
    resolve_frontier,
    round_increment,
)
from repro.core.heuristics import Priorities, make_priorities
from repro.core.luby import MISResult
from repro.core.spmv import _NEG
from repro.core.tiling import (
    BlockTiledGraph,
    pack_frontier_words,
    pack_vertex_vector,
    unpack_frontier_words,
)
from repro.graphs.graph import Graph
from repro.obs.rounds import TELEMETRY_COLS, TELEMETRY_FILL

# back-compat alias: the round state now lives with the engine layer
TCMISState = MISRoundState


@dataclasses.dataclass(frozen=True)
class TCMISConfig:
    """DEPRECATED algorithm-knob bundle — superseded by
    `repro.api.SolveOptions` (which adds preprocessing + placement policy).
    Kept as the shim config for `tc_mis`/`run_phases` callers."""
    heuristic: str = "h3"        # h1 | h2 | h3 | ecl
    lanes: int = 8               # RHS lane count (128 on TPU; 8 keeps CPU cheap)
    backend: str = "ref"         # engine name: segment | tiled_ref |
                                 # tiled_pallas | fused_pallas (ref/pallas ok)
    phase1: str = "segment"      # segment (paper-faithful) | tiled (beyond-paper)
    skip_dma: bool = False       # empty-C slabs also skip their HBM read
    max_rounds: int = 1024
    frontier: str = "auto"       # auto | dense | bitwise (DESIGN.md §13)


def _pad_priorities(pri: Priorities, tiled: BlockTiledGraph) -> Priorities:
    n_pad = tiled.n_padded - (pri.select.shape[0])
    pad = lambda x: jnp.pad(x, (0, n_pad), constant_values=int(_NEG)) if n_pad else x
    return Priorities(
        select=pad(pri.select),
        resolve=None if pri.resolve is None else pad(pri.resolve),
    )


def _setup(
    g: Graph,
    tiled: BlockTiledGraph,
    key: jax.Array,
    config,
    priorities: Priorities | None = None,
    alive0: jnp.ndarray | None = None,
    col_gate: jnp.ndarray | None = None,
    member_rounds: bool = False,
    in_mis0: jnp.ndarray | None = None,
):
    """Shared run prologue: engine resolution, context, priorities, state₀.

    `config` is any options bundle with backend/heuristic/lanes/phase1/
    skip_dma/max_rounds (`repro.api.SolveOptions` or the `TCMISConfig` shim).

    `priorities` / `alive0` / `col_gate` are the batch-serving overrides
    (repro.api.Solver.solve_many): a block-diagonal packed graph must carry
    *per-graph* priorities (each member graph's own key and degree statistics
    — Eq. 1's d̄ is per-graph, so batch-wide `make_priorities` would change
    every member's solution) and must start padding-slot vertices dead so
    they never enter the MIS or cost a round.  When `priorities` is given,
    `key` is unused; vectors may be `n_nodes`- or `n_padded`-long.

    `member_rounds` switches `rnd` to the per-vertex counting mode
    (core.engine.MISRoundState): each vertex's counter advances only while
    it is alive, so a packed member's own convergence round is the max over
    its slot — not the batch-slowest.

    `in_mis0` is the warm-start override (repro.dyngraph.repair): seed the
    MIS set with a prior solution so the convergence loop only works the
    dirty frontier the caller left alive.  Callers guarantee `in_mis0` is
    independent in `g` and disjoint from `alive0` — the engine preserves
    both invariants but never re-checks them.  In bitwise runs `alive0`/
    `in_mis0` may arrive already packed as (nbc, W) uint32 words (the repair
    path hands its warm state over without densifying) — detected by
    shape/dtype.
    """
    engine = get_engine(config.backend)
    if priorities is None:
        priorities = make_priorities(config.heuristic, key, g.n_nodes, g.degrees())
    pri = _pad_priorities(priorities, tiled)
    frontier = resolve_frontier(
        config, engine, storage=tiled.storage, member_rounds=member_rounds
    )
    bits = None
    if frontier == "bitwise":
        # plane stacks only where the plane-scan kernel compiles for real
        # (TPU); in interpret mode the clz formulation needs no planes.
        from repro.kernels.ops import resolve_interpret

        planes = engine.plane_kernel_nbr_max and not resolve_interpret()
        # hybrid runs walk only the compacted dense partition with the tile
        # machinery — build the sorted-tile / word structures over it, not
        # the full list (the sparse tail never touches them, DESIGN.md §16)
        bits_tiled = tiled
        if engine.supports_hybrid and tiled.partition is not None:
            bits_tiled = tiled.partition.dense
        bits = make_bitwise_context(bits_tiled, pri, planes=planes)
    ctx = EngineContext(
        g=g, tiled=tiled, cfg=config, col_gate=col_gate,
        frontier=frontier, bits=bits,
    )
    if alive0 is None:
        alive0 = jnp.ones((g.n_nodes,), dtype=bool)

    def as_state_vec(x):
        """Vertex mask → the state representation of this run: (n_padded,)
        bool dense, or (nbc, W) uint32 words when the frontier is bitwise.
        Already-packed inputs pass through."""
        if getattr(x, "ndim", 0) == 2 and x.dtype == jnp.uint32:
            return x
        padded = pack_vertex_vector(x.astype(bool), tiled)
        if frontier == "bitwise":
            return pack_frontier_words(padded, tiled.tile_size)
        return padded

    rnd0 = (
        jnp.zeros((tiled.n_padded,), dtype=jnp.int32)
        if member_rounds
        else jnp.int32(0)
    )
    zero_mis = jnp.zeros((tiled.n_padded,), dtype=bool)
    state0 = MISRoundState(
        alive=as_state_vec(alive0),
        in_mis=as_state_vec(zero_mis if in_mis0 is None else in_mis0),
        rnd=rnd0,
    )
    return engine, ctx, pri, state0


def _result(final: MISRoundState, g: Graph, tiled: BlockTiledGraph) -> MISResult:
    """Run epilogue — and, for bitwise runs, THE single sanctioned unpack
    site on the solve path: packed `in_mis` words densify here, after the
    convergence loop, never inside it (tools/ci_guards.py allowlists this
    function by name)."""
    in_mis = final.in_mis
    if getattr(in_mis, "ndim", 0) == 2 and in_mis.dtype == jnp.uint32:
        in_mis = unpack_frontier_words(in_mis, tiled.tile_size)
    rounds = final.rnd[: g.n_nodes] if getattr(final.rnd, "ndim", 0) else final.rnd
    return MISResult(
        in_mis=in_mis[: g.n_nodes],
        rounds=rounds,
        converged=~jnp.any(final.alive),
    )


def _tc_mis_impl(
    g: Graph,
    tiled: BlockTiledGraph,
    key: jax.Array,
    config,
    *,
    priorities: Priorities | None = None,
    alive0: jnp.ndarray | None = None,
    col_gate: jnp.ndarray | None = None,
    member_rounds: bool = False,
    in_mis0: jnp.ndarray | None = None,
) -> MISResult:
    """Run TC-MIS to convergence inside one `lax.while_loop`.

    The production driver behind `repro.api.Solver.solve`/`solve_many`; the
    whole function is jit-compatible with `config` static, which is how the
    Solver amortises ONE compiled dispatch per shape bucket over every
    request in a batch.  With `member_rounds`, `MISResult.rounds` is the
    per-vertex settle-round vector (sliced to real vertices) instead of the
    global round count.  `alive0`+`in_mis0` together are the warm-start
    seam (`repro.dyngraph.repair`): an already-converged warm state runs
    ZERO rounds — the while_loop condition fails on entry.
    """
    engine, ctx, pri, state0 = _setup(
        g, tiled, key, config, priorities, alive0, col_gate, member_rounds,
        in_mis0,
    )

    def cond(state: MISRoundState):
        return jnp.any(state.alive) & (jnp.max(state.rnd) < config.max_rounds)

    if not getattr(config, "telemetry", False):
        final = jax.lax.while_loop(
            cond, lambda s: engine.step(ctx, pri, s), state0
        )
        return _result(final, g, tiled)

    # Telemetry run (SolveOptions.telemetry; the deprecated TCMISConfig
    # never sets it): the loop carries a fixed-shape (max_rounds, K) int32
    # buffer, round r writes row r via `engine.step_with_stats`, and the
    # return becomes (result, buffer) — ONE device→host transfer when the
    # caller materialises the buffer at the epilogue (RoundTrace.from_buffer).
    # The flag is static under jit, so the telemetry-off program above stays
    # the byte-exact pre-telemetry while_loop (DESIGN.md §14).
    buf0 = jnp.full(
        (int(config.max_rounds), TELEMETRY_COLS), TELEMETRY_FILL, jnp.int32
    )

    def body(carry):
        s, buf = carry
        new, row = engine.step_with_stats(ctx, pri, s)
        # max(rnd) is the current round index in BOTH counting modes: a
        # scalar rnd counts rounds directly, and in member_rounds mode every
        # currently-alive vertex has incremented in every prior round (alive
        # is monotone per vertex), so the max over vertices is the round
        # index while anything is alive — which `cond` guarantees here.
        return new, buf.at[jnp.max(s.rnd)].set(row)

    final, buf = jax.lax.while_loop(
        lambda c: cond(c[0]), body, (state0, buf0)
    )
    return _result(final, g, tiled), buf


# --------------------------------------------------------------------------
# instrumented twin (python-stepped) for the Fig.-1 phase profiler
# --------------------------------------------------------------------------

def _run_phases_impl(  # repro-lint: disable=RPR010,RPR011 host-stepped profiler twin: per-phase wall timing requires sync
    g: Graph,
    tiled: BlockTiledGraph,
    key: jax.Array,
    config,
    warmup: bool = True,
    *,
    priorities: Priorities | None = None,
    alive0: jnp.ndarray | None = None,
    col_gate: jnp.ndarray | None = None,
    member_rounds: bool = False,
) -> Tuple[MISResult, Dict[str, float]]:
    """Same engine round body, stepped from python with per-phase timers.

    The driver behind `repro.api.Solver.profile` — benchmarks only; the
    jitted `_tc_mis_impl` is the production entry.
    Returns (result, {"phase1": s, "phase2": s, "phase3": s, "rounds": k}).
    For fused engines the ②+③ kernel pass is charged to phase2 and the
    residual state merge to phase3.
    """
    engine, ctx, pri, state0 = _setup(
        g, tiled, key, config, priorities, alive0, col_gate, member_rounds
    )

    # Hybrid runs always profile as SPLIT ②+③: fused engines demote under a
    # partition (the in-kernel ③ cannot merge the sparse-tail hits), exactly
    # like the production `step_hybrid` path.
    hybrid = engine.supports_hybrid and ctx.tiled.partition is not None
    fused_call = engine.fused and not hybrid
    if hybrid:
        dctx = dataclasses.replace(ctx, tiled=ctx.tiled.partition.dense)
    if ctx.frontier == "bitwise":
        # the packed-frontier round body, split at the same phase seams
        if hybrid:
            p1 = jax.jit(
                lambda alive: engine._hybrid_candidates_bits(ctx, dctx, pri, alive)
            )
            p2 = jax.jit(
                lambda cand, alive: engine._dense_hits_bits(
                    dctx, cand, alive, engine.col_flags_bits(ctx, cand)
                )
                | engine._sparse_hits_bits(ctx, cand)
            )
            p3 = jax.jit(phase3_update_bits)
        elif fused_call:
            p1 = jax.jit(lambda alive: engine.phase1_candidates_bits(ctx, pri, alive))
            p2 = jax.jit(
                lambda cand, alive: engine.fused_step_bits(
                    ctx, cand, alive, engine.col_flags_bits(ctx, cand)
                )
            )
            p3 = jax.jit(
                lambda state, out, inc: MISRoundState(
                    alive=out[0], in_mis=state.in_mis | out[1], rnd=state.rnd + inc
                )
            )
        else:
            p1 = jax.jit(lambda alive: engine.phase1_candidates_bits(ctx, pri, alive))
            p2 = jax.jit(
                lambda cand, alive: engine.phase2_hits(
                    ctx, cand, alive, engine.col_flags_bits(ctx, cand)
                )
            )
            p3 = jax.jit(phase3_update_bits)
    else:
        if hybrid:
            p1 = jax.jit(
                lambda alive: engine._hybrid_candidates(ctx, dctx, pri, alive)
            )
            p2 = jax.jit(
                lambda cand, alive: engine._dense_phase2(
                    dctx, cand, alive, engine.col_flags(dctx, cand, alive)
                )
                + engine._sparse_counts(ctx, cand)
            )
            p3 = jax.jit(phase3_update)
        elif fused_call:
            p1 = jax.jit(lambda alive: engine.phase1_candidates(ctx, pri, alive))
            p2 = jax.jit(
                lambda cand, alive: engine.fused_step(
                    ctx, cand, alive, engine.col_flags(ctx, cand, alive)
                )
            )
            p3 = jax.jit(
                lambda state, out, inc: MISRoundState(
                    alive=out[0], in_mis=state.in_mis | out[1], rnd=state.rnd + inc
                )
            )
        else:
            p1 = jax.jit(lambda alive: engine.phase1_candidates(ctx, pri, alive))
            p2 = jax.jit(
                lambda cand, alive: engine.phase2_counts(
                    ctx, cand, alive, engine.col_flags(ctx, cand, alive)
                )
            )
            p3 = jax.jit(phase3_update)

    def advance(state, cand, out):
        inc = round_increment(state)
        return p3(state, out, inc) if fused_call else p3(state, cand, out, inc)

    if warmup:  # compile outside the timers
        c = p1(state0.alive)
        out = p2(c, state0.alive)
        step = advance(state0, c, out)
        step.alive.block_until_ready()

    state = state0
    times = {"phase1": 0.0, "phase2": 0.0, "phase3": 0.0}
    rounds = 0
    while bool(jnp.any(state.alive)) and rounds < config.max_rounds:
        t0 = time.perf_counter()
        cand = p1(state.alive)
        cand.block_until_ready()
        t1 = time.perf_counter()
        out = p2(cand, state.alive)
        jax.block_until_ready(out)
        t2 = time.perf_counter()
        state = advance(state, cand, out)
        state.alive.block_until_ready()
        t3 = time.perf_counter()
        times["phase1"] += t1 - t0
        times["phase2"] += t2 - t1
        times["phase3"] += t3 - t2
        rounds += 1
    times["rounds"] = rounds
    result = _result(state, g, tiled)
    if not member_rounds:
        result = result._replace(rounds=jnp.int32(rounds))
    return result, times


# --------------------------------------------------------------------------
# deprecated shims — the pre-`repro.api` entry points
# --------------------------------------------------------------------------

def tc_mis(
    g: Graph,
    tiled: BlockTiledGraph,
    key: jax.Array,
    config: TCMISConfig = TCMISConfig(),
    *,
    priorities: Priorities | None = None,
    alive0: jnp.ndarray | None = None,
    col_gate: jnp.ndarray | None = None,
    member_rounds: bool = False,
) -> MISResult:
    """DEPRECATED: use `repro.api.Solver`.

    `Solver(SolveOptions(engine=..., tile_size=...)).solve(graph)` plans,
    routes and runs in one call; `Solver.solve_many` replaces the
    `priorities`/`alive0`/`col_gate` batch-kwarg spelling."""
    warnings.warn(
        "tc_mis(g, tiled, key, config) is deprecated; use repro.api: "
        "Solver(SolveOptions(engine=..., tile_size=...)).solve(graph) "
        "(solve_many for batches)",
        DeprecationWarning,
        stacklevel=2,
    )
    return _tc_mis_impl(
        g, tiled, key, config,
        priorities=priorities, alive0=alive0, col_gate=col_gate,
        member_rounds=member_rounds,
    )


def run_phases(
    g: Graph,
    tiled: BlockTiledGraph,
    key: jax.Array,
    config: TCMISConfig = TCMISConfig(),
    warmup: bool = True,
    *,
    priorities: Priorities | None = None,
    alive0: jnp.ndarray | None = None,
    col_gate: jnp.ndarray | None = None,
) -> Tuple[MISResult, Dict[str, float]]:
    """DEPRECATED: use `repro.api.Solver.profile(graph)`."""
    warnings.warn(
        "run_phases(...) is deprecated; use repro.api: "
        "Solver(SolveOptions(engine=...)).profile(graph)",
        DeprecationWarning,
        stacklevel=2,
    )
    return _run_phases_impl(
        g, tiled, key, config, warmup,
        priorities=priorities, alive0=alive0, col_gate=col_gate,
    )
