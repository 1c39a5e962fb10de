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
`backend` config field names an engine from the registry.  The jitted
`lax.while_loop` driver here owns only the convergence loop; the program
names its parts with `jax.named_scope` — `mis.init`, the round body's
`mis.p1`/`mis.p2`/`mis.p3`, `mis.result` (DESIGN.md §14) — so a device
trace splits by phase without a second, host-stepped driver.

**Public entry points live in `repro.api`** (DESIGN.md §10): `Solver.solve`
wraps `_tc_mis_impl`.  The module-level `tc_mis` and `TCMISConfig` remain
as thin deprecated shims for pre-API callers.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp

from repro.core import compaction
from repro.core.compaction import plan_ladder
from repro.core.engine import (
    EngineContext,
    MISRoundState,
    get_engine,
    make_bitwise_context,
    resolve_frontier,
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
from repro.obs.trace import SCOPE_COMPACT, SCOPE_INIT, SCOPE_P3, SCOPE_RESULT

# back-compat alias: the round state now lives with the engine layer
TCMISState = MISRoundState


@dataclasses.dataclass(frozen=True)
class TCMISConfig:
    """DEPRECATED algorithm-knob bundle — superseded by
    `repro.api.SolveOptions` (which adds preprocessing + placement policy).
    Kept as the shim config for `tc_mis` callers."""
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


def _result(
    final: MISRoundState, g: Graph, tiled: BlockTiledGraph, stream_share
) -> MISResult:
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
        stream_share=stream_share,
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
    """Run TC-MIS to convergence inside `lax.while_loop`s.

    The production driver behind `repro.api.Solver.solve`/`solve_many`; the
    whole function is jit-compatible with `config` static, which is how the
    Solver amortises ONE compiled dispatch per shape bucket over every
    request in a batch.  With `member_rounds`, `MISResult.rounds` is the
    per-vertex settle-round vector (sliced to real vertices) instead of the
    global round count.  `alive0`+`in_mis0` together are the warm-start
    seam (`repro.dyngraph.repair`): an already-converged warm state runs
    ZERO rounds — the while_loop condition fails on entry.

    Where the route reads a per-half-edge stream longer than the ladder's
    floor, the rounds run on the compaction ladder (`core.compaction`,
    DESIGN.md §18): one loop per rung, each carrying the live mask of the
    streams it compacts on exit.  Otherwise the one loop carries the state
    alone.  Answers are the same either way.

    Telemetry run (SolveOptions.telemetry; the deprecated TCMISConfig never
    sets it): the loops also carry a fixed-shape (max_rounds, K) int32
    buffer, round r writes row r via `engine.step_with_stats`, and the
    return becomes (result, buffer) — ONE device→host transfer when the
    caller materialises the buffer at the epilogue (RoundTrace.from_buffer).
    The flag is static under jit, so the telemetry-off program holds no
    value of the buffer (DESIGN.md §14).
    """
    with jax.named_scope(SCOPE_INIT):
        engine, ctx, pri, state0 = _setup(
            g, tiled, key, config, priorities, alive0, col_gate, member_rounds,
            in_mis0,
        )
    telemetry = bool(getattr(config, "telemetry", False))

    def cond(state: MISRoundState):
        return jnp.any(state.alive) & (jnp.max(state.rnd) < config.max_rounds)

    def advance(c, carry):
        """One round on the context `c` of a rung: (state,) or, with
        telemetry, (state, buffer)."""
        if not telemetry:
            return (engine.step(c, pri, carry[0]),)
        s, buf = carry
        new, row = engine.step_with_stats(c, pri, s)
        # max(rnd) is the current round index in BOTH counting modes: a
        # scalar rnd counts rounds directly, and in member_rounds mode every
        # currently-alive vertex has incremented in every prior round (alive
        # is monotone per vertex), so the max over vertices is the round
        # index while anything is alive — which `cond` guarantees here.
        with jax.named_scope(SCOPE_P3):
            return new, buf.at[jnp.max(s.rnd)].set(row)

    carry = (state0,)
    if telemetry:
        with jax.named_scope(SCOPE_INIT):
            carry += (jnp.full(
                (int(config.max_rounds), TELEMETRY_COLS), TELEMETRY_FILL,
                jnp.int32,
            ),)

    carry, share = _run_ladder(
        plan_ladder(engine, ctx), engine, ctx, pri, carry, cond, advance,
        warm=in_mis0 is not None,
    )
    with jax.named_scope(SCOPE_RESULT):
        result = _result(carry[0], g, tiled, share)
    return (result, carry[1]) if telemetry else result


def _run_ladder(ladder, engine, ctx, pri, carry, cond, advance, *, warm: bool):
    """The rounds on the compaction ladder (DESIGN.md §18).  Rung k runs
    while `cond` holds and some stream's live entries exceed its capacity
    at rung k+1, or while a vertex whose priority is at or below the mask
    value `_NEG` is alive; those streams are then compacted into rung k+1
    and the state carries on.  The last rung runs to convergence; a ladder
    of one rung is the single loop, carrying the state alone.

    The second condition keeps the answers exact: a dropped half-edge has
    a dead end, and a dead sender gives ①'s max `_NEG` at its receiver,
    which only a priority at or below `_NEG` can tell from no neighbour at
    all (H3's `resolve` rank of a hub, -deg·n - id).  Once no such vertex
    is alive, no comparison of the round can see the dropped entries.

    `warm` (the repair seam's `in_mis0` was given) computes the live masks
    before rung 0, so a warm start, whose dead vertices hold edges, enters
    the rung its live entries fit.  Otherwise every half-edge is live at
    round 0 — the batcher's `alive0` kills only padding slots, which hold
    no edge — so rung 0 starts from an all-true mask and runs round 0
    first.  Returns the final carry and the stream share, from the round
    count at each rung exit: no round reduces anything for it."""
    streams = compaction.full_streams(ctx)
    rungs = ladder.rungs
    widths, exits = [], []
    masks = low = None
    for k, caps in enumerate(rungs):
        c = compaction.bind(ctx, streams)
        widths.append(compaction.stream_width(engine, c))
        if k + 1 == len(rungs):
            carry = jax.lax.while_loop(
                lambda cr: cond(cr[0]), lambda cr: advance(c, cr), carry
            )
            exits.append(jnp.max(carry[0].rnd))
            break
        nxt = rungs[k + 1]
        shrink = tuple(s for s in caps if nxt[s] < caps[s])
        if masks is None:
            with jax.named_scope(SCOPE_COMPACT):
                low = compaction.low_priority(pri, carry[0].alive, ctx.tiled)
                masks = compaction.live_masks(
                    streams, shrink, carry[0].alive, ctx.tiled
                ) if warm else tuple(
                    jnp.ones((caps[s],), jnp.bool_) for s in shrink
                )

        def rung_cond(cr, shrink=shrink, nxt=nxt):
            over = [jnp.sum(m, dtype=jnp.int32) > nxt[s]
                    for s, m in zip(shrink, cr[-1])]
            over.append(jnp.any((cr[0].alive & low) != 0))
            return cond(cr[0]) & functools.reduce(jnp.logical_or, over)

        def rung_body(cr, c=c, cur=dict(streams), shrink=shrink):
            out = advance(c, cr[:-1])
            with jax.named_scope(SCOPE_COMPACT):
                live = compaction.live_masks(cur, shrink, out[0].alive, c.tiled)
            return (*out, live)

        *carry, masks = jax.lax.while_loop(rung_cond, rung_body, (*carry, masks))
        carry = tuple(carry)
        exits.append(jnp.max(carry[0].rnd))
        with jax.named_scope(SCOPE_COMPACT):
            counts = [jnp.sum(m, dtype=jnp.int32) for m in masks]
            for s, m in zip(shrink, masks):
                streams[s] = compaction.compact(
                    m, streams[s], nxt[s], compaction.sentinel(s, ctx)
                )
            if ladder.shared:
                streams[compaction.TAIL] = compaction.shared_tail(
                    streams[compaction.EDGES], ctx
                )
            # the compacted entries are the live ones, in the first slots
            after = rungs[k + 2] if k + 2 < len(rungs) else nxt
            masks = tuple(
                jnp.arange(nxt[s], dtype=jnp.int32) < n
                for s, n in zip(shrink, counts) if after[s] < nxt[s]
            )
    with jax.named_scope(SCOPE_RESULT):
        rounds = exits[-1]
        read = sum(
            (done - before) * jnp.float32(w)
            for done, before, w in zip(exits, [jnp.int32(0)] + exits[:-1], widths)
        )
        full = rounds * jnp.float32(widths[0])
        share = jnp.where(full > 0, read / jnp.maximum(full, 1.0), 1.0)
    return carry, share.astype(jnp.float32)


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
