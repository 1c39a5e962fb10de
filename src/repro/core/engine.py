"""The round-engine layer: one backend interface for every TC-MIS phase.

Every execution path of the system — the paper-faithful CC baseline, the jnp
tile oracle, the Pallas SpMV kernel, and the fused phase-②+③ kernel — is a
`RoundEngine`: an object that knows how to run one MIS round (DESIGN.md §4).
The driver (`core.tc_mis`) is engine-agnostic; it owns only the convergence
loop.  The API, examples and future backends (GPU Pallas) select engines
from the registry instead of hard-coding call sites — kernel selection is a
pluggable policy over one tiled schedule, the way BLEST/HC-SpMM treat their
kernel zoos.  (Bit-packed masks — once a forward reference here — are now a
first-class STORAGE axis, not a backend: every engine runs either tile
format, see DESIGN.md §11 and `core.tiling.STORAGES`.)

Registered engines:

  segment       gather/segment ops over the edge list (ECL-MIS analogue);
                the paper's CUDA-core baseline substrate.
  tiled_ref     pure-jnp BSR tile schedule — the oracle every kernel is
                validated against.
  tiled_pallas  phase ② on the Pallas SpMV kernel (MXU on TPU), phase ① per
                `cfg.phase1` (segment, or the beyond-paper tiled max kernel).
  fused_pallas  the fast path: phase ②+③ in ONE kernel pass — N_c never
                round-trips through HBM (DESIGN.md §6.3).

Per-round metadata: tiled engines compute **active block-column flags** from
the candidate vector each round (`block_col_flags`) so the kernels' empty-C
tile skip — `@pl.when` on the MXU op, and the `skip_dma` HBM-read skip — is
exercised live, not just in unit tests.  Skipping is exact: a tile whose
candidate slab is all-zero contributes exactly zero to N_c (lane 0).  Lanes
≥ 1 of a skipped column are dropped too, so the jnp oracle emulates the skip
by zeroing gated slabs — ref and kernel agree on ALL lanes.

This module also owns the raw-array tile operators (`tile_spmv`,
`tile_neighbor_max`) shared by `core.spmv` (padded-vector forms) and
`core.distributed` (shard-local slabs inside `shard_map`).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compaction import EDGES, TAIL, stream_width
from repro.core.tiling import (
    BlockTiledGraph,
    dense_tile_mask,
    gather_frontier_bits,
    pack_frontier_bits,
    pack_frontier_words,
    pack_priority_planes,
    pack_vertex_vector,
    sort_block_priorities,
    sorted_frontier_words,
    sorted_tile_bits,
    tiles_as_words,
)
from repro.graphs.graph import Graph
from repro.obs.trace import PATH_EDGE, PATH_TILE, SCOPE_P1, SCOPE_P2, SCOPE_P3

# Round-telemetry buffer columns (DESIGN.md §14).  obs.rounds is the owner
# of the layout and is deliberately numpy-only, so this import cannot cycle
# back into core.
from repro.obs.rounds import (
    COL_ALIVE,
    COL_FRONTIER,
    COL_SELECTED,
    COL_STREAM,
    COL_TILES_DENSE,
    COL_TILES_SKIPPED,
    COL_TILES_SPARSE,
    TELEMETRY_COLS,
)

_NEG = np.int32(-(1 << 30))  # numpy scalar: safe to create at import time under a trace


def _on_path(path: str):
    """Run a substrate function under its path sub-scope (`edge` for
    per-edge gather/scatter, `tile` for the tile schedule).  The round
    bodies open the phase scopes around it, so an op's name reads
    `mis.p2/edge/...` (DESIGN.md §14); scopes are op metadata only."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(path):
                return fn(*args, **kwargs)
        return scoped
    return wrap


_edge = _on_path(PATH_EDGE)
_tile = _on_path(PATH_TILE)


# --------------------------------------------------------------------------
# raw-array tile operators (shared: core.spmv, core.distributed, engines)
# --------------------------------------------------------------------------

def tile_spmv(
    tiles: jnp.ndarray,          # (nt, T, T) int8 | (nt, T, W) uint32 packed
    tile_rows: jnp.ndarray,      # (nt,) int32, non-decreasing
    tile_cols: jnp.ndarray,      # (nt,) int32
    rhs: jnp.ndarray,            # (nbc*T, L) float
    n_block_rows: int,
    tile_size: int,
    *,
    col_flags: jnp.ndarray | None = None,   # (nbc,) int32; None = all active
) -> jnp.ndarray:
    """N = A @ rhs over BSR tiles, pure jnp (the Pallas kernels' oracle).

    With `col_flags`, gated RHS slabs are zeroed before the contraction —
    the exact semantics of the kernel's `@pl.when` tile skip (a skipped tile
    contributes nothing on any lane).  Returns (n_block_rows*T, L) float32.
    """
    T = tile_size
    tiles = dense_tile_mask(tiles, T)        # bool mask, no int8 intermediate
    blocks = rhs.reshape(-1, T, rhs.shape[-1])
    gathered = blocks[tile_cols]                             # (nt, T, L)
    if col_flags is not None:
        gathered = gathered * col_flags[tile_cols][:, None, None].astype(
            gathered.dtype
        )
    prod = jnp.einsum(
        "ijk,ikl->ijl", tiles.astype(jnp.float32), gathered.astype(jnp.float32)
    )
    out = jax.ops.segment_sum(prod, tile_rows, num_segments=n_block_rows)
    return out.reshape(n_block_rows * T, rhs.shape[-1])


def tile_neighbor_max(
    tiles: jnp.ndarray,
    tile_rows: jnp.ndarray,
    tile_cols: jnp.ndarray,
    pm: jnp.ndarray,             # (nbc*T,) pre-masked priorities (_NEG = dead)
    n_block_rows: int,
    tile_size: int,
) -> jnp.ndarray:
    """Max_Np over the same BSR schedule (VPU work — max has no MXU form).

    Storage dispatch goes through `dense_tile_mask`, not `dense_tiles`: the
    packed form bit-extracts straight to the bool mask the `where` needs,
    with no int8 intermediate."""
    T = tile_size
    mask = dense_tile_mask(tiles, T)
    gathered = pm.reshape(-1, T)[tile_cols]                  # (nt, T)
    # tile (T,T) row v, col u: edge v->u.  masked max over columns.
    vals = jnp.where(mask, gathered[:, None, :], _NEG)       # (nt, T, T)
    tile_max = vals.max(axis=2)                              # (nt, T)
    out = jax.ops.segment_max(tile_max, tile_rows, num_segments=n_block_rows)
    return out.reshape(n_block_rows * T)


def block_col_flags(x: jnp.ndarray, tile_size: int) -> jnp.ndarray:
    """Per-block-column activity: (nbc*T,) vector -> (nbc,) int32 0/1 flags.

    The per-round metadata of the engine layer: a block-column is active iff
    any vertex in it carries a nonzero entry (the paper's empty-C test)."""
    return x.reshape(-1, tile_size).astype(bool).any(axis=1).astype(jnp.int32)


# --------------------------------------------------------------------------
# bitwise raw tile operators (DESIGN.md §13) — the packed-frontier round
# body's substrate.  Frontiers are (n_block_cols, W) uint32 words; nothing
# here densifies a frontier (tools/ci_guards.py).
# --------------------------------------------------------------------------

def tile_spmv_bits(
    tiles_bits: jnp.ndarray,     # (nt, T, W) uint32, standard bit layout
    tile_rows: jnp.ndarray,      # (nt,) int32, non-decreasing
    tile_cols: jnp.ndarray,      # (nt,) int32
    rhs_words: jnp.ndarray,      # (nbc, W) uint32 — packed candidate vector
    n_block_rows: int,
    tile_size: int,
    *,
    col_flags: jnp.ndarray | None = None,   # (nbc,) int32; None = all active
) -> jnp.ndarray:
    """② as pure word arithmetic: row v is hit iff popcount(tile_row_word &
    cand_word) ≠ 0 for any word — `(a & c) != 0` per word, OR over words.
    No f32 accumulator, no densify; returns (n_block_rows, W) packed hit
    words.  Exactly `tile_spmv(...)[:, 0] > 0` (the paper's N_c > 0 test —
    counts beyond 0/1 are only needed by lanes the pure-MIS round drops).

    `col_flags` zeroes gated candidate words before the AND — the same
    empty-C skip semantics as the dense path (a skipped column contributes
    no hits)."""
    gathered = rhs_words[tile_cols]                          # (nt, W)
    if col_flags is not None:
        gathered = gathered * col_flags[tile_cols][:, None].astype(jnp.uint32)
    hit = jnp.any((tiles_bits & gathered[:, None, :]) != 0, axis=2)  # (nt, T)
    acc = jax.ops.segment_max(
        hit.astype(jnp.uint32), tile_rows, num_segments=n_block_rows
    )
    return pack_frontier_bits(acc, tile_size)                # (nbr, W)


def tile_neighbor_max_bits(
    tiles_sorted: jnp.ndarray,       # (nt, T, W) uint32, MSB-first slot order
    tile_rows: jnp.ndarray,
    tile_cols: jnp.ndarray,
    p_sorted: jnp.ndarray,           # (nbc, T) int32, descending per block
    mask_sorted_words: jnp.ndarray,  # (nbc, W) uint32, sorted-slot layout
    n_block_rows: int,
    tile_size: int,
) -> jnp.ndarray:
    """① Max_Np over packed words: the priority-plane scan collapsed to one
    pass.  With each block-column's slots pre-sorted by descending priority
    (`sort_block_priorities` / `sorted_tile_bits`, once per solve), "iterate
    planes high→low, AND against the mask, fold" degenerates to "first set
    slot of (tile_row & mask)" — one AND + count-leading-zeros per word,
    then a gather from `p_sorted`.  Exact for any int32 priorities (the
    sort carries signed values; no bit-plane sign bias needed).  Returns
    (n_block_rows·T,) int32 values, `_NEG`-floored like the dense op."""
    T = int(tile_size)
    W = tiles_sorted.shape[-1]
    m = tiles_sorted & mask_sorted_words[tile_cols][:, None, :]   # (nt, T, W)
    first = jnp.full(m.shape[:2], jnp.int32(T), jnp.int32)        # T = none
    for w in range(W):
        word = m[..., w]
        pw = jnp.where(
            word != 0,
            jnp.int32(w * 32) + jax.lax.clz(word).astype(jnp.int32),
            jnp.int32(T),
        )
        first = jnp.minimum(first, pw)
    ps_g = p_sorted[tile_cols]                                    # (nt, T)
    idx = jnp.minimum(first, jnp.int32(T - 1))
    val = jnp.take_along_axis(ps_g, idx, axis=1)
    tile_max = jnp.where(first < T, val, jnp.int32(_NEG))
    out = jax.ops.segment_max(tile_max, tile_rows, num_segments=n_block_rows)
    return out.reshape(n_block_rows * T)


class SortedPriorityTiles(NamedTuple):
    """Per-priority-key setup artefact for the bitwise phase ①: the static
    block-column sort of one priority vector plus the adjacency re-packed in
    that slot order (built once per solve by `make_bitwise_context`)."""
    order: jnp.ndarray      # (nbc, T) int32 — descending-priority column order
    p_sorted: jnp.ndarray   # (nbc, T) int32 — priorities in slot order
    tiles: jnp.ndarray      # (nt, T, W) uint32 — MSB-first sorted-slot layout


class BitwiseContext(NamedTuple):
    """Everything the packed-frontier round body precomputes per solve.

    `tiles_bits` is the adjacency in standard word layout (phase ②);
    `select`/`resolve` carry the sorted-priority structures for the clz
    formulation of phase ①; `*_planes` are the explicit bit-plane stacks
    ((n_bits, nbc, W)) the Pallas plane-scan kernel consumes — built only
    when an engine asks for them (TPU runs), None otherwise."""
    tiles_bits: jnp.ndarray
    select: SortedPriorityTiles
    resolve: Optional[SortedPriorityTiles]
    select_planes: Optional[jnp.ndarray]
    resolve_planes: Optional[jnp.ndarray]


# H3 select keys are (q << 23) ≥ 0 with q ≤ 255 → 31 bits suffice; resolve
# keys are negative (-deg·n - id) → full 32 signed planes.
_SELECT_PLANE_BITS = 31
_RESOLVE_PLANE_BITS = 32


def make_bitwise_context(
    tiled: BlockTiledGraph, pri, *, planes: bool = False
) -> BitwiseContext:
    """Build the per-solve bitwise structures from static priorities.

    Priorities are fixed for the whole solve (only the alive/pending masks
    change per round), so the argsort, the column-permuted adjacency repack
    and the optional plane stacks are all one-time setup cost."""
    T = tiled.tile_size
    tiles_bits = tiles_as_words(tiled.tiles, T)

    def _sorted_for(p):
        order, p_sorted = sort_block_priorities(p, T)
        tiles_sorted = sorted_tile_bits(tiled.tiles, tiled.tile_cols, order, T)
        return SortedPriorityTiles(order, p_sorted, tiles_sorted)

    select = _sorted_for(pri.select)
    resolve = _sorted_for(pri.resolve) if pri.resolve is not None else None
    select_planes = resolve_planes = None
    if planes:
        select_planes = pack_priority_planes(
            pri.select, T, _SELECT_PLANE_BITS, signed=False
        )
        if pri.resolve is not None:
            resolve_planes = pack_priority_planes(
                pri.resolve, T, _RESOLVE_PLANE_BITS, signed=True
            )
    return BitwiseContext(tiles_bits, select, resolve, select_planes, resolve_planes)


FRONTIERS = ("auto", "dense", "bitwise")


def resolve_frontier(config, engine, *, storage: str, member_rounds: bool = False) -> str:
    """Resolve `SolveOptions.frontier` to the concrete mode a run uses.

    "auto" picks bitwise exactly when it is the fastest sound choice: a
    tile-schedule engine (`supports_bitwise`), the tiled phase ① (the
    segment phase ① would densify every round to reach the edge list),
    bitpack storage (word-AND needs word tiles), and a scalar round counter
    (per-member round vectors — the batched serving mode — need per-vertex
    alive increments the packed state does not expose).  An explicit
    "bitwise" on an engine that cannot honour it falls back to dense rather
    than erroring — mode is a performance knob, never a semantics knob."""
    mode = getattr(config, "frontier", "auto") or "auto"
    if mode == "auto":
        if (
            engine.supports_bitwise
            and not member_rounds
            and getattr(config, "phase1", "tiled") == "tiled"
            and storage == "bitpack"
        ):
            return "bitwise"
        return "dense"
    if mode == "bitwise" and (not engine.supports_bitwise or member_rounds):
        return "dense"
    return mode


# --------------------------------------------------------------------------
# engine state + context
# --------------------------------------------------------------------------

class MISRoundState(NamedTuple):
    """Per-round algorithm state; `alive`/`in_mis` are (n_padded,).

    `rnd` is polymorphic: a scalar int32 counts rounds globally (the classic
    single-graph run), while an (n_padded,) int32 vector — the batched
    serving mode — advances per vertex only while that vertex is alive, so
    `rnd[v]` converges to v's settle round and a packed member's OWN round
    count is the max over its slot (`round_increment`).  A member that
    converges early stops counting even though the batch keeps looping.
    """
    alive: jnp.ndarray    # bool
    in_mis: jnp.ndarray   # bool
    rnd: jnp.ndarray      # int32 — () global, or (n_padded,) per-vertex


class RoundFacts(NamedTuple):
    """What a round body computed on its way that the telemetry row reads.

    Every body returns it beside the new state; `step` drops it, so the
    telemetry-off program holds no op it did not hold before (DESIGN.md
    §14), and `step_with_stats` folds it into a row without a second pass.
    """
    cand: jnp.ndarray               # (n_padded,) bool, or (nbc, W) uint32 words
    flags: Optional[jnp.ndarray]    # (nbc,) col flags; None = no tile schedule
    tiled: BlockTiledGraph          # the tiling the skip counts walk (the
                                    # dense partition on hybrid routes)
    n_sparse_tiles: int             # COO-tail tiles; 0 off hybrid


@dataclasses.dataclass(frozen=True)
class EngineContext:
    """Immutable per-run bundle an engine closes over: the graph in both
    representations plus the run config (lanes, phase1 policy, skip_dma).

    `col_gate` is the batch-aware extension of the per-round flags: a static
    (n_block_cols,) 0/1 vector ANDed into every round's `col_flags`.  The
    block-diagonal batcher (`repro.serve_mis.batcher`) sets it to the
    real-vertex occupancy of each block column, so a padded bucket's empty
    trailing slots are pinned inactive from round 0 — the empty-C skip never
    depends on the candidate vector reaching those slots first.  `None`
    (single-graph runs) means "all columns may carry candidates".

    `frontier` is the RESOLVED mode ("dense" | "bitwise", never "auto" —
    see `resolve_frontier`); when bitwise, `bits` holds the per-solve packed
    structures and `MISRoundState.alive`/`in_mis` ride as (nbc, W) uint32
    words through the whole round loop (DESIGN.md §13).
    """
    g: Graph
    tiled: BlockTiledGraph
    cfg: Any   # options bundle: anything with backend/heuristic/lanes/
               # phase1/skip_dma/max_rounds (repro.api.SolveOptions, or the
               # legacy TCMISConfig shim)
    col_gate: Optional[jnp.ndarray] = None
    frontier: str = "dense"
    bits: Optional[BitwiseContext] = None


def round_increment(state: MISRoundState) -> jnp.ndarray:
    """The per-round `rnd` advance matching the state's counting mode.

    Scalar `rnd` ⇒ +1 (the driver's while_loop only runs while something is
    alive).  Vector `rnd` ⇒ +alive, so converged members / vertices stop
    counting — the per-member round-counter contract (MISRoundState)."""
    if getattr(state.rnd, "ndim", 0):
        return state.alive.astype(jnp.int32)
    return jnp.int32(1)


def phase3_update(
    state: MISRoundState,
    cand: jnp.ndarray,
    n_c: jnp.ndarray,
    rnd_inc: Optional[jnp.ndarray] = None,
) -> MISRoundState:
    """③ lock-free own-state update (paper's three rules, verbatim)."""
    return MISRoundState(
        alive=state.alive & ~cand & ~(n_c > 0),
        in_mis=state.in_mis | cand,
        rnd=state.rnd + (round_increment(state) if rnd_inc is None else rnd_inc),
    )


def phase3_update_bits(
    state: MISRoundState,
    cand_words: jnp.ndarray,
    hit_words: jnp.ndarray,
    rnd_inc: Optional[jnp.ndarray] = None,
) -> MISRoundState:
    """③ on packed words — the same three rules, 32 vertices per op.  The
    `N_c > 0` test is already folded into `hit_words` by the popcount SpMV,
    so the update is pure word logic: `alive & ~cand & ~hit`, `in_mis |
    cand`."""
    return MISRoundState(
        alive=state.alive & ~cand_words & ~hit_words,
        in_mis=state.in_mis | cand_words,
        rnd=state.rnd + (round_increment(state) if rnd_inc is None else rnd_inc),
    )


# --------------------------------------------------------------------------
# round telemetry reductions (DESIGN.md §14) — cheap folds over state the
# round body already holds; used only by `step_with_stats`, never by `step`
# --------------------------------------------------------------------------

def _popcount_words(words: jnp.ndarray) -> jnp.ndarray:
    """Σ popcount over a packed (nbc, W) uint32 frontier — scalar int32."""
    return jnp.sum(jax.lax.population_count(words).astype(jnp.int32))


def _count(mask: jnp.ndarray) -> jnp.ndarray:
    """popcount of a dense bool vector — scalar int32."""
    return jnp.sum(mask.astype(jnp.int32))


def _tiles_skipped(tiled, flags: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Tiles of `tiled` gated off this round by the empty-C col_flags skip:
    every tile whose block column carries flag 0.  Engines without flags
    (segment) skip nothing — 0."""
    if flags is None:
        return jnp.int32(0)
    n_tiles = int(tiled.tile_cols.shape[0])
    return jnp.int32(n_tiles) - jnp.sum(flags[tiled.tile_cols].astype(jnp.int32))


def _telemetry_row(
    alive, frontier, selected, skipped, tiles_dense, tiles_sparse, stream
) -> jnp.ndarray:
    """(TELEMETRY_COLS,) int32 row in the obs.rounds column layout."""
    vals = [None] * TELEMETRY_COLS
    vals[COL_ALIVE] = alive
    vals[COL_FRONTIER] = frontier
    vals[COL_SELECTED] = selected
    vals[COL_TILES_SKIPPED] = skipped
    vals[COL_TILES_DENSE] = tiles_dense
    vals[COL_TILES_SPARSE] = tiles_sparse
    vals[COL_STREAM] = stream
    return jnp.stack([jnp.asarray(v, jnp.int32) for v in vals])


def _tiles_routed_dense(
    tiled, skipped: jnp.ndarray, flags: Optional[jnp.ndarray]
) -> jnp.ndarray:
    """Tiles actually dispatched on the dense path this round: the stored
    list minus the flag-gated ones.  Engines with no tile schedule (flags
    None) route zero tiles."""
    if flags is None:
        return jnp.int32(0)
    return jnp.int32(int(tiled.tile_cols.shape[0])) - skipped


def _covered_rows(tiled) -> jnp.ndarray:
    """(n_block_rows,) bool — block rows owning at least one stored tile.

    The Pallas kernels write an output block only when a tile's grid step
    visits it (`@pl.when` zero-init on the row transition); a block row no
    tile maps to keeps whatever was in the output buffer.  Full tilings
    cover every row by construction, but the COMPACTED hybrid dense
    partition routinely has rows whose every tile went to the sparse tail —
    their lanes must be masked out before merging with the sparse half."""
    return tiled.row_starts[1:] > tiled.row_starts[:-1]


def _covered_vertices(tiled) -> jnp.ndarray:
    """`_covered_rows` expanded to the (n_padded,) vertex axis."""
    return jnp.repeat(_covered_rows(tiled), tiled.tile_size)


# --------------------------------------------------------------------------
# the engine interface
# --------------------------------------------------------------------------

class RoundEngine:
    """One MIS round as three pluggable pieces.

    Subclasses implement `_nbr_max` (phase ① substrate) and either
    `phase2_counts` (split engines) or `fused_step` (fused engines,
    `fused = True`).  `step` — the round every driver runs — is shared;
    `col_flags` is the per-round metadata hook.

    Tile-schedule engines additionally advertise `supports_bitwise` and
    implement the packed-frontier round body (`step_bits` et al., DESIGN.md
    §13): state rides as (nbc, W) uint32 words, phase ② is the popcount
    SpMV, phase ① the sorted-priority clz scan.  `round_body` picks one
    body per route from the resolved `ctx.frontier` and the partition;
    `step` and `step_with_stats` both run that one body.
    """

    name: str = "abstract"
    fused: bool = False
    supports_bitwise: bool = False
    # honours a `BlockTiledGraph.partition` (hybrid dense/sparse routing,
    # DESIGN.md §16) — tile-schedule engines only; the segment engine has
    # no tiles to split, so a partition is simply inert there
    supports_hybrid: bool = False
    # wants the (n_bits, nbc, W) plane stacks built at setup — only the
    # Pallas engines, whose bitwise phase ① can run the plane-scan kernel
    plane_kernel_nbr_max: bool = False

    # -- the per-half-edge streams (the compaction ladder, DESIGN.md §18) --
    def streams(self, ctx: EngineContext) -> Tuple[str, ...]:
        """The streams this route's round body reads once per half-edge:
        "edges" (`ctx.g`'s edge list) and "tail" (the hybrid COO tail).
        The round loop compacts them to the live half-edges between rounds."""
        return ()

    # -- phase ① ----------------------------------------------------------
    def _nbr_max(
        self, ctx: EngineContext, p: jnp.ndarray, mask: jnp.ndarray
    ) -> jnp.ndarray:
        raise NotImplementedError

    def phase1_candidates(
        self, ctx: EngineContext, pri, alive: jnp.ndarray
    ) -> jnp.ndarray:
        """① Max_Np + candidate test (+ H3 pending-set resolution)."""
        max_np = self._nbr_max(ctx, pri.select, alive)
        if pri.resolve is None:
            return alive & (pri.select > max_np)
        # H3: conflicts resolved on the pending set before C is finalised.
        pending = alive & (pri.select >= max_np)
        max_res = self._nbr_max(ctx, pri.resolve, pending)
        return pending & (pri.resolve > max_res)

    # -- per-round metadata -----------------------------------------------
    @_tile
    def col_flags(
        self, ctx: EngineContext, cand: jnp.ndarray, alive: jnp.ndarray
    ) -> Optional[jnp.ndarray]:
        """Active block-column flags for the empty-C tile skip.  Candidates
        drive phase ②'s lane 0, so a column block with no candidate is dead
        weight — flag it off.  Batched runs AND in the static `col_gate`
        (columns of empty bucket slots stay dark in every round).  Segment
        engines have no tiles to skip."""
        flags = block_col_flags(cand, ctx.tiled.tile_size)
        if ctx.col_gate is not None:
            flags = flags * ctx.col_gate.astype(flags.dtype)
        return flags

    # -- phase ② ----------------------------------------------------------
    def _pack_rhs(
        self, ctx: EngineContext, cand: jnp.ndarray, alive: jnp.ndarray
    ) -> jnp.ndarray:
        """Lane-packed RHS: lane 0 = C (the paper's SpMV input), lane 1 =
        alive (live-neighbour counts ride along free on a wide-lane TPU)."""
        rhs = jnp.zeros((ctx.tiled.n_padded, ctx.cfg.lanes), dtype=jnp.float32)
        rhs = rhs.at[:, 0].set(cand.astype(jnp.float32))
        rhs = rhs.at[:, 1].set(alive.astype(jnp.float32))
        return rhs

    def phase2_counts(
        self,
        ctx: EngineContext,
        cand: jnp.ndarray,
        alive: jnp.ndarray,
        col_flags: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        """② N_c = A × C.  Returns (n_padded,) float32."""
        raise NotImplementedError(f"{self.name} is a fused engine")

    # -- fused ②+③ --------------------------------------------------------
    def fused_step(
        self,
        ctx: EngineContext,
        cand: jnp.ndarray,
        alive: jnp.ndarray,
        col_flags: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """②+③ in one pass.  Returns (new_alive, mis_add) bool vectors."""
        raise NotImplementedError(f"{self.name} is a split engine")

    # -- bitwise round body (packed-frontier engines only) -----------------
    def step_bits(
        self, ctx: EngineContext, pri, state: MISRoundState
    ) -> Tuple[MISRoundState, RoundFacts]:
        raise NotImplementedError(
            f"{self.name} has no packed-frontier round body "
            f"(supports_bitwise={self.supports_bitwise})"
        )

    # -- the round bodies --------------------------------------------------
    def round_body(self, ctx: EngineContext):
        """The body of this run's route — dense or packed frontier, whole
        tiling or hybrid partition.  Each returns `(state, RoundFacts)`."""
        hybrid = self.supports_hybrid and ctx.tiled.partition is not None
        if ctx.frontier == "bitwise":
            return self.step_bits_hybrid if hybrid else self.step_bits
        return self.step_hybrid if hybrid else self.step_dense

    def step(
        self, ctx: EngineContext, pri, state: MISRoundState
    ) -> MISRoundState:
        return self.round_body(ctx)(ctx, pri, state)[0]

    def step_with_stats(
        self, ctx: EngineContext, pri, state: MISRoundState
    ) -> Tuple[MISRoundState, jnp.ndarray]:
        """`step` plus a (TELEMETRY_COLS,) int32 telemetry row: the same
        body, once, with six reductions over what it already computed (no
        extra SpMVs, no host callbacks) and the static stream width it
        read.  Packed frontiers count by word popcount — the frontier
        never densifies."""
        new, facts = self.round_body(ctx)(ctx, pri, state)
        count = _popcount_words if ctx.frontier == "bitwise" else _count
        with jax.named_scope(SCOPE_P3):   # telemetry counts: ③ bookkeeping
            skipped = _tiles_skipped(facts.tiled, facts.flags)
            row = _telemetry_row(
                count(state.alive),
                count(facts.cand),
                count(new.in_mis) - count(state.in_mis),
                skipped,
                _tiles_routed_dense(facts.tiled, skipped, facts.flags),
                jnp.int32(facts.n_sparse_tiles),
                jnp.int32(stream_width(self, ctx)),
            )
        return new, row

    def step_dense(
        self, ctx: EngineContext, pri, state: MISRoundState
    ) -> Tuple[MISRoundState, RoundFacts]:
        with jax.named_scope(SCOPE_P1):
            cand = self.phase1_candidates(ctx, pri, state.alive)
        with jax.named_scope(SCOPE_P2):
            flags = self.col_flags(ctx, cand, state.alive)
        with jax.named_scope(SCOPE_P3):
            inc = round_increment(state)
        if self.fused:
            with jax.named_scope(SCOPE_P2):
                new_alive, mis_add = self.fused_step(ctx, cand, state.alive, flags)
            with jax.named_scope(SCOPE_P3):
                new = MISRoundState(
                    alive=new_alive,
                    in_mis=state.in_mis | mis_add,
                    rnd=state.rnd + inc,
                )
        else:
            with jax.named_scope(SCOPE_P2):
                n_c = self.phase2_counts(ctx, cand, state.alive, flags)
            with jax.named_scope(SCOPE_P3):
                new = phase3_update(state, cand, n_c, inc)
        return new, RoundFacts(cand, flags, ctx.tiled, 0)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

ENGINES: Dict[str, RoundEngine] = {}

# legacy TCMISConfig.backend spellings kept working (but deprecated)
_ALIASES = {"ref": "tiled_ref", "pallas": "tiled_pallas", "fused": "fused_pallas"}
_DEPRECATED_SPELLINGS = ("ref", "pallas")


def register_engine(engine: RoundEngine) -> RoundEngine:
    ENGINES[engine.name] = engine
    return engine


def get_engine(name: str) -> RoundEngine:
    resolved = _ALIASES.get(name, name)
    if name in _DEPRECATED_SPELLINGS:
        warnings.warn(
            f"engine spelling {name!r} is deprecated; use {resolved!r} "
            f"(repro.api: SolveOptions(engine={resolved!r}))",
            DeprecationWarning,
            stacklevel=2,
        )
    if resolved not in ENGINES:
        raise ValueError(
            f"unknown engine {name!r}; registered: {sorted(ENGINES)} "
            f"(aliases: {_ALIASES})"
        )
    return ENGINES[resolved]


def engine_names() -> Tuple[str, ...]:
    """Registered engine names, stable registration order."""
    return tuple(ENGINES)


# --------------------------------------------------------------------------
# the four engines
# --------------------------------------------------------------------------

@_edge
def _segment_nbr_max(ctx: EngineContext, p, mask) -> jnp.ndarray:
    from repro.core.spmv import neighbor_max_segment

    n = ctx.g.n_nodes
    out = neighbor_max_segment(ctx.g, p[:n], mask[:n])
    return pack_vertex_vector(out, ctx.tiled)


class SegmentEngine(RoundEngine):
    """Paper-faithful CC baseline: every phase on the edge-list substrate."""

    name = "segment"

    def streams(self, ctx):
        return (EDGES,)

    def _nbr_max(self, ctx, p, mask):
        return _segment_nbr_max(ctx, p, mask)

    def col_flags(self, ctx, cand, alive):
        return None   # no tiles, nothing to skip

    @_edge
    def phase2_counts(self, ctx, cand, alive, col_flags=None):
        from repro.core.spmv import neighbor_sum_segment

        n = ctx.g.n_nodes
        n_c = neighbor_sum_segment(ctx.g, cand[:n].astype(jnp.float32))
        return pack_vertex_vector(n_c, ctx.tiled)


@_edge
def _segment_nbr_max_bits_oracle(ctx: EngineContext, p, mask_words) -> jnp.ndarray:
    """Phase ① for bitwise runs that pin `phase1="segment"`: the edge-list
    substrate has no word form, so the pending mask densifies here — the
    sanctioned boundary (`_oracle` suffix, tools/ci_guards.py) between the
    packed round body and the paper-faithful CC baseline."""
    from repro.core.tiling import unpack_frontier_words

    mask = unpack_frontier_words(mask_words, ctx.tiled.tile_size)
    return _segment_nbr_max(ctx, p, mask)


class _TiledEngine(RoundEngine):
    """Shared phase-① policy for tile-schedule engines: `cfg.phase1` picks
    the paper-faithful segment max or the beyond-paper tiled max.

    Also owns the HYBRID round bodies (DESIGN.md §16): when the tiling
    carries a `TilePartition`, phase ① and ② each run twice — the existing
    dense machinery over the COMPACTED dense sub-tiling (`partition.dense`,
    via a sub-context that swaps `ctx.tiled`) and the COO sparse tail
    through segment gather/scatter — and the two halves merge exactly
    (`max` for Max_Np, `+` / `|` for N_c) before phase ③, so hybrid
    solutions are bit-identical to the dense-only path.  Fused engines
    demote to the split ② under hybrid (the in-kernel ③ can't see the
    sparse hits) via the `_dense_phase2` indirection."""

    supports_bitwise = True
    supports_hybrid = True

    def streams(self, ctx):
        """① reads the edge list unless it runs on tiles; the hybrid tail
        is read by ② always, and by the tiled ①."""
        edges = () if ctx.cfg.phase1 == "tiled" else (EDGES,)
        return edges + ((TAIL,) if ctx.tiled.partition is not None else ())

    @_tile
    def _tiled_nbr_max(self, ctx, p, mask) -> jnp.ndarray:
        t = ctx.tiled
        return tile_neighbor_max(
            t.tiles, t.tile_rows, t.tile_cols, jnp.where(mask, p, _NEG),
            t.n_block_rows, t.tile_size,
        )

    def _nbr_max(self, ctx, p, mask):
        if ctx.cfg.phase1 != "tiled":
            return _segment_nbr_max(ctx, p, mask)
        return self._tiled_nbr_max(ctx, p, mask)

    # -- packed-frontier round body (DESIGN.md §13) ------------------------
    @_tile
    def _nbr_max_bits(
        self, ctx, st: SortedPriorityTiles, planes, mask_words
    ) -> jnp.ndarray:
        """Bitwise Max_Np: remap the mask words into `st`'s sorted-slot
        layout (an O(n)-word repack inside the packing substrate), then the
        clz scan.  `planes` is ignored here; the Pallas engine overrides to
        run the plane-scan kernel when a plane stack was built."""
        t = ctx.tiled
        mask_sorted = sorted_frontier_words(mask_words, st.order, t.tile_size)
        return tile_neighbor_max_bits(
            st.tiles, t.tile_rows, t.tile_cols, st.p_sorted, mask_sorted,
            t.n_block_rows, t.tile_size,
        )

    def phase1_candidates_bits(
        self, ctx, pri, alive_words, dctx=None
    ) -> jnp.ndarray:
        """① on packed frontiers.  Priorities stay dense (they are values,
        not frontiers); the select/pending/candidate SETS stay packed.  The
        padded-slot divergence between substrates (segment pads Max_Np with
        0, tiled floors at _NEG) is erased by the `& alive_words` /
        `& pending` guards — padded alive bits are always 0.

        With `dctx` (hybrid routes) the tiled Max_Np merges the dense
        partition's scan with the COO tail; the bitwise setup artefacts
        (`ctx.bits`) are then built over the dense partition
        (`make_bitwise_context(partition.dense, ...)`), so the sorted-tile
        scan only walks dense tiles."""
        T = ctx.tiled.tile_size
        b = ctx.bits

        def max_np(st, planes, p, mask_words):
            if ctx.cfg.phase1 != "tiled":
                return _segment_nbr_max_bits_oracle(ctx, p, mask_words)
            if dctx is None:
                return self._nbr_max_bits(ctx, st, planes, mask_words)
            return self._hybrid_nbr_max_bits(ctx, dctx, st, planes, p, mask_words)

        mx = max_np(b.select, b.select_planes, pri.select, alive_words)
        if pri.resolve is None:
            return pack_frontier_words(pri.select > mx, T) & alive_words
        # H3: conflicts resolved on the pending set before C is finalised.
        pending = pack_frontier_words(pri.select >= mx, T) & alive_words
        mx = max_np(b.resolve, b.resolve_planes, pri.resolve, pending)
        return pack_frontier_words(pri.resolve > mx, T) & pending

    @_tile
    def col_flags_bits(self, ctx, cand_words) -> jnp.ndarray:
        """Active block-column flags straight from the words — a column is
        live iff any of its W candidate words is nonzero (no densify)."""
        flags = (cand_words != 0).any(axis=1).astype(jnp.int32)
        if ctx.col_gate is not None:
            flags = flags * ctx.col_gate.astype(flags.dtype)
        return flags

    def phase2_hits(self, ctx, cand_words, alive_words, col_flags):
        """② popcount SpMV → packed hit words.  Returns (nbc, W) uint32."""
        raise NotImplementedError(f"{self.name} is a fused engine")

    def fused_step_bits(self, ctx, cand_words, alive_words, col_flags):
        """②+③ fused on words.  Returns (new_alive_words, mis_add_words)."""
        raise NotImplementedError(f"{self.name} is a split engine")

    def step_bits(
        self, ctx, pri, state: MISRoundState
    ) -> Tuple[MISRoundState, RoundFacts]:
        with jax.named_scope(SCOPE_P1):
            cand_w = self.phase1_candidates_bits(ctx, pri, state.alive)
        with jax.named_scope(SCOPE_P2):
            flags = self.col_flags_bits(ctx, cand_w)
        inc = round_increment(state)   # scalar: bitwise excludes member_rounds
        if self.fused:
            with jax.named_scope(SCOPE_P2):
                new_alive, mis_add = self.fused_step_bits(
                    ctx, cand_w, state.alive, flags
                )
            with jax.named_scope(SCOPE_P3):
                new = MISRoundState(
                    alive=new_alive,
                    in_mis=state.in_mis | mis_add,
                    rnd=state.rnd + inc,
                )
        else:
            with jax.named_scope(SCOPE_P2):
                hit_w = self.phase2_hits(ctx, cand_w, state.alive, flags)
            with jax.named_scope(SCOPE_P3):
                new = phase3_update_bits(state, cand_w, hit_w, inc)
        return new, RoundFacts(cand_w, flags, ctx.tiled, 0)

    # -- hybrid round bodies (DESIGN.md §16) -------------------------------
    #
    # The dense half reuses the engine's own machinery verbatim on a
    # sub-context whose `tiled` is the compacted dense partition; the
    # sparse tail is pure segment gather/scatter in GLOBAL padded vertex
    # ids.  Sentinel pairs (row == col == n_padded) scatter into the
    # dropped segment row, so padding contributes nothing — the same
    # convention as the Graph sentinel edges.

    @_tile
    def _dense_phase2(self, ctx, cand, alive, col_flags):
        """Split-② over the dense partition, masked to covered rows (the
        Pallas kernel leaves unvisited output blocks uninitialised — see
        `_covered_rows`).  `ctx` here is the DENSE sub-context."""
        counts = self._dense_phase2_counts(ctx, cand, alive, col_flags)
        return jnp.where(_covered_vertices(ctx.tiled), counts, 0.0)

    def _dense_phase2_counts(self, ctx, cand, alive, col_flags):
        """Kernel dispatch seam for the hybrid split ②.  Fused engines
        override to reach their parent's split kernel: the fused ②+③ would
        commit phase ③ before the sparse hits can merge in."""
        return self.phase2_counts(ctx, cand, alive, col_flags)

    @_edge
    def _sparse_nbr_max(self, ctx, p, mask) -> jnp.ndarray:
        """① over the COO tail: masked priority gather at the senders,
        segment max at the receivers.  Empty segments come back at the
        int32 min (< _NEG), so `jnp.maximum` with the dense half is exact."""
        part = ctx.tiled.partition
        pm = jnp.where(mask, p, _NEG)
        return jax.ops.segment_max(
            pm[part.sp_cols], part.sp_rows,
            num_segments=ctx.tiled.n_padded + 1,
        )[:-1]

    @_edge
    def _sparse_counts(self, ctx, cand) -> jnp.ndarray:
        """② over the COO tail: candidate gather + segment sum — the exact
        nnz-wise slice of N_c the dense partition no longer covers."""
        part = ctx.tiled.partition
        return jax.ops.segment_sum(
            cand[part.sp_cols].astype(jnp.float32), part.sp_rows,
            num_segments=ctx.tiled.n_padded + 1,
        )[:-1]

    @_tile
    def _dense_nbr_max(self, dctx, p, mask) -> jnp.ndarray:
        """① over the dense partition, floored to `_NEG` on uncovered rows
        (same uninitialised-output hazard as `_dense_phase2`)."""
        return jnp.where(
            _covered_vertices(dctx.tiled),
            self._tiled_nbr_max(dctx, p, mask),
            _NEG,
        )

    def _hybrid_nbr_max(self, ctx, dctx, p, mask) -> jnp.ndarray:
        if ctx.cfg.phase1 != "tiled":
            # the segment phase ① already covers the WHOLE graph — no merge
            return _segment_nbr_max(ctx, p, mask)
        dense_mx = self._dense_nbr_max(dctx, p, mask)
        return jnp.maximum(dense_mx, self._sparse_nbr_max(ctx, p, mask))

    def _hybrid_candidates(self, ctx, dctx, pri, alive) -> jnp.ndarray:
        max_np = self._hybrid_nbr_max(ctx, dctx, pri.select, alive)
        if pri.resolve is None:
            return alive & (pri.select > max_np)
        pending = alive & (pri.select >= max_np)
        max_res = self._hybrid_nbr_max(ctx, dctx, pri.resolve, pending)
        return pending & (pri.resolve > max_res)

    def step_hybrid(
        self, ctx, pri, state: MISRoundState
    ) -> Tuple[MISRoundState, RoundFacts]:
        part = ctx.tiled.partition
        dctx = dataclasses.replace(ctx, tiled=part.dense)
        with jax.named_scope(SCOPE_P1):
            cand = self._hybrid_candidates(ctx, dctx, pri, state.alive)
        with jax.named_scope(SCOPE_P2):
            flags = self.col_flags(dctx, cand, state.alive)
        with jax.named_scope(SCOPE_P3):
            inc = round_increment(state)
        with jax.named_scope(SCOPE_P2):
            n_c = self._dense_phase2(dctx, cand, state.alive, flags)
            n_c = n_c + self._sparse_counts(ctx, cand)
        with jax.named_scope(SCOPE_P3):
            new = phase3_update(state, cand, n_c, inc)
        return new, RoundFacts(cand, flags, part.dense, part.n_sparse_tiles)

    # -- hybrid, packed frontiers ------------------------------------------

    @_edge
    def _sparse_nbr_max_bits(self, ctx, p, mask_words) -> jnp.ndarray:
        """① tail on packed frontiers: a single-bit gather per nnz
        (`gather_frontier_bits` — shift-and-mask, not a densify), then the
        same masked segment max.  Priorities stay dense (they are values,
        not frontiers)."""
        part = ctx.tiled.partition
        T = ctx.tiled.tile_size
        bit = gather_frontier_bits(mask_words, part.sp_cols, T)
        pm = jnp.where(bit, p[part.sp_cols], _NEG)
        return jax.ops.segment_max(
            pm, part.sp_rows, num_segments=ctx.tiled.n_padded + 1
        )[:-1]

    @_edge
    def _sparse_hits_bits(self, ctx, cand_words) -> jnp.ndarray:
        """② tail on packed frontiers: candidate-bit gather, segment max
        (any-hit), repacked to (nbc, W) words for the `|` merge."""
        part = ctx.tiled.partition
        T = ctx.tiled.tile_size
        bit = gather_frontier_bits(cand_words, part.sp_cols, T)
        hit = jax.ops.segment_max(
            bit.astype(jnp.uint32), part.sp_rows,
            num_segments=ctx.tiled.n_padded + 1,
        )[:-1]
        return pack_frontier_words(hit, T)

    @_tile
    def _dense_hits_bits(self, dctx, cand_words, alive_words, flags) -> jnp.ndarray:
        """② hit words over the dense partition, masked to covered rows
        (same uninitialised-output hazard as `_dense_phase2`)."""
        hit_w = self.phase2_hits(dctx, cand_words, alive_words, flags)
        return jnp.where(_covered_rows(dctx.tiled)[:, None], hit_w, jnp.uint32(0))

    @_tile
    def _dense_nbr_max_bits(self, dctx, st, planes, mask_words) -> jnp.ndarray:
        return jnp.where(
            _covered_vertices(dctx.tiled),
            self._nbr_max_bits(dctx, st, planes, mask_words),
            _NEG,
        )

    def _hybrid_nbr_max_bits(
        self, ctx, dctx, st, planes, p, mask_words
    ) -> jnp.ndarray:
        dense_mx = self._dense_nbr_max_bits(dctx, st, planes, mask_words)
        return jnp.maximum(dense_mx, self._sparse_nbr_max_bits(ctx, p, mask_words))

    def step_bits_hybrid(
        self, ctx, pri, state: MISRoundState
    ) -> Tuple[MISRoundState, RoundFacts]:
        # flags come off the full context, skip counts off the dense one
        part = ctx.tiled.partition
        dctx = dataclasses.replace(ctx, tiled=part.dense)
        with jax.named_scope(SCOPE_P1):
            cand_w = self.phase1_candidates_bits(ctx, pri, state.alive, dctx)
        with jax.named_scope(SCOPE_P2):
            flags = self.col_flags_bits(ctx, cand_w)
        inc = round_increment(state)
        with jax.named_scope(SCOPE_P2):
            hit_w = self._dense_hits_bits(dctx, cand_w, state.alive, flags)
            hit_w = hit_w | self._sparse_hits_bits(ctx, cand_w)
        with jax.named_scope(SCOPE_P3):
            new = phase3_update_bits(state, cand_w, hit_w, inc)
        return new, RoundFacts(cand_w, flags, part.dense, part.n_sparse_tiles)


class TiledRefEngine(_TiledEngine):
    """jnp oracle on the BSR schedule — ground truth for both kernels."""

    name = "tiled_ref"

    @_tile
    def phase2_counts(self, ctx, cand, alive, col_flags=None):
        t = ctx.tiled
        out = tile_spmv(
            t.tiles, t.tile_rows, t.tile_cols,
            self._pack_rhs(ctx, cand, alive),
            t.n_block_rows, t.tile_size, col_flags=col_flags,
        )
        return out[:, 0]

    @_tile
    def phase2_hits(self, ctx, cand_words, alive_words, col_flags):
        t = ctx.tiled
        return tile_spmv_bits(
            ctx.bits.tiles_bits, t.tile_rows, t.tile_cols, cand_words,
            t.n_block_rows, t.tile_size, col_flags=col_flags,
        )


class TiledPallasEngine(_TiledEngine):
    """Phase ② on the Pallas SpMV kernel; live empty-C skip via col_flags."""

    name = "tiled_pallas"
    plane_kernel_nbr_max = True

    @_tile
    def _tiled_nbr_max(self, ctx, p, mask):
        from repro.kernels.ops import tc_neighbor_max

        return tc_neighbor_max(ctx.tiled, p, mask)

    @_tile
    def phase2_counts(self, ctx, cand, alive, col_flags=None):
        from repro.kernels.ops import tc_spmv

        out = tc_spmv(
            ctx.tiled, self._pack_rhs(ctx, cand, alive),
            col_flags=col_flags, skip_dma=ctx.cfg.skip_dma,
        )
        return out[:, 0]

    @_tile
    def _nbr_max_bits(self, ctx, st, planes, mask_words):
        # The plane-scan kernel runs only when a plane stack was built (real
        # TPU — `make_bitwise_context(planes=True)`); otherwise the clz jnp
        # form, which is the same scan collapsed (bit-identical either way).
        if planes is None:
            return super()._nbr_max_bits(ctx, st, planes, mask_words)
        from repro.kernels.ops import tc_neighbor_max_bits

        signed = planes.shape[0] == _RESOLVE_PLANE_BITS
        return tc_neighbor_max_bits(ctx.tiled, planes, mask_words, signed=signed)

    @_tile
    def phase2_hits(self, ctx, cand_words, alive_words, col_flags):
        from repro.kernels.ops import tc_spmv_bits

        return tc_spmv_bits(
            ctx.tiled, cand_words, tiles_words=ctx.bits.tiles_bits,
            col_flags=col_flags, skip_dma=ctx.cfg.skip_dma,
        )


class FusedPallasEngine(TiledPallasEngine):
    """The production fast path: phase ②+③ in one kernel pass — the state
    update runs in the SpMV epilogue, N_c never round-trips through HBM."""

    name = "fused_pallas"
    fused = True

    def phase2_counts(self, ctx, cand, alive, col_flags=None):
        raise NotImplementedError("fused_pallas runs ②+③ as one fused_step")

    def _dense_phase2_counts(self, ctx, cand, alive, col_flags):
        # hybrid demotes fused ②+③ to the split ② (the in-kernel ③ can't
        # merge the sparse hits) — reach TiledPallasEngine's SpMV kernel
        # past this class's intentionally-raising phase2_counts.  The
        # bitwise twin needs no indirection: `phase2_hits` is inherited,
        # not overridden.
        return super().phase2_counts(ctx, cand, alive, col_flags)

    @_tile
    def fused_step(self, ctx, cand, alive, col_flags=None):
        from repro.kernels.ops import tc_spmv_fused

        _, new_alive, mis_add = tc_spmv_fused(
            ctx.tiled, self._pack_rhs(ctx, cand, alive), cand, alive,
            col_flags=col_flags, skip_dma=ctx.cfg.skip_dma,
        )
        return new_alive, mis_add

    @_tile
    def fused_step_bits(self, ctx, cand_words, alive_words, col_flags):
        from repro.kernels.ops import tc_spmv_fused_bits

        _, new_alive, mis_add = tc_spmv_fused_bits(
            ctx.tiled, cand_words, alive_words,
            tiles_words=ctx.bits.tiles_bits,
            col_flags=col_flags, skip_dma=ctx.cfg.skip_dma,
        )
        return new_alive, mis_add


register_engine(SegmentEngine())
register_engine(TiledRefEngine())
register_engine(TiledPallasEngine())
register_engine(FusedPallasEngine())
