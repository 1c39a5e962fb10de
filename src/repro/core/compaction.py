"""The compaction ladder: after a round, stream only the live half-edges.

A half-edge (u, v) matters to a round only while both u and v are alive
(DESIGN.md §18): ① masks the sender by alive/pending and reads Max_Np only
at alive receivers; ② gathers candidates, a subset of alive, and ③ reads
`N_c > 0` only at alive vertices.  Once an endpoint dies the half-edge is
inert, yet the per-half-edge streams — the edge list ① reads, the hybrid
COO tail ② reads — keep their full length in every round.

The ladder runs the round loop at static capacities C₀ > C₁ > … of each
stream: C₀ is the full stream, C_{k+1} = ⌈C_k / 32⌉ rounded up to a
multiple of 1024, and the ladder stops at the first rung of at most
`LADDER_FLOOR` entries.  Every rung compiles a round loop of its own, so
the step is the largest that still lets road's round 1 (2.75% live)
stream a rung below the full one.  Rung k is its own `lax.while_loop`, carrying the
live mask of its streams, which it leaves once the live entries fit rung
k+1; `compact` then moves them, in order, into the first slots of rung
k+1's capacity, and the spare slots hold the stream's own sentinel.  A
stream with no rung below it runs the single loop unchanged.  While a
vertex whose priority is at or below the mask value `_NEG` lives, no rung
is left (`low_priority`): only such a priority could tell a dropped dead
sender from no neighbour at all.

This module holds the static plan (`plan_ladder`), the per-round live mask
(`live_masks`), the compaction (`compact`) and the context rebinding
(`bind`); `core.tc_mis._run_ladder` runs the rungs.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Tuple

import jax.numpy as jnp

from repro.core.spmv import _NEG
from repro.core.tiling import (
    BlockTiledGraph,
    gather_frontier_bits,
    pack_frontier_words,
)
from repro.graphs.graph import Graph

LADDER_STEP = 32       # each rung holds a 32nd of the one above
LADDER_ALIGN = 1024    # capacities are whole multiples of this
LADDER_FLOOR = 16384   # no rung below the first one of at most this size

EDGES = "edges"        # the edge list (`Graph.senders`/`receivers`)
TAIL = "tail"          # the hybrid COO tail (`TilePartition.sp_cols`/`sp_rows`)

# a stream as (gather side, scatter side): senders/receivers, sp_cols/sp_rows
Pairs = Tuple[jnp.ndarray, jnp.ndarray]


def ladder_capacities(full: int) -> Tuple[int, ...]:
    """The rung capacities of a stream of `full` entries, largest first.
    A stream of at most `LADDER_FLOOR` entries has the one rung."""
    caps = [int(full)]
    while caps[-1] > LADDER_FLOOR:
        step = -(-caps[-1] // LADDER_STEP)
        caps.append(-(-step // LADDER_ALIGN) * LADDER_ALIGN)
    return tuple(caps)


class Ladder(NamedTuple):
    """The static plan of one solve's ladder.

    `rungs[k]` maps each compacted stream to its capacity at rung k.  With
    `shared`, the route reads both streams and the partition has no dense
    tile, so the tail holds exactly the edge list's pairs: only the edge
    list is compacted, and below rung 0 phase ② reads the compacted edge
    list as its tail."""
    rungs: Tuple[Dict[str, int], ...]
    shared: bool


def full_streams(ctx) -> Dict[str, Pairs]:
    """Every stream a route may read, at full length."""
    out = {EDGES: (ctx.g.senders, ctx.g.receivers)}
    part = ctx.tiled.partition
    if part is not None:
        out[TAIL] = (part.sp_cols, part.sp_rows)
    return out


def stream_width(engine, ctx) -> int:
    """Entries of the streams one round of `engine` reads at `ctx`'s
    capacities (0 on a route that reads none)."""
    full = full_streams(ctx)
    return sum(int(full[s][0].shape[0]) for s in engine.streams(ctx))


def plan_ladder(engine, ctx) -> Ladder:
    """The ladder of this route, from the engine's streams and the plan's
    static shapes alone: one rung when no stream is longer than the floor."""
    read = engine.streams(ctx)
    part = ctx.tiled.partition
    shared = (
        read == (EDGES, TAIL)
        and part.n_dense_tiles == 0
        and part.sp_nnz == ctx.g.n_edges
    )
    full = {s: int(pairs[0].shape[0]) for s, pairs in full_streams(ctx).items()}
    ladders = {s: ladder_capacities(full[s]) for s in ((EDGES,) if shared else read)}
    depth = max((len(c) for c in ladders.values()), default=1)
    rungs = tuple(
        {s: c[min(k, len(c) - 1)] for s, c in ladders.items()}
        for k in range(depth)
    )
    return Ladder(rungs, shared)


def sentinel(stream: str, ctx) -> int:
    """The id a stream's spare slots hold: `n_nodes` for the edge list,
    `n_padded` for the tail (the conventions both already use)."""
    return ctx.g.n_nodes if stream == EDGES else ctx.tiled.n_padded


def _alive_at(alive: jnp.ndarray, ids: jnp.ndarray, tiled) -> jnp.ndarray:
    """alive[ids] as bools, False at either sentinel: padded slots are never
    alive, and the tail's sentinel `n_padded` reads an appended dead slot.
    Dense masks gather as int32, which a TPU gathers faster than bools."""
    if alive.ndim == 2:   # packed (nbc, W) words
        bit = gather_frontier_bits(alive, ids, tiled.tile_size)
        return bit & (ids < tiled.n_padded)
    return jnp.append(alive, False).astype(jnp.int32)[ids] != 0


def low_priority(pri, alive: jnp.ndarray, tiled: BlockTiledGraph) -> jnp.ndarray:
    """The vertices whose select or resolve priority is at or below the
    mask value `_NEG`, in `alive`'s representation (bools or packed
    words): while one is alive, no rung may drop a half-edge."""
    low = pri.select <= _NEG
    if pri.resolve is not None:
        low = low | (pri.resolve <= _NEG)
    if alive.ndim == 2:
        return pack_frontier_words(low, tiled.tile_size)
    return low


def live_masks(
    streams: Dict[str, Pairs], names: Tuple[str, ...], alive: jnp.ndarray,
    tiled: BlockTiledGraph,
) -> Tuple[jnp.ndarray, ...]:
    """Per named stream, the (capacity,) bool mask of half-edges with both
    ends alive — the entries a later round can still need."""
    return tuple(
        _alive_at(alive, streams[s][0], tiled) & _alive_at(alive, streams[s][1], tiled)
        for s in names
    )


def compact(mask: jnp.ndarray, pairs: Pairs, capacity: int, fill: int) -> Pairs:
    """The entries of `pairs` under `mask`, in order, in the first slots of
    `capacity`; the rest hold `fill`.  Exact when popcount(mask) fits: the
    ladder leaves a rung only then, or when no further round runs."""
    n = mask.shape[0]
    rank = jnp.cumsum(mask, dtype=jnp.int32) - 1
    slot = jnp.where(mask, rank, capacity)             # dead entries: dropped
    src = jnp.full((capacity,), n, jnp.int32).at[slot].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop"
    )
    return tuple(jnp.take(a, src, mode="fill", fill_value=fill) for a in pairs)


def bind(ctx, streams: Dict[str, Pairs]):
    """`ctx` with its graph and tail reading `streams`.  The compacted edge
    list counts every slot as an edge: a spare slot's sentinel receiver is
    the dropped segment row, so it adds nothing to any vertex."""
    g, tiled = ctx.g, ctx.tiled
    senders, receivers = streams[EDGES]
    if senders is not g.senders:
        g = Graph(senders=senders, receivers=receivers, n_nodes=g.n_nodes,
                  n_edges=int(senders.shape[0]))
    part = tiled.partition
    if part is not None and streams[TAIL][0] is not part.sp_cols:
        cols, rows = streams[TAIL]
        tiled = dataclasses.replace(
            tiled, partition=dataclasses.replace(part, sp_cols=cols, sp_rows=rows)
        )
    return dataclasses.replace(ctx, g=g, tiled=tiled)


def shared_tail(pairs: Pairs, ctx) -> Pairs:
    """The compacted edge list as a tail: (senders, receivers) are (cols,
    rows), and the edge sentinel `n_nodes` becomes the tail's `n_padded`."""
    n, pad = ctx.g.n_nodes, ctx.tiled.n_padded
    return tuple(jnp.where(a == n, pad, a) for a in pairs)
