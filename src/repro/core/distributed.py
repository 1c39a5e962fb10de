"""Distributed TC-MIS: block-row-partitioned BSR over a device mesh.

Layout (DESIGN.md §5): each chip owns a contiguous slab of block-rows of the
tiled adjacency matrix plus the matching slice of the state vectors.  Per
round the only communication is the `all_gather` of the candidate / alive
bit-vectors (optionally packed 8× as uint32 frontier words via the one
packing contract in `core.tiling`, DESIGN.md §6.4/§13) — the
distributed-Luby lower bound.  Everything else (phase ① tiled max, phase ②
tiled SpMV, phase ③ state update) is shard-local.

The mesh axes are flattened into one logical partition axis, so the same code
runs on (16,16) single-pod and (2,16,16) multi-pod meshes — the "pod" axis
simply becomes the slowest-varying factor of the row partition.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.engine import block_col_flags, tile_neighbor_max, tile_spmv
from repro.core.heuristics import Priorities
from repro.core.spmv import _NEG
from repro.core.tiling import (
    BlockTiledGraph,
    pack_frontier_words,
    unpack_frontier_words,
)


# --------------------------------------------------------------------------
# host-side shard construction
# --------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedTiledGraph:
    """Row-partitioned BSR; leading axis is the shard axis.

    tiles:     (S, nt_pad, T, T) int8 — or (S, nt_pad, T, W) uint32 when the
               source tiling is bit-packed (DESIGN.md §11); sharding is
               storage-agnostic, each shard's slab stays in the source format
    tile_rows: (S, nt_pad) int32 — block-row LOCAL to the shard
    tile_cols: (S, nt_pad) int32 — GLOBAL block-column
    """
    tiles: jnp.ndarray
    tile_rows: jnp.ndarray
    tile_cols: jnp.ndarray
    n_nodes: int = dataclasses.field(metadata=dict(static=True))
    tile_size: int = dataclasses.field(metadata=dict(static=True))
    rows_per_shard: int = dataclasses.field(metadata=dict(static=True))
    n_shards: int = dataclasses.field(metadata=dict(static=True))
    n_block_cols: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_padded(self) -> int:
        """Global padded vertex count = S · rows_per_shard · T."""
        return self.n_shards * self.rows_per_shard * self.tile_size


def shard_tiled(tiled: BlockTiledGraph, n_shards: int) -> ShardedTiledGraph:
    """Split a BSR graph into ``n_shards`` row slabs, padded to a rectangle.

    Storage-agnostic: packed uint32 tiles shard shard-locally in their
    packed form (the per-shard HBM slab shrinks by the same 8×)."""
    T = tiled.tile_size
    nbr = tiled.n_block_rows
    rows_per_shard = -(-nbr // n_shards)
    nbr_pad = rows_per_shard * n_shards

    t = np.asarray(tiled.tiles[: max(tiled.n_tiles, 1)])
    tr = np.asarray(tiled.tile_rows[: max(tiled.n_tiles, 1)])
    tc = np.asarray(tiled.tile_cols[: max(tiled.n_tiles, 1)])
    if tiled.n_tiles == 0:
        t, tr, tc = t[:0], tr[:0], tc[:0]

    owner = tr // rows_per_shard
    max_nt = max(int(np.max(np.bincount(owner, minlength=n_shards))) if tr.size else 0, 1)
    max_nt = ((max_nt + 7) // 8) * 8

    tiles_s = np.zeros((n_shards, max_nt) + t.shape[1:], dtype=t.dtype)
    # padding tiles carry the last local row (monotone) and column 0
    rows_s = np.full((n_shards, max_nt), rows_per_shard - 1, dtype=np.int32)
    cols_s = np.zeros((n_shards, max_nt), dtype=np.int32)
    for s in range(n_shards):
        sel = owner == s
        k = int(sel.sum())
        tiles_s[s, :k] = t[sel]
        rows_s[s, :k] = tr[sel] - s * rows_per_shard
        cols_s[s, :k] = tc[sel]

    # column space must cover the padded vertex range (gathered RHS length)
    n_block_cols = nbr_pad
    return ShardedTiledGraph(
        tiles=jnp.asarray(tiles_s),
        tile_rows=jnp.asarray(rows_s),
        tile_cols=jnp.asarray(cols_s),
        n_nodes=tiled.n_nodes,
        tile_size=T,
        rows_per_shard=rows_per_shard,
        n_shards=n_shards,
        n_block_cols=n_block_cols,
    )


# --------------------------------------------------------------------------
# bit-packed frontier collectives (beyond-paper, DESIGN.md §6.4): the gather
# payload rides as the SAME (…, W) uint32 frontier words the bitwise round
# engine uses — `core.tiling.pack_frontier_words` is the single packing
# contract; this module no longer carries its own uint8 variant.  A shard's
# local slice is rps·T vertices — an exact multiple of T, so the word layout
# tiles cleanly across shards and `all_gather(tiled=True)` concatenates to
# the global word vector.
# --------------------------------------------------------------------------
# shard-local tile operators: the engine layer's raw-array forms applied to
# this shard's slab — local rows, GLOBAL columns.  SpMV needs no wrapper
# (`tile_spmv` is called directly); the max adds the priority masking.
# --------------------------------------------------------------------------

def _local_nbr_max(tiles, tile_rows, tile_cols, p_global, mask_global,
                   n_local_rows, T):
    return tile_neighbor_max(
        tiles, tile_rows, tile_cols, jnp.where(mask_global, p_global, _NEG),
        n_local_rows, T,
    )


# --------------------------------------------------------------------------
# the distributed algorithm
# --------------------------------------------------------------------------

class DistMISResult(NamedTuple):
    in_mis: jnp.ndarray     # (n_padded,) bool, row-sharded
    rounds: jnp.ndarray     # int32 (replicated)


@dataclasses.dataclass(frozen=True)
class DistConfig:
    max_rounds: int = 1024
    bitpack: bool = True     # gather uint8-packed frontiers (8× fewer bytes)
    lanes: int = 8


def make_mis_step_fn(
    mesh: Mesh,
    cfg: DistConfig,
    *,
    n_nodes: int,
    tile_size: int,
    rows_per_shard: int,
    two_pass: bool = True,
):
    """The lowerable distributed-MIS entry: returns a shard_map'd callable

        fn(tiles, tile_rows, tile_cols, select, resolve) -> (in_mis, rounds)

    with tiles/rows/cols row-slab-sharded over the flattened mesh and the
    priority vectors replicated.  This is what launch/dryrun.py lowers for
    the paper's graph suite and what `build_distributed_mis` wraps for live
    runs.
    """
    axis = tuple(mesh.axis_names)
    T = tile_size
    rps = rows_per_shard
    n_local = rps * T

    def gather_bool(x_local):
        # the one sanctioned densify outside kernels/oracles on this path:
        # the shard-local phases below are dense ops (tools/ci_guards.py
        # allowlists gather_bool); only the WIRE payload is packed words.
        if cfg.bitpack:
            packed = pack_frontier_words(x_local, T)
            g = jax.lax.all_gather(packed, axis, tiled=True)
            return unpack_frontier_words(g, T)
        return jax.lax.all_gather(x_local, axis, tiled=True)

    def body_fn(tiles, tile_rows, tile_cols, select, resolve):
        """Inside shard_map: tiles/rows/cols are this shard's slab (leading
        shard axis of local size 1 — squeeze it); select/resolve are
        replicated global vectors."""
        tiles, tile_rows, tile_cols = tiles[0], tile_rows[0], tile_cols[0]
        idx = jax.lax.axis_index(axis)
        off = idx * n_local
        select_l = jax.lax.dynamic_slice(select, (off,), (n_local,))
        resolve_l = jax.lax.dynamic_slice(resolve, (off,), (n_local,))

        def cond(state):
            alive_g, _, rnd = state
            return jnp.any(alive_g) & (rnd < cfg.max_rounds)

        def body(state):
            alive_g, in_mis_l, rnd = state
            alive_l = jax.lax.dynamic_slice(alive_g, (off,), (n_local,))
            # ① tiled neighbour max (local rows, global columns)
            max_np = _local_nbr_max(
                tiles, tile_rows, tile_cols, select, alive_g, rps, T
            )
            if two_pass:
                pend_l = alive_l & (select_l >= max_np)
                pend_g = gather_bool(pend_l)
                max_res = _local_nbr_max(
                    tiles, tile_rows, tile_cols, resolve, pend_g, rps, T
                )
                cand_l = pend_l & (resolve_l > max_res)
            else:
                cand_l = alive_l & (select_l > max_np)
            # ② tiled SpMV against the gathered global candidate vector.
            # Per-round active-column flags (engine-layer metadata): every
            # shard sees the same gathered C, so the empty-C tile skip is
            # applied identically — and exactly — shard-locally.
            cand_g = gather_bool(cand_l)
            rhs = jnp.zeros((cand_g.shape[0], cfg.lanes), dtype=jnp.float32)
            rhs = rhs.at[:, 0].set(cand_g.astype(jnp.float32))
            rhs = rhs.at[:, 1].set(alive_g.astype(jnp.float32))
            flags = block_col_flags(cand_g, T)
            n_c = tile_spmv(
                tiles, tile_rows, tile_cols, rhs, rps, T, col_flags=flags
            )[:, 0]
            # ③ local own-state update, then gather the new frontier
            in_mis_l = in_mis_l | cand_l
            alive_l = alive_l & ~cand_l & ~(n_c > 0)
            alive_g = gather_bool(alive_l)
            return alive_g, in_mis_l, rnd + 1

        alive0_l = (jnp.arange(n_local) + off) < n_nodes
        alive0_g = gather_bool(alive0_l)
        in_mis0 = jnp.zeros((n_local,), dtype=bool)
        alive_g, in_mis_l, rounds = jax.lax.while_loop(
            cond, body, (alive0_g, in_mis0, jnp.int32(0))
        )
        return in_mis_l, rounds

    shard_spec = P(axis)
    return jax.shard_map(
        body_fn,
        mesh=mesh,
        in_specs=(shard_spec, shard_spec, shard_spec, P(), P()),
        out_specs=(shard_spec, P()),
        check_vma=False,
    )


def build_distributed_mis(
    sharded: ShardedTiledGraph,
    mesh: Mesh,
    cfg: DistConfig = DistConfig(),
):
    """Live-run wrapper around `make_mis_step_fn`, closed over the shards."""

    def run(pri: Priorities, two_pass: Optional[bool] = None) -> DistMISResult:
        two = (pri.resolve is not None) if two_pass is None else two_pass
        fn = make_mis_step_fn(
            mesh, cfg,
            n_nodes=sharded.n_nodes,
            tile_size=sharded.tile_size,
            rows_per_shard=sharded.rows_per_shard,
            two_pass=two,
        )
        n_padded = sharded.n_padded
        pad_to = lambda x: jnp.pad(
            x, (0, n_padded - x.shape[0]), constant_values=int(_NEG)
        )
        select = pad_to(pri.select)
        resolve = pad_to(
            pri.resolve
            if pri.resolve is not None
            else jnp.full_like(pri.select, _NEG)
        )
        in_mis, rounds = fn(
            sharded.tiles, sharded.tile_rows, sharded.tile_cols, select, resolve
        )
        return DistMISResult(in_mis=in_mis, rounds=rounds)

    return run
