"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy, decided here and nowhere else (`resolve_interpret`): off
TPU the kernels run `interpret=True`, which executes the kernel body in
Python per grid step — bit-identical semantics to the TPU lowering, minus
performance.  On TPU the same call sites compile the real Mosaic kernels.
`interpret=None` means "auto by backend".  The raw `*_pallas` wrappers take
`interpret` as a required keyword, so no caller interprets on the chip by
default.

Storage axis (DESIGN.md §11): `tiled.tiles` is passed to the kernels AS
STORED — dense int8 or bit-packed uint32 — and never densified here; a
pre-kernel unpack would materialise the (nt, T, T) array in HBM and forfeit
the 8× DMA saving (CI guards this: `tools/ci_guards.py`).  The kernels
detect the format from the dtype and unpack per-tile in VMEM.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tiling import BlockTiledGraph
from repro.kernels.tc_spmv import tc_spmv_pallas
from repro.kernels.tc_neighbor_max import tc_neighbor_max_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas

_NEG = np.int32(-(1 << 30))  # numpy scalar: safe to create at import time under a trace


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The one interpret-mode decision: explicit wins, None = off TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def tc_spmv(
    tiled: BlockTiledGraph,
    rhs: jnp.ndarray,
    *,
    col_flags: jnp.ndarray | None = None,
    interpret: Optional[bool] = None,
    skip_dma: bool = False,
) -> jnp.ndarray:
    """Paper phase ②: N = A × rhs on the block-tiled adjacency."""
    return tc_spmv_pallas(
        tiled.tiles,
        tiled.tile_rows,
        tiled.tile_cols,
        rhs,
        tiled.n_block_rows,
        col_flags=col_flags,
        interpret=resolve_interpret(interpret),
        skip_dma=skip_dma,
    )


def tc_neighbor_max(
    tiled: BlockTiledGraph,
    p: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Beyond-paper phase ①: Max_Np on the same tile schedule."""
    pm = jnp.where(mask, p, _NEG)
    return tc_neighbor_max_pallas(
        tiled.tiles,
        tiled.tile_rows,
        tiled.tile_cols,
        pm,
        tiled.n_block_rows,
        interpret=resolve_interpret(interpret),
    )


def embedding_bag(
    table: jnp.ndarray,
    indices: jnp.ndarray,
    weights: jnp.ndarray | None = None,
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Recsys embedding-bag (weighted sum over a bag of rows)."""
    if weights is None:
        weights = jnp.ones(indices.shape, dtype=jnp.float32)
    return embedding_bag_pallas(
        table, indices, weights, interpret=resolve_interpret(interpret)
    )


def tc_spmv_fused(
    tiled: BlockTiledGraph,
    rhs: jnp.ndarray,
    cand: jnp.ndarray,          # (n_padded,) bool
    alive: jnp.ndarray,         # (n_padded,) bool
    *,
    col_flags: jnp.ndarray | None = None,
    interpret: Optional[bool] = None,
    skip_dma: bool = False,
):
    """Fused phase ②+③ (DESIGN.md §6.3): one kernel pass emits N_c AND the
    updated (alive, in_mis_add) masks.

    Block-rows with no stored tiles never enter the kernel grid, so their
    epilogue is patched here from the trivial rule (no neighbours ⇒ n_c=0 ⇒
    alive' = alive ∧ ¬cand, mis_add = cand).
    """
    from repro.kernels.tc_spmv import tc_spmv_fused_pallas

    T = tiled.tile_size
    n_c, new_alive, mis_add = tc_spmv_fused_pallas(
        tiled.tiles, tiled.tile_rows, tiled.tile_cols, rhs,
        cand.astype(jnp.int8), alive.astype(jnp.int8), tiled.n_block_rows,
        col_flags=col_flags,
        interpret=resolve_interpret(interpret),
        skip_dma=skip_dma,
    )
    # static per-graph coverage: which block-rows own at least one tile
    covered_rows = jnp.zeros((tiled.n_block_rows,), bool).at[
        tiled.tile_rows[: max(tiled.n_tiles, 1)]
    ].set(tiled.n_tiles > 0)
    covered = jnp.repeat(covered_rows, T)
    new_alive_b = jnp.where(covered, new_alive != 0, alive & ~cand)
    mis_add_b = jnp.where(covered, mis_add != 0, cand)
    n_c = jnp.where(covered[:, None], n_c, 0.0)
    return n_c, new_alive_b, mis_add_b


# ---------------------------------------------------------------------------
# bitwise frontier wrappers (DESIGN.md §13): packed (nbc, W) uint32 words in,
# packed words out.  Block-rows with no stored tiles never enter the kernel
# grid, so each wrapper patches them from the trivial rule — same contract as
# the dense `tc_spmv_fused` above, word-wise.
# ---------------------------------------------------------------------------

def _covered_block_rows(tiled: BlockTiledGraph) -> jnp.ndarray:
    """(n_block_rows,) bool — block-rows owning at least one stored tile."""
    return jnp.zeros((tiled.n_block_rows,), bool).at[
        tiled.tile_rows[: max(tiled.n_tiles, 1)]
    ].set(tiled.n_tiles > 0)


def _tiles_words(tiled: BlockTiledGraph, tiles_words) -> jnp.ndarray:
    if tiles_words is not None:
        return tiles_words
    from repro.core.tiling import tiles_as_words

    return tiles_as_words(tiled.tiles, tiled.tile_size)


def tc_spmv_bits(
    tiled: BlockTiledGraph,
    rhs_words: jnp.ndarray,      # (nbc, W) uint32 — packed candidate vector
    *,
    tiles_words: jnp.ndarray | None = None,   # precomputed word tiles
    col_flags: jnp.ndarray | None = None,
    interpret: Optional[bool] = None,
    skip_dma: bool = False,
) -> jnp.ndarray:
    """Phase ② on packed words: hit = (A × C) > 0.  (nbr, W) uint32 out.

    Pass `tiles_words` (from `tiling.tiles_as_words`, cached per solve in
    the engine's BitwiseContext) to avoid re-deriving it per call."""
    from repro.kernels.tc_spmv import tc_spmv_bits_pallas

    hit = tc_spmv_bits_pallas(
        _tiles_words(tiled, tiles_words),
        tiled.tile_rows, tiled.tile_cols, rhs_words, tiled.n_block_rows,
        col_flags=col_flags,
        interpret=resolve_interpret(interpret),
        skip_dma=skip_dma,
    )
    # uncovered block-rows have no neighbours ⇒ no hits (word 0)
    return jnp.where(_covered_block_rows(tiled)[:, None], hit, jnp.uint32(0))


def tc_neighbor_max_bits(
    tiled: BlockTiledGraph,
    planes: jnp.ndarray,         # (n_bits, nbc, W) uint32 priority planes
    mask_words: jnp.ndarray,     # (nbc, W) uint32 packed mask
    *,
    tiles_words: jnp.ndarray | None = None,
    signed: bool = False,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Phase ① on packed words: the priority-plane scan kernel.

    Uncovered block-rows are patched to int32 min — the fill value
    `jax.ops.segment_max` gives rows no tile ever visits, so the jnp clz
    formulation and this kernel stay bit-identical everywhere."""
    from repro.kernels.tc_neighbor_max import tc_neighbor_max_bits_pallas

    out = tc_neighbor_max_bits_pallas(
        _tiles_words(tiled, tiles_words),
        tiled.tile_rows, tiled.tile_cols, planes, mask_words,
        tiled.n_block_rows,
        signed=signed,
        interpret=resolve_interpret(interpret),
    )
    covered = jnp.repeat(_covered_block_rows(tiled), tiled.tile_size)
    return jnp.where(covered, out, jnp.iinfo(jnp.int32).min)


def tc_spmv_fused_bits(
    tiled: BlockTiledGraph,
    cand_words: jnp.ndarray,     # (nbc, W) uint32
    alive_words: jnp.ndarray,    # (nbr, W) uint32
    *,
    tiles_words: jnp.ndarray | None = None,
    col_flags: jnp.ndarray | None = None,
    interpret: Optional[bool] = None,
    skip_dma: bool = False,
):
    """Fused ②+③ on packed words: (hit, new_alive, mis_add) word arrays."""
    from repro.kernels.tc_spmv import tc_spmv_fused_bits_pallas

    hit, new_alive, mis_add = tc_spmv_fused_bits_pallas(
        _tiles_words(tiled, tiles_words),
        tiled.tile_rows, tiled.tile_cols, cand_words, alive_words,
        tiled.n_block_rows,
        col_flags=col_flags,
        interpret=resolve_interpret(interpret),
        skip_dma=skip_dma,
    )
    covered = _covered_block_rows(tiled)[:, None]
    hit = jnp.where(covered, hit, jnp.uint32(0))
    new_alive = jnp.where(covered, new_alive, alive_words & ~cand_words)
    mis_add = jnp.where(covered, mis_add, cand_words)
    return hit, new_alive, mis_add
