"""Neighbourhood operators — the two execution paths of the paper.

* the **segment path** (`*_segment`): gather-by-edge + `segment_{sum,max}`
  over the edge list.  This is the JAX analogue of ECL-MIS's CSR traversal on
  CUDA cores — irregular, but the natural baseline.
* the **tiled path** (`*_tiled`): dense T×T tiles in BSR order.  `spmv_tiled`
  is the paper's phase-② `N_c = A × C` (MXU on TPU; the pure-jnp form here is
  also the Pallas kernel's oracle).  `neighbor_max_tiled` is our beyond-paper
  extension: phase ① on the *same* tile schedule (DESIGN.md §6.1).

Both paths accept multi-lane right-hand sides (T, L): lane-packing C / alive /
priorities into one pass is free on a 128-lane TPU (DESIGN.md §6.2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tiling import BlockTiledGraph
from repro.graphs.graph import Graph

_NEG = np.int32(-(1 << 30))  # numpy scalar: safe to create at import time under a trace


# --------------------------------------------------------------------------
# segment (edge-list) path — the CC baseline substrate
# --------------------------------------------------------------------------

def neighbor_sum_segment(g: Graph, x: jnp.ndarray) -> jnp.ndarray:
    """N_c(v) = Σ_{u∈N(v)} x(u) via gather + segment_sum (CSR-style path)."""
    contrib = jnp.where(g.edge_mask, x[g.senders], 0)
    return jax.ops.segment_sum(contrib, g.receivers, num_segments=g.n_nodes + 1)[
        : g.n_nodes
    ]


def neighbor_max_segment(
    g: Graph, p: jnp.ndarray, mask: jnp.ndarray
) -> jnp.ndarray:
    """Max_Np(v) = max_{u∈N(v), mask(u)} p(u); −inf-like where no live nbr.

    The mask folds into the priority at the vertex, so a pass gathers one
    value per half-edge, not a mask bit and a priority.  The dummy slot
    `n_nodes` holds `_NEG`, so sentinel half-edges contribute nothing."""
    pm = jnp.where(jnp.append(mask, False), jnp.append(p, _NEG), _NEG)
    return jax.ops.segment_max(
        pm[g.senders], g.receivers, num_segments=g.n_nodes + 1
    )[: g.n_nodes]


def neighbor_any_segment(g: Graph, flag: jnp.ndarray) -> jnp.ndarray:
    """Does v have a neighbour with flag set? (bool, no counting needed)."""
    contrib = (g.edge_mask & flag[g.senders]).astype(jnp.int32)
    s = jax.ops.segment_max(contrib, g.receivers, num_segments=g.n_nodes + 1)
    return s[: g.n_nodes] > 0


# --------------------------------------------------------------------------
# tiled (BSR) path — the paper's phase ② + the tiled phase ① extension
# --------------------------------------------------------------------------

def spmv_tiled(
    tiled: BlockTiledGraph,
    rhs: jnp.ndarray,
    *,
    backend: str = "ref",
    col_flags: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """N = A @ rhs over the BSR tiles.

    rhs: (n_padded, L) multi-lane right-hand side (lane 0 is the paper's C).
    col_flags: (n_block_cols,) active-column flags — gated slabs contribute
    nothing (the empty-C skip; exact on every lane, see core.engine).
    Returns (n_padded, L) float32.

    backend='ref'    pure-jnp (this function doubles as the kernel oracle)
    backend='pallas' the TPU Pallas kernel (interpret-mode on CPU)
    """
    if backend == "pallas":
        from repro.kernels.ops import tc_spmv

        return tc_spmv(tiled, rhs, col_flags=col_flags)
    from repro.core.engine import tile_spmv

    return tile_spmv(
        tiled.tiles, tiled.tile_rows, tiled.tile_cols, rhs,
        tiled.n_block_rows, tiled.tile_size, col_flags=col_flags,
    )


def neighbor_max_tiled(
    tiled: BlockTiledGraph,
    p: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    backend: str = "ref",
) -> jnp.ndarray:
    """Tiled phase ①: Max_Np via masked max over the same BSR schedule.

    p, mask: (n_padded,).  Returns (n_padded,) int32, −inf-like where no live
    neighbour.  VPU work (max has no MXU form), but identical memory schedule
    to `spmv_tiled` — the point of DESIGN.md §6.1.
    """
    if backend == "pallas":
        from repro.kernels.ops import tc_neighbor_max

        return tc_neighbor_max(tiled, p, mask)
    from repro.core.engine import tile_neighbor_max

    return tile_neighbor_max(
        tiled.tiles, tiled.tile_rows, tiled.tile_cols,
        jnp.where(mask, p, _NEG), tiled.n_block_rows, tiled.tile_size,
    )
