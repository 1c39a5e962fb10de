"""Launching a BSR kernel over a tile list of any length.

Every BSR kernel walks the stored tile list with one grid step per tile and
reads that step's block row, block column and column flag from scalar
memory, where Pallas prefetches them whole.  A TPU v5e has 1 MiB of it,
while full G2 at T = 16 stores 476,920 tiles: 5.7 MB of schedule.  So
`tile_pallas_call` cuts the list into launches of at most
`TILES_PER_LAUNCH` tiles, one `pallas_call` each.

A launch's schedule is its slice of the tile list with one block row of
context on each side (-1 past either end of the list):

    rows[i]      block row of the tile before step i
    rows[i + 1]  block row of step i's tile
    rows[i + 2]  block row of the tile after step i

so a kernel starts an output row where rows[i] != rows[i + 1] and ends one
where rows[i + 1] != rows[i + 2], whether or not a launch boundary falls
inside the row.  Every launch after the first writes into the previous
launch's outputs (aliased), so the rows it never visits keep their values,
and a row it resumes at step 0 reads back the partial accumulator the
previous launch left (`resume=True`).

Kernel signature: `(rows, cols, flags, tile, *inputs, *previous_outputs,
*outputs, resume)`, with `previous_outputs` present only when `resume`.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# three schedule words per tile: 384 KiB of the v5e's 1 MiB scalar memory
TILES_PER_LAUNCH = 32768


def _tile_index(offset: int, ndim: int) -> Callable:
    rest = (0,) * (ndim - 1)
    return lambda i, rows, cols, flags: (i + offset,) + rest


def _row_index(ndim: int) -> Callable:
    rest = (0,) * (ndim - 1)
    return lambda i, rows, cols, flags: (rows[i + 1],) + rest


def _col_index(ndim: int, skip_dma: bool) -> Callable:
    """Block-column index map.  With `skip_dma` a flagged-off column's DMA
    is retargeted at block 0: its work is predicated off, so the HBM read
    is saved on TPU with no change to the result."""
    rest = (0,) * (ndim - 1)
    if skip_dma:
        return lambda i, rows, cols, flags: (
            jnp.where(flags[i] != 0, cols[i], 0),) + rest
    return lambda i, rows, cols, flags: (cols[i],) + rest


def tile_pallas_call(
    kernel: Callable,
    *,
    tiles: jnp.ndarray,
    tile_rows: jnp.ndarray,
    tile_cols: jnp.ndarray,
    col_flags: jnp.ndarray | None,
    inputs: Sequence[Tuple[jnp.ndarray, Tuple[int, ...], str]],
    outputs: Sequence[Tuple[jax.ShapeDtypeStruct, Tuple[int, ...]]],
    carried: Sequence[int],
    interpret: bool,
    skip_dma: bool = False,
    tiles_per_launch: int = TILES_PER_LAUNCH,
) -> list:
    """Run `kernel` with one grid step per tile, in launches that fit the
    scalar memory; returns the outputs as a list.

    `inputs` are `(array, block_shape, by)` with `by` "col" (the tile's
    block column; honours `skip_dma`) or "row" (its block row).  `outputs`
    are `(shape, block_shape)`, all blocked by block row.  `carried` names
    the outputs a resumed row reads back: its accumulators."""
    nt = int(tiles.shape[0])
    flags = (
        jnp.ones((nt,), jnp.int32) if col_flags is None
        else col_flags[tile_cols].astype(jnp.int32)
    )
    edge = jnp.full((1,), -1, jnp.int32)
    rows = jnp.concatenate([edge, tile_rows.astype(jnp.int32), edge])
    cols = tile_cols.astype(jnp.int32)
    tile_block = (1,) + tuple(tiles.shape[1:])
    in_specs = [
        pl.BlockSpec(block, _row_index(len(block)) if by == "row"
                     else _col_index(len(block), skip_dma))
        for _, block, by in inputs
    ]
    out_specs = [pl.BlockSpec(block, _row_index(len(block)))
                 for _, block in outputs]
    outs = None
    for offset in range(0, nt, tiles_per_launch):
        n = min(tiles_per_launch, nt - offset)
        resume = outs is not None
        specs = [pl.BlockSpec(tile_block, _tile_index(offset, len(tile_block)))]
        args = [tiles]
        specs += in_specs
        args += [x for x, _, _ in inputs]
        aliases = {}
        if resume:
            for j, prev in enumerate(outs):
                specs.append(out_specs[j] if j in carried
                             else pl.BlockSpec(memory_space=pl.ANY))
                aliases[3 + len(args)] = j    # 3 scalar-prefetch operands
                args.append(prev)
        outs = pl.pallas_call(
            functools.partial(kernel, resume=resume),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(n,),
                in_specs=specs, out_specs=out_specs,
            ),
            out_shape=[shape for shape, _ in outputs],
            input_output_aliases=aliases,
            interpret=interpret,
        )(rows[offset: offset + n + 2], cols[offset: offset + n],
          flags[offset: offset + n], *args)
    return list(outs)
