"""Per-kernel shape/dtype sweeps: every Pallas kernel (interpret mode)
against its pure-jnp oracle in ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core.tiling import build_block_tiles
from repro.graphs.generators import erdos_renyi
from repro.kernels import embedding_bag, tc_neighbor_max, tc_spmv
from repro.kernels.ref import (
    embedding_bag_ref,
    tc_neighbor_max_ref,
    tc_spmv_ref,
)

_NEG = -(1 << 30)


def _tiled(n, deg, T, seed):
    g = erdos_renyi(n, avg_deg=deg, seed=seed)
    return g, build_block_tiles(g, tile_size=T)


@pytest.mark.parametrize("T", [8, 16, 32, 64, 128])
@pytest.mark.parametrize("lanes", [1, 8])
def test_spmv_shape_sweep(T, lanes):
    g, tiled = _tiled(4 * T + 7, 6.0, T, seed=T)
    rhs = jax.random.normal(jax.random.key(1), (tiled.n_padded, lanes), jnp.float32)
    out = tc_spmv(tiled, rhs)
    ref = tc_spmv_ref(tiled.tiles, tiled.tile_rows, tiled.tile_cols, rhs,
                      tiled.n_block_rows)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spmv_dtype_sweep(dtype):
    g, tiled = _tiled(150, 8.0, 32, seed=0)
    rhs = jax.random.normal(jax.random.key(2), (tiled.n_padded, 4)).astype(dtype)
    out = tc_spmv(tiled, rhs)
    ref = tc_spmv_ref(tiled.tiles, tiled.tile_rows, tiled.tile_cols,
                      rhs.astype(jnp.float32), tiled.n_block_rows)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=tol, atol=tol)


@pytest.mark.parametrize("skip_dma", [False, True])
def test_spmv_col_flags(skip_dma):
    """Empty-column skipping must not change results (paper's early exit)."""
    g, tiled = _tiled(200, 6.0, 16, seed=3)
    flags = (jax.random.uniform(jax.random.key(3), (tiled.n_block_cols,)) > 0.5)
    flags_i = flags.astype(jnp.int32)
    rhs = jax.random.normal(jax.random.key(4), (tiled.n_padded, 2), jnp.float32)
    # zero out gated columns so flagged-off slabs are genuinely empty
    rhs = rhs * jnp.repeat(flags_i, tiled.tile_size)[:, None].astype(jnp.float32)
    out = tc_spmv(tiled, rhs, col_flags=flags_i, skip_dma=skip_dma)
    ref = tc_spmv_ref(tiled.tiles, tiled.tile_rows, tiled.tile_cols, rhs,
                      tiled.n_block_rows)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T", [8, 16, 64])
@pytest.mark.parametrize("mask_frac", [0.0, 0.4, 1.0])
def test_neighbor_max_sweep(T, mask_frac):
    g, tiled = _tiled(3 * T + 5, 7.0, T, seed=T + 1)
    p = jax.random.randint(jax.random.key(5), (tiled.n_padded,), 0, 1 << 20,
                           dtype=jnp.int32)
    mask = jax.random.uniform(jax.random.key(6), (tiled.n_padded,)) >= mask_frac
    out = tc_neighbor_max(tiled, p, mask)
    pm = jnp.where(mask, p, _NEG)
    ref = tc_neighbor_max_ref(tiled.tiles, tiled.tile_rows, tiled.tile_cols,
                              pm, tiled.n_block_rows)
    assert bool(jnp.all(out == ref))


@pytest.mark.parametrize("B,K,D", [(4, 1, 8), (16, 5, 16), (32, 13, 32)])
@pytest.mark.parametrize("weighted", [False, True])
def test_embedding_bag_sweep(B, K, D, weighted):
    V = 500
    table = jax.random.normal(jax.random.key(7), (V, D), jnp.float32)
    idx = jax.random.randint(jax.random.key(8), (B, K), 0, V, dtype=jnp.int32)
    w = (jax.random.uniform(jax.random.key(9), (B, K)) if weighted
         else jnp.ones((B, K)))
    out = embedding_bag(table, idx, w)
    ref = embedding_bag_ref(table, idx, w)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_spmv_matches_segment_path():
    """Tiled SpMV == edge-list segment_sum (the two paper paths agree)."""
    from repro.core.spmv import neighbor_sum_segment

    g, tiled = _tiled(300, 10.0, 32, seed=11)
    x = jax.random.normal(jax.random.key(12), (g.n_nodes,), jnp.float32)
    xp = jnp.pad(x, (0, tiled.n_padded - g.n_nodes))
    out_tiled = tc_spmv(tiled, xp[:, None])[: g.n_nodes, 0]
    out_seg = neighbor_sum_segment(g, x)
    assert_allclose(np.asarray(out_tiled), np.asarray(out_seg), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# tile lists split over several launches (kernels/tile_grid.py): a row cut
# by a launch edge resumes from the partial result the previous launch left
# ---------------------------------------------------------------------------

def _launch_cases(T=16, seed=13):
    from repro.core.tiling import pack_frontier_words, pack_priority_planes
    from repro.kernels.tc_neighbor_max import (
        tc_neighbor_max_bits_pallas, tc_neighbor_max_pallas,
    )
    from repro.kernels.tc_spmv import (
        tc_spmv_bits_pallas, tc_spmv_fused_bits_pallas,
        tc_spmv_fused_pallas, tc_spmv_pallas,
    )

    g, t8 = _tiled(100, 4.0, T, seed)
    tb = t8.to_storage("bitpack")
    n, nbr = t8.n_padded, t8.n_block_rows
    k = jax.random.split(jax.random.key(seed), 4)
    rhs = jax.random.normal(k[0], (n, 2), jnp.float32)
    cand = jax.random.uniform(k[1], (n,)) > 0.6
    alive = (jax.random.uniform(k[2], (n,)) > 0.2) | cand
    p = jax.random.randint(k[3], (n,), 0, 1 << 20, dtype=jnp.int32)
    flags = (jnp.arange(t8.n_block_cols) % 3 != 0).astype(jnp.int32)
    cw, aw = pack_frontier_words(cand, T), pack_frontier_words(alive, T)
    sched = lambda t: (t.tiles, t.tile_rows, t.tile_cols)
    return {
        "spmv_int8": lambda **kw: tc_spmv_pallas(
            *sched(t8), rhs, nbr, col_flags=flags, **kw),
        "spmv_bitpack": lambda **kw: tc_spmv_pallas(
            *sched(tb), rhs, nbr, col_flags=flags, skip_dma=True, **kw),
        "fused_int8": lambda **kw: tc_spmv_fused_pallas(
            *sched(t8), rhs, cand.astype(jnp.int8), alive.astype(jnp.int8),
            nbr, **kw),
        "fused_bits": lambda **kw: tc_spmv_fused_bits_pallas(
            *sched(tb), cw, aw, nbr, col_flags=flags, **kw),
        "spmv_bits": lambda **kw: tc_spmv_bits_pallas(
            *sched(tb), cw, nbr, **kw),
        "nbr_max_bitpack": lambda **kw: tc_neighbor_max_pallas(
            *sched(tb), jnp.where(alive, p, _NEG), nbr, **kw),
        "plane_resolve": lambda **kw: tc_neighbor_max_bits_pallas(
            *sched(tb), pack_priority_planes(-p - 1, T, 32, signed=True),
            aw, nbr, signed=True, **kw),
    }, t8


@pytest.mark.parametrize("tiles_per_launch", [10, 24])
@pytest.mark.parametrize("name", [
    "spmv_int8", "spmv_bitpack", "fused_int8", "fused_bits", "spmv_bits",
    "nbr_max_bitpack", "plane_resolve",
])
def test_kernel_split_over_launches_matches_one_launch(name, tiles_per_launch):
    """Every BSR kernel gives the same result whether its tile list runs
    as one launch or as several short ones, which cut block rows apart."""
    cases, t = _launch_cases()
    run = cases[name]
    assert t.n_tiles > tiles_per_launch         # at least two launches
    whole = jax.tree_util.tree_leaves(run(interpret=True))
    split = jax.tree_util.tree_leaves(
        run(interpret=True, tiles_per_launch=tiles_per_launch)
    )
    # rows no tile visits are never written (the ops wrappers patch them)
    covered = np.asarray(t.row_starts[1:] > t.row_starts[:-1])
    for a, b in zip(whole, split):
        a, b = np.asarray(a), np.asarray(b)
        rows = covered if a.shape[0] == covered.shape[0] \
            else np.repeat(covered, t.tile_size)
        np.testing.assert_array_equal(a[rows], b[rows])
