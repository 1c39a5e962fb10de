"""Round-engine layer: registry contract, fused ②+③ vs the jnp oracle on
degenerate tilings (empty block-rows, isolated vertices), live col_flags
equivalence, and the every-engine-same-MIS property on seeded graphs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    TCMISConfig,
    build_block_tiles,
    engine_names,
    get_engine,
    is_valid_mis,
    tc_mis,
)
from repro.core.engine import EngineContext, block_col_flags
from repro.core.tiling import pack_vertex_vector
from repro.graphs.graph import from_edges
from repro.kernels.ops import tc_spmv_fused

ALL_ENGINES = ("segment", "tiled_ref", "tiled_pallas", "fused_pallas")


def _random_graph(n, density, seed):
    rng = np.random.default_rng(seed)
    m = int(density * n * (n - 1) / 2)
    src = rng.integers(0, n, max(m, 1))
    dst = rng.integers(0, n, max(m, 1))
    return from_edges(src, dst, n)


def _clustered_graph(n=100, tile=16, seed=0):
    """Edges confined to vertices [0, n//3): most block-rows store no tiles
    and vertices ≥ n//3 are isolated — the fused kernel's patched epilogue
    (uncovered rows) and the trivial rule must both fire."""
    rng = np.random.default_rng(seed)
    hi = max(n // 3, 2)
    src = rng.integers(0, hi, 4 * hi)
    dst = rng.integers(0, hi, 4 * hi)
    g = from_edges(src, dst, n)
    return g, build_block_tiles(g, tile_size=tile)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

def test_registry_contents_and_aliases():
    assert set(ALL_ENGINES) <= set(engine_names())
    assert get_engine("ref") is get_engine("tiled_ref")
    assert get_engine("pallas") is get_engine("tiled_pallas")
    assert get_engine("fused") is get_engine("fused_pallas")
    assert get_engine("fused_pallas").fused
    assert not get_engine("tiled_ref").fused
    with pytest.raises(ValueError, match="unknown engine"):
        get_engine("cuda_warp")


# --------------------------------------------------------------------------
# fused ②+③ kernel vs the split oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("graph_kind", ["random", "clustered"])
def test_fused_step_matches_oracle(seed, graph_kind):
    """fused_step's (new_alive, mis_add) == oracle phase ② + phase ③ rules,
    including block-rows with no tiles and isolated vertices."""
    if graph_kind == "random":
        g = _random_graph(150, 0.05, seed)
        tiled = build_block_tiles(g, tile_size=16)
    else:
        g, tiled = _clustered_graph(n=100 + 7 * seed, tile=16, seed=seed)
    cfg = TCMISConfig()
    ctx = EngineContext(g=g, tiled=tiled, cfg=cfg)
    ref = get_engine("tiled_ref")
    fused = get_engine("fused_pallas")

    key = jax.random.key(seed)
    alive = pack_vertex_vector(
        jax.random.uniform(key, (g.n_nodes,)) < 0.8, tiled
    )
    cand = alive & pack_vertex_vector(
        jax.random.uniform(jax.random.key(seed + 99), (g.n_nodes,)) < 0.3,
        tiled,
    )
    flags = ref.col_flags(ctx, cand, alive)

    n_c = ref.phase2_counts(ctx, cand, alive, flags)
    want_alive = alive & ~cand & ~(n_c > 0)
    got_alive, got_mis = fused.fused_step(ctx, cand, alive, flags)
    assert bool(jnp.all(got_alive == want_alive))
    assert bool(jnp.all(got_mis == cand))


@pytest.mark.parametrize("skip_dma", [False, True])
def test_fused_kernel_nc_matches_oracle_with_flags(skip_dma):
    """The fused kernel's N_c output equals the flag-gated oracle on every
    lane (skipped slabs contribute nothing anywhere)."""
    from repro.core.engine import tile_spmv

    g, tiled = _clustered_graph(n=90, tile=16, seed=4)
    rhs = jax.random.normal(jax.random.key(0), (tiled.n_padded, 4), jnp.float32)
    cand = jax.random.uniform(jax.random.key(1), (tiled.n_padded,)) < 0.3
    rhs = rhs.at[:, 0].set(cand.astype(jnp.float32))
    alive = jnp.ones((tiled.n_padded,), bool)
    flags = block_col_flags(cand, tiled.tile_size)

    n_c, _, _ = tc_spmv_fused(
        tiled, rhs, cand, alive, col_flags=flags, skip_dma=skip_dma
    )
    want = tile_spmv(
        tiled.tiles, tiled.tile_rows, tiled.tile_cols, rhs,
        tiled.n_block_rows, tiled.tile_size, col_flags=flags,
    )
    # uncovered block-rows are patched to zero by the wrapper
    covered = np.zeros(tiled.n_block_rows, bool)
    covered[np.asarray(tiled.tile_rows[: max(tiled.n_tiles, 1)])] = tiled.n_tiles > 0
    want = jnp.where(
        jnp.repeat(jnp.asarray(covered), tiled.tile_size)[:, None], want, 0.0
    )
    np.testing.assert_allclose(
        np.asarray(n_c), np.asarray(want), rtol=1e-5, atol=1e-5
    )


# --------------------------------------------------------------------------
# whole-algorithm equivalence across engines
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("heuristic", ["ecl", "h3"])
def test_every_engine_same_valid_mis(seed, heuristic):
    """Same seeded priorities ⇒ all four engines return the SAME valid MIS
    (the acceptance contract of the engine layer)."""
    g = _random_graph(120 + 30 * seed, 0.04, seed)
    tiled = build_block_tiles(g, tile_size=16)
    key = jax.random.key(seed)
    ref = None
    for backend in ALL_ENGINES:
        res = tc_mis(g, tiled, key, TCMISConfig(heuristic=heuristic, backend=backend))
        assert bool(res.converged), backend
        assert is_valid_mis(g, res.in_mis), backend
        if ref is None:
            ref = res.in_mis
        else:
            assert bool(jnp.all(res.in_mis == ref)), backend


@pytest.mark.parametrize("backend", ["fused_pallas", "tiled_pallas"])
def test_skip_dma_and_tiled_phase1_equivalent(backend):
    g, tiled = _clustered_graph(n=140, tile=16, seed=7)
    key = jax.random.key(0)
    ref = tc_mis(g, tiled, key, TCMISConfig(backend="tiled_ref"))
    got = tc_mis(
        g, tiled, key,
        TCMISConfig(backend=backend, phase1="tiled", skip_dma=True),
    )
    assert is_valid_mis(g, got.in_mis)
    assert bool(jnp.all(got.in_mis == ref.in_mis))


def test_isolated_vertices_all_selected():
    """Isolated vertices must end up in the MIS under every engine (the
    fused kernel reaches them only via the uncovered-row patch)."""
    g, tiled = _clustered_graph(n=100, tile=16, seed=1)
    deg = np.asarray(g.degrees())
    isolated = np.flatnonzero(deg == 0)
    assert isolated.size > 0, "fixture must contain isolated vertices"
    for backend in ALL_ENGINES:
        res = tc_mis(g, tiled, jax.random.key(5), TCMISConfig(backend=backend))
        assert bool(jnp.all(res.in_mis[isolated])), backend
