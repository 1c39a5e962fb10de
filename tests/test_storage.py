"""The storage axis (DESIGN.md §11): 1-bit tile packing end-to-end.

Covers the bit-parity contract — bitpack solutions are BIT-IDENTICAL to
int8 for every registered engine on the local, batched and sharded routes —
plus the pack/unpack round-trip property, the auto-storage policy, the
plan-cache format-version migration, and the deprecation/validation
hygiene of the `storage` spellings.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from conftest import run_multidevice

from repro.api import (
    BITPACK_AUTO_THRESHOLD,
    PlanCache,
    SolveOptions,
    Solver,
    resolve_storage,
)
from repro.api.plan import _META_LEN, _PLAN_VERSION
from repro.core.engine import engine_names, tile_spmv
from repro.core.tc_mis import _tc_mis_impl
from repro.core.tiling import (
    STORAGES,
    build_block_tiles,
    pack_tile_bits,
    packed_words,
    tile_stats,
    unpack_tile_bits,
)
from repro.graphs.generators import erdos_renyi, grid2d, powerlaw

# ---------------------------------------------------------------------------
# pack/unpack round-trip
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(
    T=st.sampled_from([8, 16, 32, 64, 128, 256]),
    density=st.floats(0.0, 1.0),
    seed=st.integers(0, 1000),
)
def test_pack_unpack_roundtrip(T, density, seed):
    rng = np.random.default_rng(seed)
    tiles = (rng.random((3, T, T)) < density).astype(np.int8)
    packed = pack_tile_bits(tiles)
    assert packed.shape == (3, T, packed_words(T))
    assert packed.dtype == np.uint32
    np.testing.assert_array_equal(
        np.asarray(unpack_tile_bits(jnp.asarray(packed), T)), tiles
    )


def test_unpack_is_jit_compatible():
    tiles = (np.random.default_rng(0).random((4, 32, 32)) < 0.3).astype(np.int8)
    packed = jnp.asarray(pack_tile_bits(tiles))
    out = jax.jit(lambda p: unpack_tile_bits(p, 32))(packed)
    np.testing.assert_array_equal(np.asarray(out), tiles)


def test_build_block_tiles_bitpack_matches_int8():
    g = erdos_renyi(300, avg_deg=6.0, seed=1)
    a = build_block_tiles(g, tile_size=32, pad_tiles_to=64)
    b = build_block_tiles(g, tile_size=32, pad_tiles_to=64, storage="bitpack")
    assert b.storage == "bitpack" and b.tiles.dtype == jnp.uint32
    assert b.n_tiles_pad == a.n_tiles_pad  # padding tiles pack too
    np.testing.assert_array_equal(
        np.asarray(unpack_tile_bits(b.tiles, 32)), np.asarray(a.tiles)
    )
    # converters round-trip between the formats
    np.testing.assert_array_equal(
        np.asarray(b.to_storage("int8").tiles), np.asarray(a.tiles)
    )
    np.testing.assert_array_equal(
        np.asarray(a.to_storage("bitpack").tiles), np.asarray(b.tiles)
    )


# ---------------------------------------------------------------------------
# stats fixes ride along with the storage axis
# ---------------------------------------------------------------------------


def test_nnz_density_and_memory_bytes_both_storages():
    g = erdos_renyi(200, avg_deg=5.0, seed=2)
    a = build_block_tiles(g, tile_size=16)
    b = a.to_storage("bitpack")
    assert a.nnz() == b.nnz() == g.n_edges
    assert a.density() == b.density() > 0
    # memory_bytes now includes row_starts, and the bitpack payload is the
    # packed word count — not an unpacked shadow
    for t in (a, b):
        idx_bytes = (t.tile_rows.size + t.tile_cols.size + t.row_starts.size) * 4
        assert t.memory_bytes() == t.tile_payload_bytes() + idx_bytes
    assert b.tile_payload_bytes() * 4 == a.tile_payload_bytes()  # T=16: W=1
    sa, sb = tile_stats(a), tile_stats(b)
    assert sa["intra_tile_density"] == sb["intra_tile_density"]
    assert (sa["storage"], sb["storage"]) == ("int8", "bitpack")


def test_tile_payload_reduction_at_t128():
    g = erdos_renyi(1024, avg_deg=8.0, seed=3)
    a = build_block_tiles(g, tile_size=128)
    b = a.to_storage("bitpack")
    assert a.tile_payload_bytes() / b.tile_payload_bytes() == 8.0
    assert a.memory_bytes() / b.memory_bytes() >= 6.0


# ---------------------------------------------------------------------------
# bit-parity: every engine, local route
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", engine_names())
def test_solver_bit_parity_every_engine(engine):
    g = erdos_renyi(90, avg_deg=5.0, seed=4)
    res = {}
    for storage in ("int8", "bitpack"):
        r = Solver(SolveOptions(
            engine=engine, tile_size=8, storage=storage, placement="local",
        )).solve(g)
        res[storage] = r
    np.testing.assert_array_equal(res["int8"].in_mis, res["bitpack"].in_mis)
    assert res["int8"].rounds == res["bitpack"].rounds
    assert res["bitpack"].plan.tiled.tiles.dtype == jnp.uint32


def test_profile_bit_parity():
    g = grid2d(8, 10)
    out = {}
    for storage in ("int8", "bitpack"):
        out[storage] = Solver(SolveOptions(
            engine="tiled_ref", tile_size=8, storage=storage,
        )).solve(g)
    np.testing.assert_array_equal(out["int8"].in_mis, out["bitpack"].in_mis)


# ---------------------------------------------------------------------------
# bit-parity: batched route (block-diagonal bucket + col_gate)
# ---------------------------------------------------------------------------


def test_solve_many_bucket_bit_parity():
    graphs = [
        erdos_renyi(70, avg_deg=4.0, seed=5),
        grid2d(6, 9),
        powerlaw(60, avg_deg=3.0, seed=6),
    ]
    outs = {}
    for storage in ("int8", "bitpack"):
        solver = Solver(SolveOptions(
            engine="tiled_ref", tile_size=8, storage=storage,
        ))
        outs[storage] = solver.solve_many(graphs)
    for a, b in zip(outs["int8"], outs["bitpack"]):
        assert a.placement == b.placement == "batched"
        np.testing.assert_array_equal(a.in_mis, b.in_mis)
        assert a.rounds == b.rounds
    # the bucket signature carries the storage (distinct compiled programs)
    assert outs["int8"][0].stats["bucket"].endswith(".int8")
    assert outs["bitpack"][0].stats["bucket"].endswith(".bitpack")


def test_col_gate_bit_parity():
    """The static col_gate (batch empty-slot gate) composes with either
    storage: gating trailing block-columns gives identical solutions."""
    g = erdos_renyi(60, avg_deg=4.0, seed=7)
    key = jax.random.key(0)
    res = {}
    for storage in ("int8", "bitpack"):
        tiled = build_block_tiles(g, tile_size=8, storage=storage)
        gate = jnp.ones((tiled.n_block_cols,), jnp.int32)
        opts = SolveOptions(engine="tiled_ref", tile_size=8, storage=storage)
        res[storage] = _tc_mis_impl(g, tiled, key, opts, col_gate=gate)
    np.testing.assert_array_equal(
        np.asarray(res["int8"].in_mis), np.asarray(res["bitpack"].in_mis)
    )


def test_mixed_storage_members_split_into_separate_buckets():
    """solve_many must not pack int8 and bitpack plans into one batch."""
    solver = Solver(SolveOptions(engine="tiled_ref", tile_size=8))
    plans = [
        solver.plans.plan(erdos_renyi(40, avg_deg=3.0, seed=8),
                          tile_size=8, storage="int8")[0],
        solver.plans.plan(erdos_renyi(44, avg_deg=3.0, seed=9),
                          tile_size=8, storage="bitpack")[0],
        solver.plans.plan(erdos_renyi(48, avg_deg=3.0, seed=10),
                          tile_size=8, storage="int8")[0],
    ]
    out = solver.solve_many(plans)
    assert [r.placement for r in out] == ["batched", "local", "batched"]
    for r in out:
        assert r.mis_size > 0


# ---------------------------------------------------------------------------
# bit-parity: sharded route
# ---------------------------------------------------------------------------


def test_sharded_bit_parity():
    out = run_multidevice("""
        import numpy as np
        from repro.api import Solver, SolveOptions
        from repro.graphs.generators import powerlaw
        g = powerlaw(1024, avg_deg=5.0, seed=11)
        res = {}
        for storage in ("int8", "bitpack"):
            r = Solver(SolveOptions(
                engine="tiled_ref", tile_size=32, storage=storage,
                placement="sharded",
            )).solve(g)
            assert r.placement == "sharded", r.placement
            res[storage] = r
        np.testing.assert_array_equal(
            res["int8"].in_mis, res["bitpack"].in_mis
        )
        assert res["int8"].rounds == res["bitpack"].rounds
        # and the sharded result matches the local route bit-for-bit
        local = Solver(SolveOptions(
            engine="tiled_ref", tile_size=32, storage="bitpack",
            placement="local",
        )).solve(g)
        np.testing.assert_array_equal(local.in_mis, res["bitpack"].in_mis)
        print("SHARDED_STORAGE_OK")
    """, n_devices=4)
    assert "SHARDED_STORAGE_OK" in out


# ---------------------------------------------------------------------------
# the auto policy
# ---------------------------------------------------------------------------


def test_resolve_storage_policy():
    # tiny graph: worst-case int8 payload under the threshold → int8
    assert resolve_storage("auto", 100, 400, 16) == "int8"
    # huge graph: far over the threshold → bitpack
    big_edges = BITPACK_AUTO_THRESHOLD  # E·T² ≥ threshold at any T
    assert resolve_storage("auto", 1 << 20, big_edges, 128) == "bitpack"
    # concrete spellings pass through
    assert resolve_storage("int8", 1 << 20, big_edges, 128) == "int8"
    assert resolve_storage("bitpack", 100, 400, 16) == "bitpack"
    with pytest.raises(ValueError, match="valid"):
        resolve_storage("packed", 100, 400, 16)


def test_solver_auto_storage_resolves_per_graph():
    small = erdos_renyi(60, avg_deg=4.0, seed=12)
    solver = Solver(SolveOptions(engine="tiled_ref", tile_size=8, storage="auto"))
    assert solver.plan(small).tiled.storage == "int8"
    # force the threshold down: the same policy flips to bitpack
    assert resolve_storage(
        "auto", small.n_nodes, small.n_edges, 8, threshold=1
    ) == "bitpack"


# ---------------------------------------------------------------------------
# validation / deprecation hygiene
# ---------------------------------------------------------------------------


def test_unknown_storage_spellings_rejected_with_valid_set():
    with pytest.raises(ValueError) as ei:
        SolveOptions(storage="uint1")
    assert "int8" in str(ei.value) and "bitpack" in str(ei.value)
    with pytest.raises(ValueError, match="valid"):
        build_block_tiles(erdos_renyi(10, avg_deg=2.0, seed=0),
                          tile_size=8, storage="dense")
    with pytest.raises(ValueError, match="valid"):
        build_block_tiles(
            erdos_renyi(10, avg_deg=2.0, seed=0), tile_size=8
        ).to_storage("nibble")
    assert STORAGES == ("int8", "bitpack")


# ---------------------------------------------------------------------------
# plan-cache format migration
# ---------------------------------------------------------------------------


def _rewrite_as_v1(path: str) -> None:
    """Rewrite a v2 npz as the pre-storage-axis v1 layout (6-int meta)."""
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    arrays["meta"] = arrays["meta"][:6]
    np.savez(path.replace(".npz", ""), **arrays)


def test_plan_cache_migration_smoke(tmp_path):
    """An old-format disk entry is detected, warned about, evicted and
    REBUILT — never mis-read as a current plan."""
    g = erdos_renyi(80, avg_deg=4.0, seed=13)
    cache = PlanCache(tile_size=8, cache_dir=str(tmp_path))
    plan, status = cache.plan(g)
    assert status == "built"
    path = cache._path(plan.key)
    _rewrite_as_v1(path)

    fresh = PlanCache(tile_size=8, cache_dir=str(tmp_path))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        plan2, status2 = fresh.plan(g)
    assert status2 == "built"           # rebuilt, not disk-loaded
    assert fresh.stats["evicted_stale"] == 1
    msgs = [str(w.message) for w in caught]
    assert any("stale plan-cache entry" in m for m in msgs), msgs
    np.testing.assert_array_equal(
        np.asarray(plan2.tiled.tiles), np.asarray(plan.tiled.tiles)
    )
    # the rebuilt entry is current-format: a third cache disk-hits it
    assert PlanCache(tile_size=8, cache_dir=str(tmp_path)).plan(g)[1] == "disk"


def test_plan_cache_migration_of_genuine_v1_keyed_entry(tmp_path):
    """A REAL v1 upgrade: the old entry sits at the v1 key path (storage
    was not part of the key then), so the disk miss at the current key must
    probe the legacy path, evict the orphan with a warning, and rebuild."""
    from repro.api.plan import _legacy_v1_cache_key

    g = erdos_renyi(60, avg_deg=4.0, seed=20)
    cache = PlanCache(tile_size=8, cache_dir=str(tmp_path))
    # manufacture the v1 entry exactly where a v1 process would have put it
    v1_path = cache._path(_legacy_v1_cache_key(g, 8, None))
    plan, _ = cache.plan(g)                    # v2 build (writes the v2 file)
    import shutil
    shutil.copy(cache._path(plan.key), v1_path)
    _rewrite_as_v1(v1_path)

    fresh = PlanCache(tile_size=8, cache_dir=str(tmp_path))
    import os
    os.unlink(cache._path(plan.key))           # leave ONLY the v1 orphan
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        _, status = fresh.plan(g)
    assert status == "built"
    assert fresh.stats["evicted_stale"] == 1
    assert not os.path.exists(v1_path)          # orphan cleaned up
    assert any("v1 key" in str(w.message) for w in caught)


def test_plan_cache_version_mismatch_evicts(tmp_path):
    """A versioned entry from a DIFFERENT format version is evicted too."""
    g = erdos_renyi(40, avg_deg=3.0, seed=14)
    cache = PlanCache(tile_size=8, cache_dir=str(tmp_path))
    plan, _ = cache.plan(g)
    path = cache._path(plan.key)
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    meta = arrays["meta"].copy()
    meta[6] = _PLAN_VERSION + 1
    arrays["meta"] = meta
    np.savez(path.replace(".npz", ""), **arrays)
    fresh = PlanCache(tile_size=8, cache_dir=str(tmp_path))
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        _, status = fresh.plan(g)
    assert status == "built" and fresh.stats["evicted_stale"] == 1


def test_disk_cache_stores_packed_tiles_packed(tmp_path):
    """The 8× plan-cache byte reduction is real on disk: the bitpack entry's
    tiles array persists as uint32 words."""
    g = erdos_renyi(256, avg_deg=6.0, seed=15)
    cache = PlanCache(tile_size=32, cache_dir=str(tmp_path))
    p_int8, _ = cache.plan(g, storage="int8")
    p_pack, _ = cache.plan(g, storage="bitpack")
    assert p_int8.key != p_pack.key     # distinct cache entries
    with np.load(cache._path(p_pack.key)) as z:
        assert z["tiles"].dtype == np.uint32
        assert int(z["meta"][6]) == _PLAN_VERSION
        assert z["meta"].shape[0] == _META_LEN
        packed_nbytes = z["tiles"].nbytes
    with np.load(cache._path(p_int8.key)) as z:
        assert z["tiles"].dtype == np.int8
        int8_nbytes = z["tiles"].nbytes
    assert int8_nbytes == 8 * packed_nbytes
    # round-trip through the disk layer preserves the packed form
    fresh = PlanCache(tile_size=32, cache_dir=str(tmp_path))
    loaded, status = fresh.plan(g, storage="bitpack")
    assert status == "disk" and loaded.tiled.storage == "bitpack"
    np.testing.assert_array_equal(
        np.asarray(loaded.tiled.tiles), np.asarray(p_pack.tiled.tiles)
    )


# ---------------------------------------------------------------------------
# request-key invariance (the mechanism behind batched parity)
# ---------------------------------------------------------------------------


def test_graph_key_is_storage_and_tiling_invariant():
    from repro.serve_mis.batcher import request_key

    g = erdos_renyi(50, avg_deg=4.0, seed=16)
    cache = PlanCache(tile_size=8)
    a = cache.plan(g, storage="int8")[0]
    b = cache.plan(g, storage="bitpack")[0]
    c = cache.plan(g, tile_size=16, storage="int8")[0]
    assert a.graph_key == b.graph_key == c.graph_key
    base = jax.random.key(0)
    ka, kb = request_key(base, a), request_key(base, b)
    assert jnp.all(jax.random.key_data(ka) == jax.random.key_data(kb))


@pytest.mark.parametrize("skip_dma", [False, True])
def test_kernel_col_flags_skip_dma_compose_with_bitpack(skip_dma):
    """The empty-C tile skip (and its DMA-skip variant) must be exact on
    packed tiles too — the skipped-or-not transfer is just 8× smaller."""
    from repro.kernels import tc_spmv

    g = erdos_renyi(200, avg_deg=6.0, seed=18)
    a = build_block_tiles(g, tile_size=16)
    b = a.to_storage("bitpack")
    flags = (
        jax.random.uniform(jax.random.key(3), (a.n_block_cols,)) > 0.5
    ).astype(jnp.int32)
    rhs = jax.random.normal(jax.random.key(4), (a.n_padded, 2), jnp.float32)
    rhs = rhs * jnp.repeat(flags, a.tile_size)[:, None].astype(jnp.float32)
    out_a = tc_spmv(a, rhs, col_flags=flags, skip_dma=skip_dma)
    out_b = tc_spmv(b, rhs, col_flags=flags, skip_dma=skip_dma)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))


def test_oracle_accepts_raw_packed_arrays():
    """The raw-array seam (core.distributed's entry) is storage-polymorphic:
    packed uint32 tiles flow through tile_spmv unchanged."""
    g = erdos_renyi(100, avg_deg=5.0, seed=17)
    a = build_block_tiles(g, tile_size=16)
    b = a.to_storage("bitpack")
    rhs = jax.random.normal(jax.random.key(1), (a.n_padded, 4), jnp.float32)
    oa = tile_spmv(a.tiles, a.tile_rows, a.tile_cols, rhs, a.n_block_rows, 16)
    ob = tile_spmv(b.tiles, b.tile_rows, b.tile_cols, rhs, b.n_block_rows, 16)
    np.testing.assert_array_equal(np.asarray(oa), np.asarray(ob))
