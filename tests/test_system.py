"""End-to-end system behaviour: the paper's full pipeline on real (reduced)
graph instances, plus registry completeness."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED_ARCHS, REGISTRY


def test_all_assigned_archs_registered():
    for arch in ASSIGNED_ARCHS:
        assert arch in REGISTRY, f"missing assigned arch {arch}"
    assert "tcmis" in REGISTRY  # the paper's own config
    # LM archs expose the 4 LM shapes, GNN archs the 4 GNN shapes, etc.
    for arch in ASSIGNED_ARCHS:
        assert len(REGISTRY[arch].cells) == 4, arch
    assert len(REGISTRY["tcmis"].cells) == 8  # G1..G8


def test_tcmis_smoke():
    REGISTRY["tcmis"].smoke()


@pytest.mark.parametrize("paper_id", ["G2", "G4"])
def test_paper_pipeline_on_suite_graph(paper_id):
    """Generate a Table-1 stand-in, tile it, run all three algorithms,
    validate, and check the paper's qualitative claims hold."""
    from repro.core import (
        TCMISConfig, build_block_tiles, cardinality, ecl_mis, is_valid_mis,
        luby_mis, tc_mis,
    )
    from repro.graphs.generators import GRAPH_SUITE

    spec = GRAPH_SUITE[paper_id]
    g = spec.make(4000, 0)
    tiled = build_block_tiles(g, tile_size=64)
    key = jax.random.key(0)

    r_luby = luby_mis(g, key)
    r_ecl = ecl_mis(g, key)
    r_tc = tc_mis(g, tiled, key, TCMISConfig(heuristic="h3"))
    for r in (r_luby, r_ecl, r_tc):
        assert bool(r.converged)
        assert is_valid_mis(g, r.in_mis)
    # degree-aware beats pure-random cardinality (paper Fig. 3 direction)
    assert cardinality(r_ecl.in_mis) >= cardinality(r_luby.in_mis)
    # rounds are logarithmic-ish, not linear
    assert int(r_tc.rounds) < 64


def test_train_loop_end_to_end_lm(tmp_path):
    """examples/train driver logic: tiny LM trains and loss decreases."""
    import numpy as np

    from repro.configs.qwen15_0_5b import SMOKE
    from repro.configs.common import make_lm_train_step
    from repro.data.pipeline import TokenStream
    from repro.models import transformer as tf
    from repro.train import LoopConfig, OptConfig, TrainLoop, adamw_init

    cfg = SMOKE
    params = tf.init_lm(jax.random.key(0), cfg)
    raw = jax.jit(make_lm_train_step(cfg, OptConfig(lr=3e-3, warmup_steps=5,
                                                    total_steps=100)))

    def step_fn(state, batch):
        params, opt = state
        tokens, targets = batch
        params, opt, loss, xent = raw(params, opt, jnp.asarray(tokens),
                                      jnp.asarray(targets))
        return (params, opt), {"loss": loss}

    loop = TrainLoop(
        step_fn=step_fn,
        init_state=(params, adamw_init(params)),
        stream=TokenStream(cfg.vocab, 8, 32, seed=3),
        cfg=LoopConfig(ckpt_dir=str(tmp_path), checkpoint_every=20),
    )
    first = []
    orig_step = loop.step_fn

    res = loop.run(60)
    assert np.isfinite(res["metrics"]["loss"])
    # copy-structure stream is learnable: loss must drop below uniform
    assert res["metrics"]["loss"] < float(np.log(cfg.vocab)) - 0.3


def test_generate_builds_vectorised_graphs_at_full_scale():
    """G2 and G3 build at their published |V|; the per-vertex Python
    generators refuse full scale instead of running for hours."""
    from repro.graphs.generators import GRAPH_SUITE, generate

    g2 = generate("G2", scale="full", seed=0)
    assert g2.n_nodes == 1044 * 1045 >= GRAPH_SUITE["G2"].n_full
    assert generate("G2").n_nodes == 200 * 200   # reduced scale unchanged
    for pid in ("G1", "G5", "G7"):
        with pytest.raises(ValueError, match="dry-run spec"):
            generate(pid, scale="full")
    with pytest.raises(ValueError, match="unknown scale"):
        generate("G2", scale="half")


def test_compile_cache_honours_env_else_fixed_checkout_path(monkeypatch):
    """The entry points' compile-cache helper: the env variable wins and
    nothing is set; otherwise the fixed `.jax_cache/` of the checkout.
    `jax.config.update` is captured, so the test turns no cache on."""
    from repro.launch import compile_cache as cc

    updates = []
    monkeypatch.setattr(cc.jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv(cc.CACHE_ENV, "/some/cache")
    assert cc.enable_compile_cache() == "/some/cache"
    assert updates == []
    monkeypatch.delenv(cc.CACHE_ENV)
    path = cc.enable_compile_cache()
    assert path == str(cc.DEFAULT_CACHE_DIR)
    assert path.endswith(".jax_cache")
    assert (cc.DEFAULT_CACHE_DIR.parent / "src" / "repro").is_dir()
    assert updates == [("jax_compilation_cache_dir", path)]
