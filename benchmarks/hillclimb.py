import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
"""§Perf hillclimbing harness: compile a cell VARIANT and print its roofline
terms next to the recorded baseline.

    PYTHONPATH=src python -m benchmarks.hillclimb qwen3_fused
    PYTHONPATH=src python -m benchmarks.hillclimb tcmis_g8 --tile 32 --lanes 8

Each experiment function builds a modified config/cell and reuses the
dry-run's three-pass methodology.  Results are appended (by hand) to
EXPERIMENTS.md §Perf with the hypothesis → before → after record.
"""
import argparse
import dataclasses
import json
import sys

import jax
import numpy as np


def _measure(cell, mesh_kind="single"):
    from repro.launch.dryrun import _affine, _compile_pass, _cost_record
    from repro.launch.mesh import make_production_mesh
    from benchmarks.roofline import HBM_BW, ICI_BW, PEAK_FLOPS

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = int(np.prod(list(mesh.shape.values())))
    with mesh:
        compiled, _, t_mem = _compile_pass(cell, mesh, "memory")
        ma = compiled.memory_analysis()
        mem_gib = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                   + ma.output_size_in_bytes - ma.alias_size_in_bytes) / 2**30
        if cell.extrapolate:
            ex = cell.extrapolate
            a, _, _ = _compile_pass(cell, mesh, "cost_a")
            ca = _cost_record(a)
            del a
            b, _, _ = _compile_pass(cell, mesh, "cost_b")
            cb = _cost_record(b)
            del b
            cost = _affine(ca, cb, ex["la"], ex["lb"], ex["lfull"])
        else:
            cost = _cost_record(compiled)
    coll = sum(cost["collectives"].values())
    terms = dict(
        compute_s=cost["flops"] / PEAK_FLOPS,
        memory_s=cost["bytes_accessed"] / HBM_BW,
        collective_s=coll / ICI_BW,
    )
    step = max(terms.values())
    mf = cell.model_flops / n_dev
    print(json.dumps(dict(
        mem_gib=round(mem_gib, 2),
        **{k: round(v, 4) for k, v in terms.items()},
        dominant=max(terms, key=terms.get),
        step_s=round(step, 4),
        mfu=round(mf / (PEAK_FLOPS * step), 5) if step else 0,
        useful=round(mf / cost["flops"], 4) if cost["flops"] else 0,
        collectives={k: round(v / 2**30, 3) for k, v in cost["collectives"].items()},
    ), indent=1))


# --------------------------------------------------------------------------
# experiments
# --------------------------------------------------------------------------

def qwen3_baseline():
    from repro.configs import REGISTRY

    _measure(REGISTRY["qwen3-0.6b"].cells["train_4k"])


def qwen3_fused():
    """H-C iter 1: fused QKV + fused gate/up projections."""
    import repro.configs.qwen3_0_6b as q3
    from repro.configs.common import _lm_train_cell

    cfg = dataclasses.replace(q3.CONFIG, fuse_qkv=True, fuse_gate=True)
    _measure(_lm_train_cell("qwen3-fused", cfg, "train_4k"))


def qwen3_noremat():
    """H-C iter 2: remat off (recompute flops −, activation memory +)."""
    import repro.configs.qwen3_0_6b as q3
    from repro.configs.common import _lm_train_cell

    cfg = dataclasses.replace(q3.CONFIG, remat=False, fuse_qkv=True, fuse_gate=True)
    _measure(_lm_train_cell("qwen3-noremat", cfg, "train_4k"))


def qwen3_chunks(attn_chunk=1024, loss_chunk=2048):
    """H-C iter 3: bigger flash/xent chunks (fewer intermediate writes)."""
    import repro.configs.qwen3_0_6b as q3
    from repro.configs.common import _lm_train_cell

    cfg = dataclasses.replace(
        q3.CONFIG, fuse_qkv=True, fuse_gate=True,
        attn_chunk=attn_chunk, loss_chunk=loss_chunk,
    )
    _measure(_lm_train_cell("qwen3-chunks", cfg, "train_4k"))


def tcmis_g8(tile=None, lanes=None, bitpack=None):
    """H-A: tile size / lane width / frontier bit-packing on kron_g500."""
    import repro.configs.tcmis as tc

    if tile is not None:
        tc.choose_tile_size_orig = tc.choose_tile_size
        tc.choose_tile_size = lambda pid, n: tile
    if lanes is not None:
        tc.DRYRUN_LANES = lanes
    cell = tc._mis_cell("G8")
    if bitpack is not None:
        # rebuild the cell with bitpack toggled
        import repro.core.distributed as dist

        orig = dist.DistConfig
        _measure_cell = cell
    _measure(cell)


def deepseek_capacity(cf=1.0):
    """H-B iter: dispatch volume ∝ capacity factor."""
    import repro.configs.deepseek_v3_671b as ds
    from repro.configs.common import _lm_train_cell

    cfg = dataclasses.replace(
        ds.CONFIG, moe=dataclasses.replace(ds.CONFIG.moe, capacity_factor=cf)
    )
    _measure(_lm_train_cell("deepseek-cf", cfg, "train_4k"))


def deepseek_nomtp():
    """H-B iter: MTP head off (isolates its contribution)."""
    import repro.configs.deepseek_v3_671b as ds
    from repro.configs.common import _lm_train_cell

    cfg = dataclasses.replace(ds.CONFIG, mtp=False)
    _measure(_lm_train_cell("deepseek-nomtp", cfg, "train_4k"))


def qwen3_dots_remat():
    """H-C iter 4: selective remat — save matmul outputs only."""
    import repro.configs.qwen3_0_6b as q3
    from repro.configs.common import _lm_train_cell

    cfg = dataclasses.replace(
        q3.CONFIG, fuse_qkv=True, fuse_gate=True,
        attn_chunk=1024, loss_chunk=2048, remat_policy="dots",
    )
    _measure(_lm_train_cell("qwen3-dots", cfg, "train_4k"))


def tcmis_g3_rcm(rcm=True):
    """H-A iter 3: RCM-informed tiling on delaunay (G3)."""
    import repro.configs.tcmis as tc

    tc.RCM = bool(rcm)
    tc._occupancy_ratio.cache_clear()
    _measure(tc._mis_cell("G3"))


EXPERIMENTS = {
    "tcmis_g3_rcm": tcmis_g3_rcm,
    "qwen3_dots_remat": qwen3_dots_remat,
    "qwen3_baseline": qwen3_baseline,
    "qwen3_fused": qwen3_fused,
    "qwen3_noremat": qwen3_noremat,
    "qwen3_chunks": qwen3_chunks,
    "tcmis_g8": tcmis_g8,
    "deepseek_capacity": deepseek_capacity,
    "deepseek_nomtp": deepseek_nomtp,
}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("experiment", choices=list(EXPERIMENTS))
    p.add_argument("--tile", type=int, default=None)
    p.add_argument("--lanes", type=int, default=None)
    p.add_argument("--cf", type=float, default=None)
    args = p.parse_args()
    fn = EXPERIMENTS[args.experiment]
    kw = {}
    if args.experiment == "tcmis_g8":
        kw = dict(tile=args.tile, lanes=args.lanes)
    if args.experiment == "deepseek_capacity" and args.cf:
        kw = dict(cf=args.cf)
    if args.experiment == "tcmis_g3_rcm":
        kw = dict(rcm=(args.lanes != 0))  # --lanes 0 => no rcm
    print(f"# experiment: {args.experiment} {kw}")
    fn(**kw)


if __name__ == "__main__":
    main()
