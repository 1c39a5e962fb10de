"""Drift observability for long-horizon mutation streams (DESIGN.md §17).

The RCM permutation is frozen at epoch 0, so tile locality decays under
sustained churn, and repair can lose to a cold solve at 1-5% deltas.  The
ROADMAP's re-anchoring item needs a *signal* before a
policy can exist; this module is that signal.  Three gauges, all recorded
at the eager patch seam (`api.plan.patch_plan` — the one funnel every
actual patch event passes through, cached hits excluded so an epoch is
counted exactly once):

* ``dyngraph.touched_tiles`` (histogram) + ``dyngraph.touched_frac`` —
  distinct tiles a delta's half-edges land in: the touched-tiles-per-delta
  trend.  Rising trend at fixed delta size = edges spreading across the
  stale tiling.
* ``dyngraph.locality_decay`` — 1 − occupancy/occupancy₀, where occupancy
  is stored-tile density ``2·E / (n_tiles · T²)`` and occupancy₀ the same
  at the epoch-0 build.  0 at epoch 0; grows toward 1 as the same edges
  smear over ever more tiles (each tile ever emptier); negative when
  mutation *densifies* the tiling (also informative).
* ``dyngraph.dirty_frac`` — fraction of vertices a delta dirties (the
  drift twin of ``repair.dirty_frac``, which records what the *repair*
  decision saw; this one is recorded whether or not a repair follows).

Import-light by design (numpy + the metrics registry): `api.plan` calls
in here, so any jax / core import would re-create the layering cycle the
lazy dyngraph imports in `patch_plan` exist to avoid.  Never on the jitted
hot path — the eager-only metrics contract (DESIGN.md §14) holds.
"""
from __future__ import annotations

import numpy as np

from repro.obs import metrics as obs_metrics


def tile_occupancy(n_edges: int, n_tiles: int, tile_size: int) -> float:
    """Mean stored-tile density: half-edge cells over stored cell capacity.

    Each undirected edge occupies two cells ((u,v) and (v,u)), hence the
    2·E numerator.  Real tiles only — padding tiles are capacity the
    engine skips, not capacity the graph wastes.
    """
    cap = max(int(n_tiles), 1) * int(tile_size) * int(tile_size)
    return 2.0 * max(int(n_edges), 0) / cap


def touched_tile_count(delta, tile_size: int, n_block_cols: int) -> int:
    """Distinct tiles the delta's half-edges land in (add and remove both
    count — a remove dirties its tile's words exactly like an add)."""
    T = int(tile_size)
    nbc = np.int64(max(int(n_block_cols), 1))
    keys = []
    for pairs in (delta.add, delta.remove):
        p = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
        if not p.shape[0]:
            continue
        u = np.concatenate([p[:, 0], p[:, 1]])
        v = np.concatenate([p[:, 1], p[:, 0]])
        keys.append((u // T) * nbc + (v // T))
    if not keys:
        return 0
    return int(np.unique(np.concatenate(keys)).shape[0])


def dirty_vertex_frac(delta, n_nodes: int) -> float:
    """Fraction of vertices that are an endpoint of some delta edge."""
    both = np.concatenate([
        np.asarray(delta.add, dtype=np.int64).reshape(-1),
        np.asarray(delta.remove, dtype=np.int64).reshape(-1),
    ])
    if not both.shape[0]:
        return 0.0
    return float(np.unique(both).shape[0]) / max(int(n_nodes), 1)


def note_drift(
    *,
    epoch: int,
    touched_tiles: int,
    n_tiles: int,
    dirty_frac: float,
    occupancy: float,
    occupancy0: float,
) -> None:
    """Record one patch event's drift metrics into the process registry.

    Eager-only (never under a jit trace); called once per *applied* delta
    by `api.plan.patch_plan` — plan-cache mem/disk hits replay a patch
    that already happened and must NOT re-record.
    """
    reg = obs_metrics.REGISTRY
    reg.counter("dyngraph.epochs").inc()
    reg.gauge("dyngraph.epoch").set(epoch)
    reg.histogram("dyngraph.touched_tiles").observe(touched_tiles)
    reg.gauge("dyngraph.touched_frac").set(
        touched_tiles / max(int(n_tiles), 1)
    )
    reg.gauge("dyngraph.dirty_frac").set(dirty_frac)
    reg.gauge("dyngraph.occupancy").set(occupancy)
    decay = 1.0 - occupancy / occupancy0 if occupancy0 > 0 else 0.0
    reg.gauge("dyngraph.locality_decay").set(decay)
