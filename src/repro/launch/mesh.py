"""Production mesh definition (DESIGN.md §5).

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods × 256 = 512 chips as (pod=2, data=16, model=16) — the
'pod' axis composes with 'data' for batch sharding, so cross-pod traffic is
only the gradient all-reduce (and the MIS frontier gather).

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run pins the device count before first jax init).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / examples)."""
    return jax.make_mesh(shape, axes)
