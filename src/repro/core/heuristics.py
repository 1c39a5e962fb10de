"""Priority-assignment heuristics H1/H2/H3 (paper §3.3) + ECL's Eq. (1).

All heuristics produce **int32 total orders**: the high bits carry the
structural bias (quantised Eq. 1), the low 23 bits carry a random permutation
of vertex ids so priorities are *globally distinct*.  Distinctness is what
makes phase ③ lock-free-by-construction exact: two adjacent vertices can never
both satisfy ``P(v) > Max_Np(v)``, so candidates are guaranteed independent
and the algorithm is deterministic given the key.

Execution-semantics modelling (see DESIGN.md §4): the paper's H2-vs-H3 quality
gap arises from priority inversions during warp-asynchronous tile execution.
A JAX array program is synchronous, so we model the same effect where it
actually lives — in the *resolution order of ties of the quantised priority*:

  H1  pure random permutation                       (paper: hash(v))
  H2  coarse 4-bit Eq. 1 ‖ random tie resolution    (ties resolved by chance,
      mirroring the paper's unordered premature eliminations)
  H3  8-bit Eq. 1 ‖ *ordered* resolution on the pending set: remaining ties
      resolve deterministically by (lower degree, then id) before C is
      finalised — the paper's "Alive → conflict resolution → candidate
      finalisation → state update" pipeline.

ECL-MIS itself uses Eq. 1 at its native ~8-bit discretisation with hashed tie
break, which is what `ecl_priorities` provides.

Packed batches (serve_mis.batcher) give every member the priorities of its
solo solve.  H3's are a per-vertex function of the member's key, size and
degrees — its random draw is prefix-consistent, element i the same whatever
the length drawn — so `h3_priorities_packed` builds every member's at once
inside the packed program (`PACKED`).  H1, H2 and ECL draw a permutation,
whose prefix is no smaller permutation; their members' priorities are
built one by one on the host (`make_priorities`).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

from repro.core.spmv import _NEG

# low-bit budget for the distinctness permutation: supports |V| < 2^23 ≈ 8.4M,
# which covers the paper's whole suite (max 4.85M vertices).
_LOW_BITS = 23
_LOW_MASK = (1 << _LOW_BITS) - 1


class Priorities(NamedTuple):
    """Total-order priorities plus (for H3) the two-pass resolution key.

    select:  (n,) int32 — used for the phase-① candidate test.
    resolve: optional (n,) int32 — when set, candidate generation runs the
             H3 two-pass: pending by quantised `select`, finalise by strict
             `resolve` order among pending vertices.
    """
    select: jnp.ndarray
    resolve: Optional[jnp.ndarray] = None


def _perm(key: jax.Array, n: int) -> jnp.ndarray:
    return jax.random.permutation(key, jnp.arange(n, dtype=jnp.int32))


def _eq1_levels(deg_f, dbar, eps, bits: int) -> jnp.ndarray:
    p = dbar / (dbar + deg_f - eps)
    levels = (1 << bits) - 1
    return jnp.clip((p * levels).astype(jnp.int32), 0, levels)


def eq1_quantized(
    deg: jnp.ndarray, key: jax.Array, bits: int
) -> jnp.ndarray:
    """Paper Eq. (1): P(v) = d̄ / (d̄ + deg(v) − ε(v)), discretised to ``bits``."""
    deg_f = deg.astype(jnp.float32)
    dbar = jnp.mean(deg_f)
    eps = jax.random.uniform(key, deg.shape, minval=0.0, maxval=1.0)
    return _eq1_levels(deg_f, dbar, eps, bits)


def h1_priorities(key: jax.Array, n: int, deg: jnp.ndarray) -> Priorities:
    """H1: random priority — maximal parallelism, no structural bias."""
    del deg
    return Priorities(select=_perm(key, n))


def h2_priorities(key: jax.Array, n: int, deg: jnp.ndarray) -> Priorities:
    """H2: coarse degree-aware priority, ties broken by chance."""
    kq, kp = jax.random.split(key)
    q = eq1_quantized(deg, kq, bits=4)
    return Priorities(select=(q << _LOW_BITS) | _perm(kp, n))


def h3_priorities(key: jax.Array, n: int, deg: jnp.ndarray) -> Priorities:
    """H3: fine degree-aware priority + ordered conflict resolution.

    ``select`` keeps only the quantised structural priority (ties allowed —
    tied vertices enter the *pending* set); ``resolve`` is the deterministic
    ordered key (degree-major, id-minor) that finalises C conflict-free.
    """
    kq, _ = jax.random.split(key)
    q = eq1_quantized(deg, kq, bits=8)
    # ordered resolution: lower degree wins, then lower id — encode as a
    # strictly decreasing function so "larger key wins" stays the convention.
    rank = _h3_rank(deg, jnp.int32(n), jnp.arange(n, dtype=jnp.int32))
    return Priorities(select=(q << _LOW_BITS), resolve=rank)


def _h3_rank(deg, n, ids) -> jnp.ndarray:
    return (-deg.astype(jnp.int32)) * n - ids


class MemberSlots(NamedTuple):
    """Where each slot of a packed vertex vector sits in its member graph:
    the key-independent inputs of `h3_priorities_packed`.

    member: (n,) int32 — the slot's member index, -1 in padding slots
    local:  (n,) int32 — the slot's vertex id within its member
    deg:    (n,) int32 — the slot's degree in its member graph
    sizes:  (M,) int32 — each member's vertex count (0 past the last)
    """
    member: jnp.ndarray
    local: jnp.ndarray
    deg: jnp.ndarray
    sizes: jnp.ndarray


def _uniform_at(key_data: jnp.ndarray, index: jnp.ndarray) -> jnp.ndarray:
    """`jax.random.uniform(key, (n,))[index]` for each row of `key_data`
    (threefry key words) and its `index`, for any n > index.  Under the
    partitionable threefry (jax's default) element i of a draw hashes the
    counter (0, i) alone, whatever n; its float is the one `uniform` makes
    of the 32 bits, in [0, 1)."""
    if not jax.config.jax_threefry_partitionable:
        raise ValueError("packed H3 priorities need jax_threefry_partitionable, "
                         "under which a draw's prefix does not depend on its length")
    b1, b2 = threefry2x32_p.bind(
        key_data[:, 0], key_data[:, 1],
        jnp.zeros(index.shape, jnp.uint32), index.astype(jnp.uint32),
    )
    bits = (b1 ^ b2) >> np.uint32(32 - 23) | np.uint32(0x3F800000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32) - jnp.float32(1.0)


def h3_priorities_packed(keys: jax.Array, slots: MemberSlots) -> Priorities:
    """H3 priorities of every member of a packed batch, member m under
    `keys[m]`: each slot holds exactly what `h3_priorities(keys[m], n_m,
    deg_m)` gives its vertex, and padding slots hold `_NEG`.

    Bit-identical to the solo construction where a member's degree sum
    (its half-edge count) is below 2^24: d̄ is then an exact float32 sum
    in either order of summation."""
    real = slots.member >= 0
    m = jnp.maximum(slots.member, 0)
    kq = jax.vmap(lambda k: jax.random.split(k)[0])(keys)
    if "threefry" not in str(jax.random.key_impl(kq)):
        raise ValueError("packed H3 priorities need threefry keys")
    eps = _uniform_at(jax.random.key_data(kq)[m], slots.local)
    total = jax.ops.segment_sum(jnp.where(real, slots.deg, 0), m,
                                num_segments=slots.sizes.shape[0])
    dbar = total.astype(jnp.float32) / slots.sizes.astype(jnp.float32)
    q = _eq1_levels(slots.deg.astype(jnp.float32), dbar[m], eps, bits=8)
    rank = _h3_rank(slots.deg, slots.sizes[m], slots.local)
    neg = jnp.int32(_NEG)
    return Priorities(
        select=jnp.where(real, q << _LOW_BITS, neg),
        resolve=jnp.where(real, rank, neg),
    )


def ecl_priorities(key: jax.Array, n: int, deg: jnp.ndarray) -> Priorities:
    """ECL-MIS native priority: 8-bit Eq. (1) with hashed low bits."""
    kq, kp = jax.random.split(key)
    q = eq1_quantized(deg, kq, bits=8)
    return Priorities(select=(q << _LOW_BITS) | _perm(kp, n))


HEURISTICS = {
    "h1": h1_priorities,
    "h2": h2_priorities,
    "h3": h3_priorities,
    "ecl": ecl_priorities,
}


# heuristics whose packed-batch priorities the packed program builds itself
PACKED = {"h3": h3_priorities_packed}


def make_priorities(
    heuristic: str, key: jax.Array, n: int, deg: jnp.ndarray
) -> Priorities:
    try:
        fn = HEURISTICS[heuristic]
    except KeyError:
        raise ValueError(f"unknown heuristic {heuristic!r}; options {list(HEURISTICS)}")
    return fn(key, n, deg)
