"""Roofline-term extraction and the analytic cost model (§Roofline, §16).

Three terms, all in seconds, from the PER-DEVICE compiled module (XLA SPMD
cost_analysis / memory_analysis report per-device numbers, verified
empirically in tests/test_roofline.py):

    compute    = HLO_FLOPs              / peak_FLOP/s          (197 TF bf16)
    memory     = HLO_bytes_accessed     / HBM_bw               (819 GB/s)
    collective = Σ collective op bytes  / ICI link bw          (50 GB/s)

collective bytes are parsed from the compiled HLO text: the output payload of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (async -start forms counted once, -done skipped).

Hardware model: `DEVICE_PEAKS`, one entry per chip keyed by
`jax.Device.device_kind`, each with its published source.  A TPU kind that
is not in the table is an error (`device_peaks`), never a silent default.
The module constants `PEAK_FLOPS` / `HBM_BW` / `ICI_BW` are the TPU v5e
entry — the chip the dry-run cost models and the analytic hybrid threshold
are sized for.

The same hardware constants drive the hybrid tile-routing threshold
(DESIGN.md §16): a dense tile pays a fixed cost regardless of occupancy,
a segment-path edge pays a per-nnz cost, and the break-even nnz between
the two is ``hybrid_density_threshold``, which the planner reads at plan
time.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

import jax


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one device kind."""
    flops_bf16: float    # FLOP/s, bf16 matrix unit
    hbm_bw: float        # HBM bytes/s
    ici_bw: float        # chip-to-chip bytes/s per link
    source: str


_V5E_SOURCE = (
    "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
    "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI over 4 links"
)

# keyed by `jax.Device.device_kind` as JAX reports it
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        flops_bf16=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8 / 4,
        source=_V5E_SOURCE,
    ),
}

V5E = DEVICE_PEAKS["TPU v5 lite"]
PEAK_FLOPS = V5E.flops_bf16     # bf16 per chip
HBM_BW = V5E.hbm_bw             # bytes/s per chip
ICI_BW = V5E.ici_bw             # bytes/s per link


def peaks_for_kind(device_kind: str) -> DevicePeaks:
    """Peaks of one chip by `device_kind`; an unlisted kind is an error."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(DEVICE_PEAKS)}"
        ) from None


def device_peaks(device=None) -> Optional[DevicePeaks]:
    """Peaks of `device` (default: the first JAX device).  None off TPU —
    a CPU run has no roofline; an unlisted TPU kind raises."""
    d = jax.devices()[0] if device is None else device
    if d.platform != "tpu":
        return None
    return peaks_for_kind(d.device_kind)


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?P<out>\([^)]*\)|[a-z0-9]+\[[^\]]*\][^\s]*)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<start>-start)?\("
)
_SHAPE_RE = re.compile(r"(?P<dt>[a-z0-9]+)\[(?P<dims>[\d,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt = m.group("dt")
        if dt not in _DTYPE_BYTES:
            continue
        dims = m.group("dims")
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-op-type output bytes of every collective in a compiled HLO."""
    out: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        op = m.group("op")
        out[op] = out.get(op, 0) + _shape_bytes(m.group("out"))
    return out


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    flops: float
    bytes_accessed: float
    collective_bytes: int
    collectives: Dict[str, int]
    model_flops: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time = max of the three overlappable terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flop_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPS per device — remat/padding/waste detector."""
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def mfu(self) -> float:
        """Model FLOPs / (chips × peak × step_time) — the roofline score."""
        t = self.step_time_s
        return self.model_flops / (PEAK_FLOPS * t) if t > 0 else 0.0

    def as_dict(self) -> dict:
        return dict(
            compute_s=self.compute_s,
            memory_s=self.memory_s,
            collective_s=self.collective_s,
            dominant=self.dominant,
            flops=self.flops,
            bytes_accessed=self.bytes_accessed,
            collective_bytes=self.collective_bytes,
            collectives=self.collectives,
            model_flops=self.model_flops,
            useful_flop_fraction=self.useful_flop_fraction,
            step_time_s=self.step_time_s,
            mfu=self.mfu,
        )


def roofline_from_compiled(
    compiled, n_devices: int, model_flops_global: float
) -> RooflineTerms:
    """Derive the three terms from a compiled (SPMD, per-device) module."""
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    colls = parse_collective_bytes(compiled.as_text())
    coll_bytes = sum(colls.values())
    return RooflineTerms(
        compute_s=flops / PEAK_FLOPS,
        memory_s=byts / HBM_BW,
        collective_s=coll_bytes / ICI_BW,
        flops=flops,
        bytes_accessed=byts,
        collective_bytes=coll_bytes,
        collectives=colls,
        model_flops=model_flops_global / max(n_devices, 1),
    )


# --------------------------------------------------------------------------
# hybrid tile-routing cost model (DESIGN.md §16)
# --------------------------------------------------------------------------

# Bytes one segment-path nnz moves through HBM: two int32 coordinates plus a
# gathered operand word and its scattered contribution.
_SPARSE_BYTES_PER_EDGE = 16


def dense_tile_cost_s(
    tile_size: int, storage: str = "int8", lanes: int = 8,
    peaks: DevicePeaks = V5E,
) -> float:
    """Roofline cost of pushing ONE tile through the dense path, any occupancy.

    A dense T×T tile costs the same whether it holds 1 nnz or T² — that
    fixed cost is what the sparse tail wastes.  Compute term: the phase-②
    SpMV MACs over ``lanes`` rhs columns.  Memory term: the tile payload
    (storage-dependent — bitpack is 8× smaller) plus the rhs slab and the
    tile's share of the output.
    """
    if tile_size <= 0:
        raise ValueError(f"tile_size must be positive, got {tile_size}")
    t = int(tile_size)
    flops = 2.0 * t * t * lanes
    if storage == "bitpack":
        payload = t * max(t // 32, 1) * 4
    else:
        payload = t * t
    rhs_bytes = t * lanes * 4
    out_bytes = t * lanes * 4
    compute_s = flops / peaks.flops_bf16
    memory_s = (payload + rhs_bytes + out_bytes) / peaks.hbm_bw
    return max(compute_s, memory_s)


def sparse_edge_cost_s(peaks: DevicePeaks = V5E) -> float:
    """Roofline cost of ONE nnz on the COO/segment path (pure gather/scatter)."""
    return _SPARSE_BYTES_PER_EDGE / peaks.hbm_bw


def predicted_round_cost_s(
    dense_tiles: float,
    sparse_edges: float = 0.0,
    *,
    tile_size: int,
    storage: str = "int8",
    lanes: int = 8,
    peaks: DevicePeaks = V5E,
) -> float:
    """Model-predicted cost of ONE solver round (seconds).

    The same two primitives the hybrid router prices with, summed over a
    round's actual dispatch mix: ``dense_tiles`` tiles through the dense
    path (telemetry COL_TILES_DENSE, skip-gating already subtracted) plus
    ``sparse_edges`` half-edges through the COO/segment tail.  Fractional
    tile counts are fine — callers pass per-round means.
    """
    dense = max(float(dense_tiles), 0.0)
    edges = max(float(sparse_edges), 0.0)
    return (dense * dense_tile_cost_s(tile_size, storage, lanes, peaks)
            + edges * sparse_edge_cost_s(peaks))


def round_cost_attribution(
    *,
    dense_tiles: float,
    sparse_edges: float,
    tile_size: int,
    storage: str,
    measured_s: float,
    lanes: int = 8,
    peaks: DevicePeaks = V5E,
) -> Dict[str, float]:
    """Predicted-vs-measured per-round cost: the model-error gauge.

    Following HC-SpMM's practice of continuously scoring its hybrid-core
    cost model, this closes the loop on the router's pricing: `error_pct`
    = (measured − predicted) / predicted × 100.  The signal is the trend,
    not the absolute: a drifting error under churn means the dispatch mix
    no longer matches what the plan priced.  `peaks` is the measuring
    chip's entry (`device_peaks`); the Solver records the gauge only on a
    chip that has one.
    """
    predicted = predicted_round_cost_s(
        dense_tiles, sparse_edges,
        tile_size=tile_size, storage=storage, lanes=lanes, peaks=peaks,
    )
    measured = max(float(measured_s), 0.0)
    error_pct = (
        (measured - predicted) / predicted * 100.0 if predicted > 0 else 0.0
    )
    return dict(
        predicted_us=round(predicted * 1e6, 3),
        measured_us=round(measured * 1e6, 3),
        error_pct=round(error_pct, 1),
    )


def hybrid_density_threshold(
    tile_size: int, storage: str = "int8", lanes: int = 8
) -> int:
    """Break-even nnz per tile between the dense and segment paths.

    A tile with fewer nnz than this is cheaper as scattered edges than as a
    dense MMA; at or above it the dense path wins.  Clamped to [1, T²] so
    degenerate hardware constants can never route everything one way.
    Representative values: T=64/int8 ≈ 512 nnz (12.5% density),
    T=128/int8 ≈ 1536, T=128/bitpack ≈ 640.
    """
    dense = dense_tile_cost_s(tile_size, storage, lanes)
    edge = sparse_edge_cost_s()
    thr = int(dense / edge)
    return max(1, min(thr, int(tile_size) * int(tile_size)))
