"""Published per-chip peaks, keyed by `jax.Device.device_kind`.

A copy of the program's `perf.roofline.DEVICE_PEAKS`, kept here so that no
change to the program can move the yardstick.  A device kind that is not in
the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float    # FLOP/s of the matrix unit in bf16
    ops_int8: float      # OP/s of the matrix unit in int8
    hbm_bw: float        # HBM bytes/s
    source: str


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, ops_int8=393e12, hbm_bw=819e9,
        source=("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                "393 TOP/s int8, 16 GB HBM at 819 GB/s"),
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
