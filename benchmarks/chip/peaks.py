"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` JAX reports.

Kept here, and not read from the program's cost model, so that a change
to the program cannot move the yardstick.  A device that is not in the
table is an error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    bf16_flops: float     # dense bf16 FLOP/s of one chip
    hbm_bytes_s: float    # HBM bandwidth of one chip, bytes/s
    hbm_bytes: float      # HBM capacity of one chip, bytes
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        bf16_flops=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM2 at 819 GB/s per chip"),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
