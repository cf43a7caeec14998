"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark's own copy of the table: roofline and MFU shares divide by
these numbers, and a device that is not in the table is an error, never a
default (a share against another chip's peaks is a wrong number that looks
right).
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    flops: float          # dense bf16 FLOP/s per chip
    hbm_bw: float         # HBM bytes/s per chip
    source: str


#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9,
                         source="Google Cloud documentation, 'TPU v5e'"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
