"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Roofline terms divide by these.  A device that is not in the table is an
error, never a default: a roofline against another chip's peaks is a wrong
number that looks right.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class Peaks:
    flops: float          # dense bf16 FLOP/s per chip
    hbm_bw: float         # HBM bytes/s per chip
    ici_bw: float         # inter-chip bytes/s per link
    source: str


#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s, 1,600 Gbit/s of inter-chip interconnect over 4 links.
TPU_V5E = "TPU v5 lite"

PEAKS: Dict[str, Peaks] = {
    TPU_V5E: Peaks(flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8 / 4,
                   source="Google Cloud documentation, 'TPU v5e'"),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for a device the
    table does not know."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
