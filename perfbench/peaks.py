"""The benchmark's own table of per-chip peaks, keyed by what the device
reports as ``device_kind``.

The program has a table too (``raytpu/core/chip_specs.py``); this copy is
the yardstick's, so a later PR that edits the program cannot move a
utilization. A kind that is not here is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source='Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s '
               "bf16, 16 GB HBM2e at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of the chip that reports ``device_kind``; ``KeyError``
    with the known kinds for any other."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"perfbench has no published peaks for device_kind "
            f"{device_kind!r}; known: {sorted(PEAKS)}") from None
