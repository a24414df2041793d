"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

Copied from ``src/repro/launch/roofline.py`` (``PEAKS``) so that no later
change to the program moves the yardstick. Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s, 1,600 Gbit/s inter-chip interconnect.

A device that is not in the table is an error, not a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float      # bf16 FLOP/s per chip
    hbm_bw: float     # HBM bytes/s per chip
    ici_bw: float     # inter-chip interconnect bytes/s per chip
    source: str


PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8,
                         source="Google Cloud documentation, TPU v5e"),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; have {sorted(PEAKS)}") from None
