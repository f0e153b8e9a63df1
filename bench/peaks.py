"""Published per-chip peaks, keyed by ``device.device_kind``.  A kind
that is not listed is an error, never a default."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float  # dense bf16 FLOP/s per chip
    hbm_bytes_s: float  # HBM bytes/s per chip
    hbm_bytes: float  # HBM capacity per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12,
        hbm_bytes_s=819e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "393 TOP/s int8, 16 GB HBM at 819 GB/s per chip",
    ),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(f"no published peaks for device kind {device_kind!r}; add them to bench/peaks.py with their source") from None
