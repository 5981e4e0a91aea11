"""Published per-chip peaks, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a
share of a peak that nobody published is not a measurement.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": dict(
        flops_bf16=197e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s '
               'chip-to-chip interconnect'),
}


def peaks(device_kind: str) -> Dict:
    """The published peaks of ``device_kind``; raises for a device the
    table does not list."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (have {sorted(PEAKS)})")
    return PEAKS[device_kind]
