"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" — per chip 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect.  A device kind missing from the table has no
peak: utilisation gauges stay unset for it rather than borrow another
chip's figure.
"""
from __future__ import annotations

from typing import Dict, Optional

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {                 # TPU v5e
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
    },
}


def chip_peak(device_kind: str, metric: str) -> Optional[float]:
    """``metric`` of one chip of ``device_kind``; None when the kind is
    not in :data:`PEAKS`."""
    return PEAKS.get(device_kind, {}).get(metric)
