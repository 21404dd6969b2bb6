"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A device kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"add it to peaks.py with its source") from None


def roofline_seconds(flops: float, byts: float, peak: Dict) -> float:
    """The least time the chip needs: the larger of the compute and the
    memory bound."""
    return max(flops / peak["bf16_flops"], byts / peak["hbm_bytes_per_s"])
