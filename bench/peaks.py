"""Published peaks of each accelerator the benchmark may run on.

Keyed by `jax.Device.device_kind`. A device that is not listed is an
error: a roofline or utilization against a guessed peak means nothing.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM at 819 GB/s.
    "TPU v5 lite": {
        "bf16": 197e12,
        "int8": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}

SOURCE = 'Google Cloud documentation, "TPU v5e" (per-chip peaks)'


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table of `device_kind`; KeyError names the known kinds."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
