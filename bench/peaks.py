"""Published peak rates of one chip, keyed by ``jax.Device.device_kind``.

The benchmark keeps its own table so that no change to the program can move
the denominators of its utilization metrics.  A device kind that is not here
is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 393 TOP/s in
# int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of ``device_kind``; raises ``KeyError`` for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
