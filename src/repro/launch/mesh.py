"""Production mesh builders and per-chip peak rates.

Functions (not module constants) so importing never touches jax device state.
Target: TPU v5e, 256 chips/pod; single-pod (16, 16) = (data, model), multi-pod
(2, 16, 16) = (pod, data, model).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax

__all__ = ["make_production_mesh", "make_mesh", "HW", "V5E", "ChipPeaks",
           "peaks"]


@dataclass(frozen=True)
class ChipPeaks:
    """Published peak rates of one chip, the denominators of the roofline."""
    flops_bf16: float   # FLOP/s
    hbm_bw: float       # HBM bytes/s
    hbm_bytes: int      # HBM capacity
    ici_bw: float       # bytes/s per inter-chip link


V5E = "TPU v5 lite"  # jax.Device.device_kind of a TPU v5e chip

# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 16 GiB of HBM at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect per chip, i.e. 50 GB/s on each of its 4 links.
HW = {
    V5E: ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16 * 2**30,
                   ici_bw=50e9),
}


def peaks(device_kind: str) -> ChipPeaks:
    """Peak rates of ``device_kind``; a device not in ``HW`` is an error."""
    try:
        return HW[device_kind]
    except KeyError:
        raise ValueError(f"no peak rates for device kind {device_kind!r} "
                         f"(known: {sorted(HW)})") from None


def _make(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh with Auto axis types (e.g. trial sub-meshes)."""
    return _make(shape, axes)
