"""Pallas TPU kernels for the model zoo's compute hot spots.

Each kernel ships three pieces: ``<name>.py`` (pl.pallas_call + BlockSpec
VMEM tiling), a wrapper in ``ops.py`` (jit-friendly padding; interpret mode
on the CPU only), and an oracle in ``ref.py`` (pure-jnp ground truth used by the
allclose sweeps in tests/test_kernels.py).
"""
from . import ops, ref
from .ops import flash_attention, moe_router, rglru_scan, rwkv6_scan
