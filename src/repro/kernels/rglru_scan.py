"""RG-LRU linear-recurrence Pallas kernel: h_t = a_t * h_{t-1} + b_t.

Grid (B, nR, nT): feature blocks are independent lanes (8x128-aligned); the
time axis is last (sequential) so the (block_r,) carry persists in VMEM
scratch across time chunks.  Inside a chunk the recurrence is a fori_loop of
fused multiply-adds over rows — VPU work, no MXU — which is the right shape
for TPU: the recurrence is memory-bound, so the win is keeping the carry and
the (chunk, block_r) tile resident in VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rglru_scan_pallas", "DEFAULT_CHUNK_T", "DEFAULT_BLOCK_R"]

DEFAULT_CHUNK_T = 128
DEFAULT_BLOCK_R = 512


def _kernel(a_ref, b_ref, h0_ref, h_ref, carry_scr, *, chunk_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        carry_scr[...] = h0_ref[0].astype(jnp.float32)

    def row(t, carry):                 # carry (1, block_r)
        # Rows are read and written through the refs: Mosaic lowers a
        # dynamic row slice of a ref, not of a loaded value.
        a = a_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        b = b_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        h = a * carry + b
        h_ref[0, pl.ds(t, 1), :] = h.astype(h_ref.dtype)
        return h

    carry_scr[...] = jax.lax.fori_loop(0, chunk_t, row, carry_scr[...])


def rglru_scan_pallas(
    a: jax.Array, b: jax.Array, h0: Optional[jax.Array] = None,
    chunk_t: int = DEFAULT_CHUNK_T, block_r: int = DEFAULT_BLOCK_R,
    interpret: bool = False,
) -> jax.Array:
    """a/b (B,S,R); h0 (B,R) or None -> h (B,S,R).  S % chunk_t == 0 and
    R % block_r == 0 (ops.py pads: a=1,b=0 rows are identity steps)."""
    B, S, R = a.shape
    chunk_t = min(chunk_t, S)
    block_r = min(block_r, R)
    if S % chunk_t or R % block_r:
        raise ValueError(f"S={S},R={R} must divide blocks ({chunk_t},{block_r})")
    if h0 is None:
        h0 = jnp.zeros((B, R), jnp.float32)

    kernel = functools.partial(_kernel, chunk_t=chunk_t)
    return pl.pallas_call(
        kernel,
        grid=(B, R // block_r, S // chunk_t),
        in_specs=[
            pl.BlockSpec((1, chunk_t, block_r), lambda bi, ri, ti: (bi, ti, ri)),
            pl.BlockSpec((1, chunk_t, block_r), lambda bi, ri, ti: (bi, ti, ri)),
            pl.BlockSpec((1, 1, block_r), lambda bi, ri, ti: (bi, 0, ri)),
        ],
        out_specs=pl.BlockSpec((1, chunk_t, block_r),
                               lambda bi, ri, ti: (bi, ti, ri)),
        out_shape=jax.ShapeDtypeStruct((B, S, R), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_r), jnp.float32)],
        interpret=interpret,
    )(a, b, h0[:, None, :])  # (B, 1, R): see the h0 block above
