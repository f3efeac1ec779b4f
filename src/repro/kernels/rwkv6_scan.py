"""RWKV-6 WKV chunked-scan Pallas kernel.

Grid (B*H, n_chunks): the chunk axis is sequential on TPU, so the (N, N) fp32
recurrent state lives in VMEM scratch across chunks (loaded from the initial
state at chunk 0, flushed to the output at the last chunk).  Within a chunk,
decay-ratio weights are computed in log space (ratios <= 1, no overflow) and
the heavy lifting — intra-chunk A @ V, inter-chunk (r*decay) @ S, and the
state update K^T @ V — are MXU matmuls.  The (L, L, N) ratio tensor is the
VPU-side cost; L (chunk) is kept small (32-64) so it fits VMEM comfortably:
VMEM ~= L*N*4 inputs * 4 + L*L*N*4 ratio ~= 0.6 MiB at L=32, N=64.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rwkv6_scan_pallas", "DEFAULT_CHUNK"]

DEFAULT_CHUNK = 32


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,   # inputs
            y_ref, sout_ref,                              # outputs
            s_scr,                                        # scratch (N,N) f32
            *, n_chunks: int, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)   # (L, N)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    logw = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)   # (1, N)

    L, N = r.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # Inclusive prefix sum over time as a lower-triangular matmul: Mosaic
    # has no cumsum.
    cum = jax.lax.dot_general(
        (row >= col).astype(jnp.float32), logw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                   # (L, N)
    cum_excl = cum - logw

    # intra-chunk: A[t,s] = sum_n r[t,n] k[s,n] exp(cum_excl[t,n] - cum[s,n]), s<t
    ratio = cum_excl[:, None, :] - cum[None, :, :]          # (L, L, N)
    past = (jax.lax.broadcasted_iota(jnp.int32, (L, L, N), 0)
            > jax.lax.broadcasted_iota(jnp.int32, (L, L, N), 1))
    ratio = jnp.where(past, ratio, -jnp.inf)
    A = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(ratio), axis=-1)  # (L, L)
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)        # (L, 1)
    A = A + jnp.where(row == col, diag, 0.0)

    y_intra = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    r_dec = r * jnp.exp(cum_excl)
    y_inter = jax.lax.dot_general(r_dec, s_scr[...], (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    last = cum[L - 1:L]                                       # (1, N)
    k_scaled = k * jnp.exp(last - cum)                        # (L, N)
    # diag(exp(last)) @ S scales the state's rows; as a matmul it needs no
    # (1, N) -> (N, 1) relayout.
    n_row = jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
    n_col = jax.lax.broadcasted_iota(jnp.int32, (N, N), 1)
    decay = jnp.where(n_row == n_col, jnp.exp(last), 0.0)     # (N, N)
    s_scr[...] = jax.lax.dot_general(
        decay, s_scr[...], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32) + jax.lax.dot_general(
        k_scaled, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ci == n_chunks - 1)
    def _finish():
        sout_ref[0] = s_scr[...]


def rwkv6_scan_pallas(
    r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
    u: jax.Array, state: jax.Array,
    chunk: int = DEFAULT_CHUNK, interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """r/k/v (B,S,H,N); logw (B,S,H,N) fp32; u (H,N); state (B,H,N,N) fp32.

    Returns (y (B,S,H,N), final state).  S must divide ``chunk`` (ops.py pads
    with logw=0, k=0 which leaves y/state unchanged)."""
    B, S, H, N = r.shape
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"S={S} must divide chunk={chunk}")
    n_chunks = S // chunk

    def to_bh(a):
        return a.transpose(0, 2, 1, 3).reshape(B * H, S, N)

    rb, kb, vb = to_bh(r), to_bh(k), to_bh(v)
    wb = to_bh(logw.astype(jnp.float32))
    s0 = state.reshape(B * H, N, N).astype(jnp.float32)

    kernel = functools.partial(_kernel, n_chunks=n_chunks, chunk=chunk)
    y, s_out = pl.pallas_call(
        kernel,
        grid=(B * H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, N), lambda bh, ci: (bh % H, 0, 0)),
            pl.BlockSpec((1, N, N), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, N, N), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, N), r.dtype),
            jax.ShapeDtypeStruct((B * H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(rb, kb, vb, wb, u[:, None, :], s0)
    return (y.reshape(B, H, S, N).transpose(0, 2, 1, 3),
            s_out.reshape(B, H, N, N))
