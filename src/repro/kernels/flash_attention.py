"""Flash attention Pallas kernel — blocked online softmax, TPU tiling.

Grid (B, H, nQ, nK); the last axis is sequential on TPU, so fp32 running
(max, sum, acc) live in VMEM scratch across the kv sweep and the output block
is written on the final kv step.  Block shapes are MXU-aligned (q-block x hd
and k-block x hd tiles, 128-multiples where shapes allow).  Supports causal /
sliding-window / bidirectional masks from explicit position vectors (ring
caches pass k_pos with -1 for unfilled slots), GQA head grouping and logit
soft-capping.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas", "DEFAULT_BLOCK_Q", "DEFAULT_BLOCK_K"]

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30


def _kernel(q_pos_ref, k_pos_ref, q_ref, k_ref, v_ref,  # inputs
            o_ref,                                      # output
            m_scr, l_scr, acc_scr,                      # scratch
            *, causal: bool, window: Optional[int], softcap: Optional[float],
            scale: float, n_k: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)          # (bk, hd)
    qp = q_pos_ref[0]                            # (bq, 1)
    kp = k_pos_ref[0]                            # (1, bk)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    d = qp - kp
    ok = kp >= 0
    if causal:
        ok &= d >= 0
    if window is not None:
        ok &= d < window
    s = jnp.where(ok, s, _NEG_INF)

    m_prev = m_scr[...]                           # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array,
    q_pos: jax.Array, k_pos: jax.Array,
    causal: bool = True, window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """q (B,Sq,H,hd); k/v (B,Sk,K,hd); q_pos (B,Sq); k_pos (B,Sk).

    Sq and Sk must be multiples of the block sizes (ops.py pads)."""
    B, Sq, H, hd = q.shape
    _, Sk, K, _ = k.shape
    G = H // K
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    if Sq % block_q or Sk % block_k:
        raise ValueError(f"Sq={Sq}/Sk={Sk} must divide blocks ({block_q},{block_k})")
    n_q, n_k = Sq // block_q, Sk // block_k

    # layout: (B, heads, S, hd) for blocked access.  Positions go in as a
    # (B, Sq, 1) column and a (B, 1, Sk) row: a TPU block's last two dims
    # must tile (8, 128) or span the array, which a (1, block) slice of a
    # (B, S) array does not once B > 1.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _kernel, causal=causal, window=window, softcap=softcap,
        scale=1.0 / math.sqrt(hd), n_k=n_k)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda b, h, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, qi, ki: (b, 0, ki)),
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q_pos[:, :, None], k_pos[:, None, :], qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
