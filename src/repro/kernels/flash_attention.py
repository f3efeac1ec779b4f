"""Flash attention Pallas kernels — blocked online softmax, forward and backward.

Three kernels, each on a grid whose last axis is sequential on TPU, so fp32
running state lives in VMEM scratch across that axis and the (Sq, Sk) scores
exist only block by block:

- forward, grid (B, H, nQ, nK): running (max, sum, acc) per query block; it
  writes the output and each row's float32 logsumexp (the backward's
  residual).
- dq, grid (B, H, nQ, nK): recomputes P = exp(S - lse) block by block and
  accumulates dQ = dS K, with dS = P * (dO V^T - D), D = rowsum(dO * O)
  (dO rounded as the MXU takes it, so D matches the sum of P * dP).
- dk/dv, grid (B, K, nK, G * nQ): the same recomputation from the key side;
  it sums dK = dS^T Q and dV = P^T dO over the G query heads of its KV head.

Masks come from explicit position vectors: causal, sliding-window or
bidirectional, ring caches pass k_pos = -1 for unfilled slots; a row with no
visible key gives zeros and no gradient.  Logit soft-capping is
differentiated.  A block pair whose positions leave no visible key does no
work, and the index maps clamp to the live span of blocks, so K/V (or Q)
blocks outside it are never fetched.  (Skipping the mask in fully visible
blocks measured 1 % of the kernels' time on a v5e, and is not done.)

Precision: ``None`` feeds the MXU bfloat16 operands with float32
accumulation (what JAX's default does to a float32 matmul on a TPU);
``"highest"`` feeds float32 operands at ``Precision.HIGHEST``.  Max, exp,
sums, logsumexp and accumulators are float32 either way.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas", "block_sizes"]

_NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def block_sizes(sq: int, sk: int, hd: int,
                precision: Optional[str] = None) -> Tuple[int, int]:
    """(block_q, block_k) for a (Sq, Sk, head_dim) call at a precision.

    1024 x 1024 up to head_dim 128 (the fastest at SmolLM-135M's 8 x 2048,
    hd 64, on a v5e: PERF.md), halved for each doubling of the head beyond
    128 and again at "highest", so that a block's VMEM stays what it is at
    hd 128: larger blocks overflow a v5e's scoped VMEM in the backward (hd
    256 at 1024, hd 64 at "highest" at 1024).  A shorter length is one
    block; a longer one not a multiple of the block takes its largest
    divisor of 1/2 or 1/4 the block, down to 128, else 128, and is padded."""
    pref = 1024 >> max(0, (hd - 1).bit_length() - 7)
    pref = max(128, pref // 2 if precision == "highest" else pref)
    return _pick(sq, pref), _pick(sk, pref)


def _pick(s: int, pref: int) -> int:
    if s <= pref:
        return s
    return next((b for b in (pref, pref // 2, pref // 4) if b >= 128 and s % b == 0),
                128)


class _Static(NamedTuple):
    causal: bool
    window: Optional[int]
    softcap: Optional[float]
    precision: Optional[str]
    block_q: int
    block_k: int
    interpret: bool


# -- the kernels' shared block math ------------------------------------------------

def _operand(x, precision):
    """``x`` as the MXU takes it at the stated precision."""
    return x.astype(jnp.float32 if precision == "highest" else jnp.bfloat16)


def _dot(a, b, dims, precision):
    """One MXU product at the stated precision, float32 out."""
    return jax.lax.dot_general(
        _operand(a, precision), _operand(b, precision), dims,
        precision=jax.lax.Precision.HIGHEST if precision == "highest" else None,
        preferred_element_type=jnp.float32)


def _delta(do, o, precision):
    """D = rowsum(dO * O), with dO as the dO V^T product takes it, so that D
    is the sum of P * dP that the softmax backward subtracts."""
    return jnp.sum(_operand(do, precision).astype(jnp.float32)
                   * o.astype(jnp.float32), axis=-1, keepdims=True)


def _scores(q, k, qp, kp, st: _Static):
    """Masked logits (bq, bk) of scaled q against k, and tanh(raw / softcap)
    for the softcap's derivative (None without a softcap)."""
    s = _dot(q, k, _NT, st.precision)
    t = None
    if st.softcap:
        t = jnp.tanh(s / st.softcap)
        s = t * st.softcap
    ok = kp >= 0
    if st.causal or st.window is not None:
        d = qp - kp
        if st.causal:
            ok &= d >= 0
        if st.window is not None:
            ok &= d < st.window
    return jnp.where(ok, s, _NEG_INF), t


def _live(spans, row, i):
    """Block ``i`` of a grid row lies in the row's live span."""
    return (spans[0][row] <= i) & (i <= spans[1][row])


def _clamp(spans, row, i):
    """Block ``i`` held inside the row's live span: a dead step repeats its
    neighbour's block index, so its block is not fetched again."""
    lo, hi = spans[0][row], spans[1][row]
    return jnp.minimum(jnp.maximum(i, lo), jnp.maximum(hi, lo))


def _softmax_grad(p, dp, delta, t):
    ds = p * (dp - delta)
    return ds * (1.0 - t * t) if t is not None else ds


# -- forward ---------------------------------------------------------------------------

def _fwd_kernel(lo, hi, qp_ref, kp_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr, *, st: _Static,
                scale: float, n_q: int, n_k: int):
    ki = pl.program_id(3)
    row = pl.program_id(0) * n_q + pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_live((lo, hi), row, ki))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        s, _ = _scores(q, k_ref[0, 0], qp_ref[0], kp_ref[0], st)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + _dot(p, v_ref[0, 0], _NN,
                                                   st.precision)
        m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        # A row with no visible key gives zeros, and an lse that no logit
        # reaches, so the backward's P is zero there too.
        m, l = m_scr[...], l_scr[...]
        empty = m < 0.5 * _NEG_INF
        o = acc_scr[...] / jnp.maximum(l, 1e-30)
        o_ref[0, 0] = jnp.where(empty, 0.0, o).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(empty, -_NEG_INF,
                                  m + jnp.log(jnp.maximum(l, 1e-30)))


# -- backward --------------------------------------------------------------------------

def _dq_kernel(lo, hi, qp_ref, kp_ref, q_ref, k_ref, v_ref, do_ref, o_ref,
               lse_ref, dq_ref, dq_scr, d_scr, *, st: _Static, scale: float,
               n_q: int, n_k: int):
    ki = pl.program_id(3)
    row = pl.program_id(0) * n_q + pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        d_scr[...] = _delta(do_ref[0, 0], o_ref[0, 0], st.precision)

    @pl.when(_live((lo, hi), row, ki))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0]
        s, t = _scores(q, k, qp_ref[0], kp_ref[0], st)
        p = jnp.exp(s - lse_ref[0, 0])
        dp = _dot(do_ref[0, 0], v_ref[0, 0], _NT, st.precision)
        ds = _softmax_grad(p, dp, d_scr[...], t)
        dq_scr[...] += _dot(ds, k, _NN, st.precision)

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(lo, hi, qp_ref, kp_ref, q_ref, k_ref, v_ref, do_ref, o_ref,
                lse_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                st: _Static, scale: float, n_q: int, n_k: int, groups: int):
    t_idx = pl.program_id(3)
    qi = t_idx % n_q
    row = pl.program_id(0) * n_k + pl.program_id(2)

    @pl.when(t_idx == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_live((lo, hi), row, qi))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        do = do_ref[0, 0]
        s, t = _scores(q, k_ref[0, 0], qp_ref[0], kp_ref[0], st)
        p = jnp.exp(s - lse_ref[0, 0])
        dv_scr[...] += _dot(p.T, do, _NN, st.precision)
        dp = _dot(do, v_ref[0, 0], _NT, st.precision)
        ds = _softmax_grad(p, dp, _delta(do, o_ref[0, 0], st.precision), t)
        dk_scr[...] += _dot(ds.T, q, _NN, st.precision)

    @pl.when(t_idx == groups * n_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


# -- wrappers --------------------------------------------------------------------------

def _block_spans(q_pos, k_pos, st: _Static):
    """The first and last live block of each grid row, on each side: per
    (b, query block) over key blocks, and per (b, key block) over query
    blocks, each as (B * n,) int32 vectors for scalar prefetch.  A block pair
    is live unless its positions rule out every visible key: no valid slot,
    every key after every query (causal), or every key beyond the window.  A
    row with none live gets the empty span (0, -1)."""
    B, Sq = q_pos.shape
    Sk = k_pos.shape[1]
    qb = q_pos.reshape(B, Sq // st.block_q, st.block_q)
    kb = k_pos.reshape(B, Sk // st.block_k, st.block_k)
    valid = kb >= 0
    kmin = jnp.where(valid, kb, jnp.iinfo(jnp.int32).max).min(-1)[:, None, :]
    kmax = jnp.where(valid, kb, -1).max(-1)[:, None, :]
    live = jnp.broadcast_to(kmax >= 0, (B, qb.shape[1], kb.shape[1]))
    if st.causal:
        live = live & (kmin <= qb.max(-1)[:, :, None])
    if st.window is not None:
        live = live & (kmax > qb.min(-1)[:, :, None] - st.window)

    def span(a):
        idx = jnp.arange(a.shape[-1])
        hi = jnp.where(a, idx, -1).max(-1)
        lo = jnp.where(hi >= 0, jnp.where(a, idx, a.shape[-1]).min(-1), 0)
        return lo.reshape(-1).astype(jnp.int32), hi.reshape(-1).astype(jnp.int32)

    return span(live), span(live.swapaxes(1, 2))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _by_query_block(G, n_q, bq, bk, hd, n_row_inputs):
    """Block specs of a (B, H, nQ, nK) grid, the forward's and dq's: query
    positions, key positions, q, k, v, then ``n_row_inputs`` more blocks of
    the query rows (dO, O, lse), all clamped to the row's live key span."""
    def k_blk(b, h, qi, ki, *spans):
        return (b, h // G, _clamp(spans, b * n_q + qi, ki), 0)

    def q_blk(b, h, qi, ki, *spans):
        return (b, h, qi, 0)

    rows = [pl.BlockSpec((1, 1, bq, hd), q_blk), pl.BlockSpec((1, 1, bq, hd), q_blk),
            pl.BlockSpec((1, 1, bq, 1), q_blk)][:n_row_inputs]
    return q_blk, [
        pl.BlockSpec((1, bq, 1), lambda b, h, qi, ki, *spans: (b, qi, 0)),
        pl.BlockSpec((1, 1, bk), lambda b, h, qi, ki, *spans:
                     (b, 0, _clamp(spans, b * n_q + qi, ki))),
        pl.BlockSpec((1, 1, bq, hd), q_blk),
        pl.BlockSpec((1, 1, bk, hd), k_blk),
        pl.BlockSpec((1, 1, bk, hd), k_blk),
    ] + rows


def _forward(q, k, v, q_pos, k_pos, st: _Static):
    B, Sq, H, hd = q.shape
    _, Sk, K, _ = k.shape
    bq, bk = st.block_q, st.block_k
    n_q, n_k = Sq // bq, Sk // bk
    by_q, by_k = _block_spans(q_pos, k_pos, st)
    # (B, heads, S, hd) for blocked access.  Positions go in as a (B, Sq, 1)
    # column and a (B, 1, Sk) row: a TPU block's last two dims must tile
    # (8, 128) or span the array, which a (1, block) slice of (B, S) does not.
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    qp, kp = q_pos[:, :, None], k_pos[:, None, :]
    q_blk, in_specs = _by_query_block(H // K, n_q, bq, bk, hd, 0)
    ot, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, st=st, scale=1.0 / math.sqrt(hd),
                          n_q=n_q, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, n_q, n_k),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, 1, bq, hd), q_blk),
                       pl.BlockSpec((1, 1, bq, 1), q_blk)],
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),   # running max
                pltpu.VMEM((bq, 1), jnp.float32),   # running sum
                pltpu.VMEM((bq, hd), jnp.float32),  # output accumulator
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=st.interpret,
        name="flash_attention_fwd",
    )(*by_q, qp, kp, qt, kt, vt)
    return ot.transpose(0, 2, 1, 3), (qt, kt, vt, ot, lse, qp, kp, by_q, by_k)


def _backward(st: _Static, res, g):
    qt, kt, vt, ot, lse, qp, kp, by_q, by_k = res
    B, H, Sq, hd = qt.shape
    _, K, Sk, _ = kt.shape
    G = H // K
    bq, bk = st.block_q, st.block_k
    n_q, n_k = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(hd)
    operands = (qp, kp, qt, kt, vt, g.transpose(0, 2, 1, 3), ot, lse)

    # dq: one query block against its live key blocks.
    q_blk, in_specs = _by_query_block(G, n_q, bq, bk, hd, 3)
    dqt = pl.pallas_call(
        functools.partial(_dq_kernel, st=st, scale=scale, n_q=n_q, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, n_q, n_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, bq, hd), q_blk),
            scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32),   # dq
                            pltpu.VMEM((bq, 1), jnp.float32)]),  # D
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), qt.dtype),
        compiler_params=_PARAMS,
        interpret=st.interpret,
        name="flash_attention_dq",
    )(*by_q, *operands)

    # dk, dv: one key block against the live query blocks of its G heads.
    def q_side(b, j, ki, t, *spans):
        return (b, j * G + t // n_q, _clamp(spans, b * n_k + ki, t % n_q), 0)

    def k_side(b, j, ki, t, *spans):
        return (b, j, ki, 0)

    dkt, dvt = pl.pallas_call(
        functools.partial(_dkv_kernel, st=st, scale=scale, n_q=n_q, n_k=n_k,
                          groups=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, K, n_k, G * n_q),
            in_specs=[
                pl.BlockSpec((1, bq, 1), lambda b, j, ki, t, *spans:
                             (b, _clamp(spans, b * n_k + ki, t % n_q), 0)),
                pl.BlockSpec((1, 1, bk), lambda b, j, ki, t, *spans: (b, 0, ki)),
                pl.BlockSpec((1, 1, bq, hd), q_side),
                pl.BlockSpec((1, 1, bk, hd), k_side),
                pl.BlockSpec((1, 1, bk, hd), k_side),
                pl.BlockSpec((1, 1, bq, hd), q_side),
                pl.BlockSpec((1, 1, bq, hd), q_side),
                pl.BlockSpec((1, 1, bq, 1), q_side),
            ],
            out_specs=[pl.BlockSpec((1, 1, bk, hd), k_side),
                       pl.BlockSpec((1, 1, bk, hd), k_side)],
            scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                            pltpu.VMEM((bk, hd), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, K, Sk, hd), kt.dtype),
                   jax.ShapeDtypeStruct((B, K, Sk, hd), vt.dtype)],
        compiler_params=_PARAMS,
        interpret=st.interpret,
        name="flash_attention_dkv",
    )(*by_k, *operands)
    return (dqt.transpose(0, 2, 1, 3), dkt.transpose(0, 2, 1, 3),
            dvt.transpose(0, 2, 1, 3), None, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _flash(q, k, v, q_pos, k_pos, st: _Static):
    return _forward(q, k, v, q_pos, k_pos, st)[0]


_flash.defvjp(_forward, _backward)


def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array,
    q_pos: jax.Array, k_pos: jax.Array,
    causal: bool = True, window: Optional[int] = None,
    softcap: Optional[float] = None, precision: Optional[str] = None,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """q (B,Sq,H,hd); k/v (B,Sk,K,hd); q_pos (B,Sq); k_pos (B,Sk) -> (B,Sq,H,hd).

    Differentiable in q, k and v.  Blocks default to ``block_sizes``.  Sq
    and Sk are padded to multiples of the blocks: extra query rows repeat
    the last query position and are sliced off (their cotangent is zero);
    extra key slots get k_pos = -1, which the mask rejects."""
    if precision not in (None, "highest"):
        raise ValueError(f"precision must be None or 'highest', not {precision!r}")
    Sq, Sk, hd = q.shape[1], k.shape[1], q.shape[3]
    auto_q, auto_k = block_sizes(Sq, Sk, hd, precision)
    bq, bk = min(block_q or auto_q, Sq), min(block_k or auto_k, Sk)
    q_pos, k_pos = q_pos.astype(jnp.int32), k_pos.astype(jnp.int32)
    pq, pk = -Sq % bq, -Sk % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pq)), mode="edge")
    if pk:
        k, v = (jnp.pad(x, ((0, 0), (0, pk), (0, 0), (0, 0))) for x in (k, v))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pk)), constant_values=-1)
    st = _Static(bool(causal), window, softcap or None, precision, bq, bk,
                 bool(interpret))
    out = _flash(q, k, v, q_pos, k_pos, st)
    return out[:, :Sq] if pq else out
