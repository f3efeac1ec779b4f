"""RWKV-6 "Finch" (arXiv:2404.05892) — attention-free, data-dependent decay.

Time-mixing:   S_t = diag(w_t) S_{t-1} + k_t v_t^T,
               y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
with per-channel data-dependent decay w_t = exp(-exp(w0 + lora_w(x))) and
data-dependent token-shift interpolation (DDLerp) for r/k/v/w/g.

Training uses an exact *chunked* evaluation: within a chunk, intra-chunk
contributions are a masked matmul with decay-ratio weights computed in log
space (ratios are always <= 1, so no overflow); inter-chunk state is carried by
``lax.scan``.  Decode is the plain single-step recurrence.  The Pallas kernel
(kernels/rwkv6_scan.py) implements the same chunked scheme with VMEM tiling;
it has no backward yet, so the model always takes the jnp scan.

Channel-mixing: squared-ReLU MLP with static token-shift (Finch eq. 20-22).

Device ops carry the named scopes ``rwkv6.time_mix`` (with ``rwkv6.wkv``
inside it) and ``rwkv6.channel_mix``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import _dense_init

Params = Dict[str, Any]

_N_MIX = 5  # r, k, v, w, g
_LORA_MIX = 32
_LORA_DECAY = 64
# w0 + lora above this would make -exp(.) overflow float32 to -inf, and the
# chunked form's log-space cumsum NaN (-inf - -inf); exp(-exp(8)) is already
# 0 in float32, so the clip changes no decay.
_LOG_DECAY_MAX = 8.0


def init_time_mix(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    D = cfg.d_model
    N = cfg.rwkv_head_dim
    H = D // N
    ks = jax.random.split(key, 10)
    return {
        "mu_x": jnp.zeros((D,), dtype),
        "mu_rkvwg": jnp.zeros((_N_MIX, D), dtype),
        "maa_w1": _dense_init(ks[0], (D, _N_MIX * _LORA_MIX), dtype, scale=1e-2),
        "maa_w2": _dense_init(ks[1], (_N_MIX, _LORA_MIX, D), dtype, scale=1e-2),
        "w0": jnp.full((D,), -6.0, dtype),  # slow initial decay
        "w_lora_a": _dense_init(ks[2], (D, _LORA_DECAY), dtype, scale=1e-2),
        "w_lora_b": _dense_init(ks[3], (_LORA_DECAY, D), dtype, scale=1e-2),
        "u": (jax.random.normal(ks[4], (H, N)) * 0.1).astype(dtype),
        "w_r": _dense_init(ks[5], (D, D), dtype),
        "w_k": _dense_init(ks[6], (D, D), dtype),
        "w_v": _dense_init(ks[7], (D, D), dtype),
        "w_g": _dense_init(ks[8], (D, D), dtype),
        "w_o": _dense_init(ks[9], (D, D), dtype),
        "ln_x_scale": jnp.ones((D,), dtype),
        "ln_x_bias": jnp.zeros((D,), dtype),
    }


def init_channel_mix(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "mu_k": jnp.zeros((D,), dtype),
        "mu_r": jnp.zeros((D,), dtype),
        "w_k": _dense_init(ks[0], (D, F), dtype),
        "w_v": _dense_init(ks[1], (F, D), dtype),
        "w_r": _dense_init(ks[2], (D, D), dtype),
    }


def _token_shift(x: jax.Array, prev: Optional[jax.Array]) -> jax.Array:
    """s_t = x_{t-1}; position 0 uses ``prev`` (decode state) or zeros."""
    if x.shape[1] == 1:
        return prev[:, None, :] if prev is not None else jnp.zeros_like(x)
    first = prev[:, None, :] if prev is not None else jnp.zeros_like(x[:, :1])
    return jnp.concatenate([first, x[:, :-1]], axis=1)


def _ddlerp(p: Params, x: jax.Array, s: jax.Array) -> jax.Array:
    """Data-dependent lerp -> (5, B, S, D) mixed inputs for r/k/v/w/g."""
    xm = x + (s - x) * p["mu_x"].astype(x.dtype)
    lora = jnp.tanh(xm @ p["maa_w1"].astype(x.dtype))           # (B,S,5*r)
    lora = lora.reshape(*lora.shape[:-1], _N_MIX, _LORA_MIX)
    m = jnp.einsum("bsnr,nrd->nbsd", lora, p["maa_w2"].astype(x.dtype))
    m = m + p["mu_rkvwg"].astype(x.dtype)[:, None, None, :]
    return x[None] + (s - x)[None] * m


def _decay(p: Params, xw: jax.Array) -> jax.Array:
    """log-decay (negative), fp32: logw = -exp(w0 + lora_w(xw))."""
    lora = jnp.tanh(xw @ p["w_lora_a"].astype(xw.dtype)) @ p["w_lora_b"].astype(xw.dtype)
    return -jnp.exp(jnp.minimum(p["w0"].astype(jnp.float32) + lora.astype(jnp.float32),
                                _LOG_DECAY_MAX))


def _group_norm(p: Params, y: jax.Array, n_heads: int, eps: float = 64e-5) -> jax.Array:
    """Per-head LayerNorm over the flattened (H*N) output (RWKV ln_x)."""
    B, S, D = y.shape
    yh = y.reshape(B, S, n_heads, D // n_heads).astype(jnp.float32)
    mu = yh.mean(-1, keepdims=True)
    var = ((yh - mu) ** 2).mean(-1, keepdims=True)
    yh = (yh - mu) * jax.lax.rsqrt(var + eps)
    out = yh.reshape(B, S, D) * p["ln_x_scale"].astype(jnp.float32) + p["ln_x_bias"].astype(jnp.float32)
    return out.astype(y.dtype)


def _wkv_chunked(r, k, v, logw, u, state, chunk: int):
    """Exact chunked WKV.  r/k/v: (B,S,H,N); logw fp32 (B,S,H,N); u (H,N);
    state (B,H,N,N) fp32.  Returns (y (B,S,H,N), new_state).

    Each chunk is recomputed in the backward pass: otherwise the backward
    keeps every chunk's (B,L,L,H,N) decay ratios, S*L*H*N floats a tensor
    (1 GiB each at 1 x 4096, L = 32, 32 heads of 64), and keeps several."""
    B, S, H, N = r.shape
    L = min(chunk, S)
    n_chunks = -(-S // L)
    pad = n_chunks * L - S
    if pad:
        zpad = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = zpad(r), zpad(k), zpad(v)
        logw = jnp.pad(logw, ((0, 0), (0, pad), (0, 0), (0, 0)))  # logw=0 -> w=1 (no decay)
    cs = lambda a: a.reshape(B, n_chunks, L, H, N).swapaxes(0, 1)
    rc, kc, vc, wc = cs(r), cs(k), cs(v), cs(logw)

    mask = jnp.tril(jnp.ones((L, L), bool), k=-1)  # strictly lower: tau < t

    def body(S0, xs):
        rb, kb, vb, wb = xs                    # (B,L,H,N)
        rb32, kb32, vb32 = (a.astype(jnp.float32) for a in (rb, kb, vb))
        cum = jnp.cumsum(wb, axis=1)           # inclusive cumsum of log-decay
        cum_excl = cum - wb                    # exclusive
        # intra-chunk: A[t,tau] = sum_n r[t,n] k[tau,n] exp(cum_excl[t]-cum[tau])
        ratio = cum_excl[:, :, None, :, :] - cum[:, None, :, :, :]  # (B,t,tau,H,N)
        ratio = jnp.where(mask[None, :, :, None, None], ratio, -jnp.inf)
        A = jnp.einsum("bthn,bshn,btshn->bhts", rb32, kb32, jnp.exp(ratio))
        diag = jnp.einsum("bthn,hn,bthn->bht", rb32, u.astype(jnp.float32), kb32)
        A = A + jnp.eye(L)[None, None] * diag[..., None]
        y_intra = jnp.einsum("bhts,bshn->bthn", A, vb32)
        # inter-chunk: y += (r ⊙ exp(cum_excl))^T S0
        y_inter = jnp.einsum("bthn,bhnm->bthm", rb32 * jnp.exp(cum_excl), S0)
        # state update: S = diag(exp(cum_L)) S0 + sum_tau (k ⊙ exp(cum_L - cum_tau)) v^T
        decay_all = jnp.exp(cum[:, -1])        # (B,H,N)
        k_scaled = kb32 * jnp.exp(cum[:, -1][:, None] - cum)
        S_new = decay_all[..., None] * S0 + jnp.einsum("bthn,bthm->bhnm", k_scaled, vb32)
        return S_new, (y_intra + y_inter).astype(r.dtype)

    state, ys = jax.lax.scan(jax.checkpoint(body), state, (rc, kc, vc, wc))
    y = ys.swapaxes(0, 1).reshape(B, n_chunks * L, H, N)
    return y[:, :S], state


def _wkv_step(r, k, v, logw, u, state):
    """Single decode step. r/k/v/logw: (B,H,N); state (B,H,N,N) fp32."""
    r32, k32, v32 = (a.astype(jnp.float32) for a in (r, k, v))
    kv = k32[..., :, None] * v32[..., None, :]             # (B,H,N,N)
    y = jnp.einsum("bhn,bhnm->bhm", r32, state + u.astype(jnp.float32)[None, :, :, None] * kv)
    state = jnp.exp(logw)[..., None] * state + kv
    return y.astype(r.dtype), state


def apply_time_mix(
    p: Params, x: jax.Array, cfg: ModelConfig,
    state: Optional[Dict[str, jax.Array]] = None,
    chunk: Optional[int] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x (B,S,D).  ``state`` = {"prev": (B,D), "wkv": (B,H,N,N) fp32} for decode."""
    chunk = chunk or cfg.rwkv_chunk
    B, S, D = x.shape
    N = cfg.rwkv_head_dim
    H = D // N
    with jax.named_scope("rwkv6.time_mix"):
        prev = state["prev"] if state else None
        s = _token_shift(x, prev)
        xr, xk, xv, xw, xg = _ddlerp(p, x, s)
        r = (xr @ p["w_r"].astype(x.dtype)).reshape(B, S, H, N)
        k = (xk @ p["w_k"].astype(x.dtype)).reshape(B, S, H, N)
        v = (xv @ p["w_v"].astype(x.dtype)).reshape(B, S, H, N)
        g = jax.nn.silu(xg @ p["w_g"].astype(x.dtype))
        logw = _decay(p, xw).reshape(B, S, H, N)

        wkv0 = state["wkv"] if state else jnp.zeros((B, H, N, N), jnp.float32)
        with jax.named_scope("rwkv6.wkv"):
            if S == 1:
                y, wkv = _wkv_step(r[:, 0], k[:, 0], v[:, 0], logw[:, 0], p["u"], wkv0)
                y = y[:, None]
            else:
                y, wkv = _wkv_chunked(r, k, v, logw, p["u"], wkv0, chunk)

        y = _group_norm(p, y.reshape(B, S, D), H) * g
        out = y @ p["w_o"].astype(x.dtype)
    return out, {"prev": x[:, -1], "wkv": wkv}


def apply_channel_mix(
    p: Params, x: jax.Array, cfg: ModelConfig,
    state: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    with jax.named_scope("rwkv6.channel_mix"):
        prev = state["prev"] if state else None
        s = _token_shift(x, prev)
        xk = x + (s - x) * p["mu_k"].astype(x.dtype)
        xr = x + (s - x) * p["mu_r"].astype(x.dtype)
        k = jnp.square(jax.nn.relu(xk @ p["w_k"].astype(x.dtype)))
        v = k @ p["w_v"].astype(x.dtype)
        rgate = jax.nn.sigmoid(xr @ p["w_r"].astype(x.dtype))
        out = rgate * v
    return out, {"prev": x[:, -1]}


def init_state(cfg: ModelConfig, batch: int) -> Dict[str, jax.Array]:
    """Per-layer decode state (O(1) in sequence length — the long_500k enabler)."""
    D = cfg.d_model
    N = cfg.rwkv_head_dim
    H = D // N
    adt = jnp.dtype(cfg.activation_dtype)
    return {
        "tm": {"prev": jnp.zeros((batch, D), adt), "wkv": jnp.zeros((batch, H, N, N), jnp.float32)},
        "cm": {"prev": jnp.zeros((batch, D), adt)},
    }
