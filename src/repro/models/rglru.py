"""RG-LRU recurrent block — RecurrentGemma / Griffin (arXiv:2402.19427).

Temporal block (recurrent variant):
    gate branch:      g = GeLU(x @ w_gate)
    recurrent branch: u = x @ w_x -> causal depthwise conv1d(width 4) -> RG-LRU
    output:           (g * h) @ w_out

RG-LRU:  r_t = sigmoid(x W_a + b_a), i_t = sigmoid(x W_i + b_i)
         log a_t = -c * softplus(lambda) * r_t            (c = 8)
         h_t = a_t h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

Training evaluates the linear recurrence with ``jax.lax.associative_scan``
(log-depth — the TPU-native choice for a 4k..512k sequence); decode is the
single step.  The Pallas scan (kernels/rglru_scan.py) has no backward, so
the model does not call it.  Griffin's block-diagonal gate matrices are
implemented dense (adaptation noted in DESIGN.md — dense is MXU-friendlier at
these widths).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import _dense_init

Params = Dict[str, Any]

_C = 8.0  # Griffin's fixed gate sharpness


def init_rglru_block(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    D = cfg.d_model
    R = cfg.rglru_d_rnn or D
    W = cfg.conv1d_width
    ks = jax.random.split(key, 6)
    # lambda init so that a^c = exp(-c*softplus(l)) is spread in (0.9, 0.999)
    lam = jax.random.uniform(ks[5], (R,), minval=math.log(math.exp(0.001) - 1) / 1,
                             maxval=math.log(math.exp(0.1) - 1))
    return {
        "w_gate": _dense_init(ks[0], (D, R), dtype),
        "w_x": _dense_init(ks[1], (D, R), dtype),
        "w_out": _dense_init(ks[2], (R, D), dtype),
        "conv_w": (jax.random.normal(ks[3], (W, R)) / math.sqrt(W)).astype(dtype),
        "conv_b": jnp.zeros((R,), dtype),
        "gates": {
            "w_a": _dense_init(ks[4], (R, R), dtype, scale=1.0 / math.sqrt(R)),
            "b_a": jnp.zeros((R,), dtype),
            "w_i": _dense_init(jax.random.fold_in(ks[4], 1), (R, R), dtype,
                               scale=1.0 / math.sqrt(R)),
            "b_i": jnp.zeros((R,), dtype),
        },
        "lam": lam.astype(jnp.float32),
    }


def _causal_conv1d(p: Params, u: jax.Array,
                   conv_state: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv. u (B,S,R); conv_state (B,W-1,R) carries history."""
    W = p["conv_w"].shape[0]
    if conv_state is None:
        conv_state = jnp.zeros((u.shape[0], W - 1, u.shape[2]), u.dtype)
    xext = jnp.concatenate([conv_state.astype(u.dtype), u], axis=1)  # (B, S+W-1, R)
    out = sum(
        xext[:, i : i + u.shape[1]] * p["conv_w"][i].astype(u.dtype)
        for i in range(W)
    ) + p["conv_b"].astype(u.dtype)
    return out, xext[:, -(W - 1):]


def _rglru_scan(a: jax.Array, b: jax.Array, h0: Optional[jax.Array]) -> jax.Array:
    """h_t = a_t h_{t-1} + b_t via associative scan over axis 1.  fp32."""
    if h0 is not None:
        # fold initial state into the first step
        b = b.at[:, 0].add(a[:, 0] * h0)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h


def apply_rglru_block(
    p: Params, x: jax.Array, cfg: ModelConfig,
    state: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x (B,S,D) -> (out (B,S,D), new_state {"h": (B,R) fp32, "conv": (B,W-1,R)})."""
    gate = jax.nn.gelu(x @ p["w_gate"].astype(x.dtype), approximate=True)
    u = x @ p["w_x"].astype(x.dtype)
    u, conv_state = _causal_conv1d(p, u, state["conv"] if state else None)

    u32 = u.astype(jnp.float32)
    g = p["gates"]
    r = jax.nn.sigmoid(u32 @ g["w_a"].astype(jnp.float32) + g["b_a"].astype(jnp.float32))
    i = jax.nn.sigmoid(u32 @ g["w_i"].astype(jnp.float32) + g["b_i"].astype(jnp.float32))
    log_a = -_C * jax.nn.softplus(p["lam"]) * r                  # (B,S,R) fp32
    a = jnp.exp(log_a)
    gated_in = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * u32)

    h0 = state["h"] if state else None
    if x.shape[1] == 1:
        h_prev = h0 if h0 is not None else jnp.zeros_like(gated_in[:, 0])
        h_last = a[:, 0] * h_prev + gated_in[:, 0]
        h = h_last[:, None]
    else:
        h = _rglru_scan(a, gated_in, h0)
        h_last = h[:, -1]

    out = (gate * h.astype(x.dtype)) @ p["w_out"].astype(x.dtype)
    return out, {"h": h_last, "conv": conv_state}


def init_state(cfg: ModelConfig, batch: int) -> Dict[str, jax.Array]:
    R = cfg.rglru_d_rnn or cfg.d_model
    adt = jnp.dtype(cfg.activation_dtype)
    return {
        "h": jnp.zeros((batch, R), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv1d_width - 1, R), adt),
    }
