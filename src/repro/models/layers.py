"""Core layers: norms, RoPE, GQA/MQA attention (naive + chunked online-softmax,
or the Pallas flash kernel of kernels/ops.py: ``attn_impl``, see
``attn_impl()``), gated MLPs, embeddings.

Parameter containers are plain nested dicts so they stack cleanly for
scan-over-layers and shard via path-based rules (dist/sharding.py).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

Params = Dict[str, Any]


# -- init helpers ---------------------------------------------------------------

def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_norm(d: int, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    p = {"scale": jnp.ones((d,), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    # Reductions in fp32 (numerics), multiplies in the activation dtype: a full
    # fp32 copy of x would otherwise be saved as a backward residual — at
    # (L, B, S, D) stacked over a scanned layer stack that doubles activation
    # memory (observed: +160 GiB/device on qwen-110b train_4k).
    if cfg.norm == "layernorm":
        mu = x.astype(jnp.float32).mean(-1, keepdims=True)
        var = jnp.square(x.astype(jnp.float32) - mu).mean(-1, keepdims=True)
        inv = jax.lax.rsqrt(var + cfg.norm_eps)
        y = (x - mu.astype(x.dtype)) * inv.astype(x.dtype)
        return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)
    var = jnp.square(x.astype(jnp.float32)).mean(-1, keepdims=True)
    inv = jax.lax.rsqrt(var + cfg.norm_eps)
    return x * inv.astype(x.dtype) * p["scale"].astype(x.dtype)


# -- rotary position embeddings ----------------------------------------------------

def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions (...,) -> cos/sin of shape (..., head_dim//2), fp32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., S, H, hd); cos/sin: (..., S, half) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]  # add head axis
    s = sin[..., None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * c - xf2 * s, xf2 * c + xf1 * s], axis=-1).astype(x.dtype)


# -- attention ---------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (D, H * hd), dtype),
        "wk": _dense_init(ks[1], (D, K * hd), dtype),
        "wv": _dense_init(ks[2], (D, K * hd), dtype),
        "wo": _dense_init(ks[3], (H * hd, D), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((K * hd,), dtype)
        p["bv"] = jnp.zeros((K * hd,), dtype)
    return p


def _qkv(p: Params, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    H, K, hd = cfg.n_heads, cfg.kv_heads, cfg.hd
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return (q.reshape(B, S, H, hd), k.reshape(B, S, K, hd), v.reshape(B, S, K, hd))


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
               window: Optional[int]) -> jax.Array:
    """(…, Sq, Sk) additive bias in fp32: 0 allowed / -inf masked."""
    d = q_pos[..., :, None] - k_pos[..., None, :]
    ok = k_pos[..., None, :] >= 0  # ring-cache slots still empty carry kpos=-1
    if causal:
        ok &= d >= 0
    if window is not None:
        ok &= d < window
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def _sdpa(q, k, v, bias, softcap: Optional[float]) -> jax.Array:
    """q (B,Sq,H,hd) k/v (B,Sk,K,hd) bias (B?,Sq,Sk) -> (B,Sq,H,hd). GQA via reshape."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    logits = logits / math.sqrt(hd)
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    logits = logits + bias[:, None, None, :, :]
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    return out.reshape(B, Sq, H, hd)


def _sdpa_chunked(q, k, v, q_pos, k_pos, causal, window, softcap, chunk: int) -> jax.Array:
    """Online-softmax over q-chunks: memory O(Sq_blk * Sk), never (Sq, Sk) full.

    The flash-attention recurrence over query blocks (k/v stay resident); used
    for long-sequence shapes where the naive (Sq, Sk) score tensor would not
    fit HBM.  fp32 accumulators.
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    n_chunks = -(-Sq // chunk)
    pad = n_chunks * chunk - Sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-1)
    qc = q.reshape(B, n_chunks, chunk, H, hd)
    pc = q_pos.reshape(B, n_chunks, chunk)

    # jax.checkpoint: without it, autodiff saves every chunk's (chunk, Sk)
    # logits across the scan — exactly the O(Sq*Sk) blow-up this code exists
    # to avoid.  Rematerializing the chunk in backward keeps memory O(chunk*Sk).
    @jax.checkpoint
    def body(_, xs):
        qb, pb = xs  # (B, chunk, H, hd), (B, chunk)
        qg = qb.reshape(B, chunk, K, G, hd)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) / math.sqrt(hd)
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
        bias = _mask_bias(pb, k_pos, causal, window)  # (B, chunk, Sk)
        logits = logits + bias[:, None, None, :, :]
        m = jnp.max(logits, axis=-1, keepdims=True)
        m = jnp.maximum(m, -1e30)  # rows fully masked
        p = jnp.exp(logits - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bkgqs,bskd->bqkgd", (p / jnp.maximum(l, 1e-30)).astype(q.dtype), v)
        return None, o.reshape(B, chunk, H, hd)

    _, out = jax.lax.scan(body, None, (qc.swapaxes(0, 1), pc.swapaxes(0, 1)))
    out = out.swapaxes(0, 1).reshape(B, n_chunks * chunk, H, hd)
    return out[:, :Sq]


def project_qkv(p: Params, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """QKV projection + RoPE.  Returns q (B,S,H,hd), k/v (B,S,K,hd)."""
    q, k, v = _qkv(p, x, cfg)
    cos, sin = rope_angles(positions, cfg.hd, cfg.rope_theta)
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def attn_impl(impl: str, sq: int, sk: int) -> str:
    """The attention path a call takes: ``naive``, ``chunked`` or ``flash``.

    ``auto`` picks from the platform and the shapes: decode (one query)
    stays naive; on a TPU, a step XLA does not partition takes the Pallas
    flash kernel, whose S x S scores live only in VMEM, forward and
    backward; elsewhere the jnp paths (naive up to 2048 keys, chunked
    above).  An explicit choice is honoured; ``pallas`` and ``chunked`` fall
    back to naive for one query."""
    if impl == "auto":
        from ..dist.sharding import activations_partitioned
        if sq > 1 and jax.default_backend() == "tpu" and not activations_partitioned():
            return "flash"
        impl = "chunked" if sk > 2048 else "naive"
    if impl not in ("naive", "chunked", "pallas"):
        raise ValueError(f"unknown attn_impl {impl!r}")
    if sq == 1 or impl == "naive":
        return "naive"
    return "flash" if impl == "pallas" else "chunked"


def attend(q: jax.Array, k_all: jax.Array, v_all: jax.Array,
           q_pos: jax.Array, k_pos: jax.Array, cfg: ModelConfig,
           causal: bool = True, window: Optional[int] = None,
           impl: Optional[str] = None) -> jax.Array:
    """Scaled-dot-product attention core with mask from positions.  The
    path taken is counted, at trace time, as ``attention.impl.<path>`` in
    ``repro.obs.PROCESS_METRICS``; its ops carry the scope
    ``attention.<path>``."""
    from ..obs import PROCESS_METRICS
    path = attn_impl(impl or cfg.attn_impl, q.shape[1], k_all.shape[1])
    PROCESS_METRICS.counter(f"attention.impl.{path}").inc()
    with jax.named_scope(f"attention.{path}"):
        if path == "flash":
            from ..kernels import ops as kops
            return kops.flash_attention(q, k_all, v_all, q_pos, k_pos,
                                        causal=causal, window=window,
                                        softcap=cfg.logit_softcap,
                                        precision=cfg.matmul_precision)
        if path == "chunked":
            return _sdpa_chunked(q, k_all, v_all, q_pos, k_pos, causal, window,
                                 cfg.logit_softcap, cfg.attn_chunk)
        bias = _mask_bias(q_pos, k_pos, causal, window)
        return _sdpa(q, k_all, v_all, bias, cfg.logit_softcap)


def attention(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Self-attention layer (no cache).  Returns (out, (k, v)) — this call's
    post-RoPE keys/values so callers can fill decode caches (prefill)."""
    q, k_new, v_new = project_qkv(p, x, cfg, positions)
    out = attend(q, k_new, v_new, positions, positions, cfg, causal, window, impl)
    B, S_, H, hd = out.shape
    y = out.reshape(B, S_, H * hd) @ p["wo"]
    return y, (k_new, v_new)


# -- MLPs -------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    D, F = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.activation in ("swiglu", "geglu"):
        return {
            "w_gate": _dense_init(ks[0], (D, F), dtype),
            "w_up": _dense_init(ks[1], (D, F), dtype),
            "w_down": _dense_init(ks[2], (F, D), dtype),
        }
    return {
        "w_in": _dense_init(ks[0], (D, F), dtype),
        "b_in": jnp.zeros((F,), dtype),
        "w_out": _dense_init(ks[1], (F, D), dtype),
        "b_out": jnp.zeros((D,), dtype),
    }


def apply_mlp(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.activation == "swiglu":
        return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    if cfg.activation == "geglu":
        return (jax.nn.gelu(x @ p["w_gate"], approximate=True) * (x @ p["w_up"])) @ p["w_down"]
    h = jax.nn.gelu(x @ p["w_in"] + p["b_in"].astype(x.dtype), approximate=True)
    return h @ p["w_out"] + p["b_out"].astype(x.dtype)


# -- embeddings ----------------------------------------------------------------------

def init_embedding(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    V = cfg.padded_vocab or cfg.vocab_size
    p = {"tok": (jax.random.normal(key, (V, cfg.d_model)) * 0.02).astype(dtype)}
    return p


def embed_tokens(p: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0).astype(jnp.dtype(cfg.activation_dtype))
    if cfg.embedding_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def lm_logits(embed_p: Params, head_p: Optional[Params], x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings or head_p is None:
        logits = x @ embed_p["tok"].T.astype(x.dtype)
    else:
        logits = x @ head_p["w"].astype(x.dtype)
    if cfg.padded_vocab and cfg.padded_vocab > cfg.vocab_size:
        # mask padding rows: -inf contributes nothing to logsumexp/argmax and
        # keeps the padded (shardable) vocab axis intact — no unsharded slice
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        logits = jnp.where(iota < cfg.vocab_size,
                           logits, jnp.asarray(-1e30, logits.dtype))
    return logits


def init_lm_head(key, cfg: ModelConfig) -> Optional[Params]:
    if cfg.tie_embeddings:
        return None
    dtype = jnp.dtype(cfg.param_dtype)
    V = cfg.padded_vocab or cfg.vocab_size
    return {"w": _dense_init(key, (cfg.d_model, V), dtype, scale=0.02)}
