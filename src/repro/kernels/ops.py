"""Public jit'd wrappers for the Pallas kernels.

Handles block-size padding of the scan and router kernels (each requires
divisible shapes; flash attention pads itself) and the choice of execution: on TPU the call sites lower to real Mosaic kernels; on
the CPU they run with ``interpret=True`` (the kernel body runs as it would on
TPU, minus the tiling), which is how the tests check them.  Any other backend
is refused rather than interpreted.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import moe_router as _router
from . import rglru_scan as _rglru
from . import rwkv6_scan as _rwkv

__all__ = ["flash_attention", "rwkv6_scan", "rglru_scan", "moe_router",
           "use_interpret"]


def use_interpret() -> bool:
    """False on TPU, True on the CPU; any other backend raises."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas kernels run on tpu, or interpreted on cpu; "
                           f"the default backend is {backend!r}")
    return backend == "cpu"


def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0) -> Tuple[jax.Array, int]:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), pad


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    q_pos: jax.Array, k_pos: jax.Array,
    causal: bool = True, window: Optional[int] = None,
    softcap: Optional[float] = None, precision: Optional[str] = None,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
) -> jax.Array:
    """flash_attention_pallas, interpreted on the CPU; see it for the
    contract (it pads to its blocks)."""
    return _fa.flash_attention_pallas(
        q, k, v, q_pos, k_pos, causal=causal, window=window, softcap=softcap,
        precision=precision, block_q=block_q, block_k=block_k,
        interpret=use_interpret())


def rwkv6_scan(
    r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
    u: jax.Array, state: jax.Array, chunk: int = _rwkv.DEFAULT_CHUNK,
) -> Tuple[jax.Array, jax.Array]:
    """Padding: logw=0 (w=1) and k=0 make padded steps state-identities."""
    S = r.shape[1]
    c = min(chunk, S)
    r, pad = _pad_to(r, 1, c)
    k, _ = _pad_to(k, 1, c)
    v, _ = _pad_to(v, 1, c)
    logw, _ = _pad_to(logw, 1, c)
    y, s_out = _rwkv.rwkv6_scan_pallas(r, k, v, logw, u, state, chunk=c,
                                       interpret=use_interpret())
    return (y[:, :S] if pad else y), s_out


def rglru_scan(
    a: jax.Array, b: jax.Array, h0: Optional[jax.Array] = None,
    chunk_t: int = _rglru.DEFAULT_CHUNK_T, block_r: int = _rglru.DEFAULT_BLOCK_R,
) -> jax.Array:
    """Padding: a=1, b=0 rows are identity steps; extra R lanes sliced off."""
    B, S, R = a.shape
    ct, br = min(chunk_t, S), min(block_r, R)
    a, pad_t = _pad_to(a, 1, ct, value=1.0)
    b, _ = _pad_to(b, 1, ct, value=0.0)
    a, pad_r = _pad_to(a, 2, br, value=1.0)
    b, _ = _pad_to(b, 2, br, value=0.0)
    if h0 is None:
        h0 = jnp.zeros((B, a.shape[2]), jnp.float32)
    else:
        h0, _ = _pad_to(h0, 1, br, value=0.0)
    h = _rglru.rglru_scan_pallas(a, b, h0, chunk_t=ct, block_r=br,
                                 interpret=use_interpret())
    return h[:, :S, :R]


def moe_router(logits: jax.Array, top_k: int,
               block_t: int = _router.DEFAULT_BLOCK_T) -> Tuple[jax.Array, jax.Array]:
    """Padding: extra token rows routed to garbage and sliced off."""
    T = logits.shape[0]
    bt = min(block_t, T)
    logits_p, pad = _pad_to(logits, 0, bt)
    w, idx = _router.moe_router_pallas(logits_p, top_k, block_t=bt,
                                       interpret=use_interpret())
    return (w[:T], idx[:T]) if pad else (w, idx)
