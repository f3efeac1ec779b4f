"""Model entry points: init / train forward / prefill / decode for every family.

Modality frontends are STUBS by assignment: ``audio`` consumes precomputed
conv-feature frames (B, S, frontend_dim) through a linear projection (the
HuBERT conv codec itself is out of scope); ``vlm`` consumes precomputed SigLIP
patch embeddings (B, P, frontend_dim) through a projector, prepended to the
text token embeddings (PaliGemma's prefix-LM layout).
"""
from __future__ import annotations

import contextlib
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from . import layers as L
from . import transformer as T

Params = Dict[str, Any]


# -- init ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 5)
    p: Params = {
        "embed": L.init_embedding(ks[0], cfg),
        "stack": T.init_stack(ks[1], cfg),
        "final_norm": L.init_norm(cfg.d_model, cfg),
    }
    if cfg.family == "ssm":
        p["ln0"] = L.init_norm(cfg.d_model, cfg)
    head = L.init_lm_head(ks[2], cfg)
    if head is not None:
        p["lm_head"] = head
    if cfg.frontend == "audio_stub":
        p["frontend"] = {"proj": L._dense_init(ks[3], (cfg.frontend_dim, cfg.d_model),
                                               jnp.dtype(cfg.param_dtype))}
    elif cfg.frontend == "vision_stub":
        p["frontend"] = {"proj": L._dense_init(ks[3], (cfg.frontend_dim, cfg.d_model),
                                               jnp.dtype(cfg.param_dtype))}
    return p


def param_count(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


# -- input embedding ---------------------------------------------------------------

def _embed_tokens(params: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = L.embed_tokens(params["embed"], tokens, cfg)
    if cfg.family == "ssm":  # RWKV normalises the embeddings before block 0 (ln0)
        x = L.apply_norm(params["ln0"], x, cfg)
    return x


def _embed_inputs(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig) -> jax.Array:
    from ..dist.sharding import constrain
    adt = jnp.dtype(cfg.activation_dtype)
    if cfg.frontend == "audio_stub":
        x = batch["features"].astype(adt) @ params["frontend"]["proj"].astype(adt)
    elif cfg.frontend == "vision_stub":
        img = batch["patch_embeds"].astype(adt) @ params["frontend"]["proj"].astype(adt)
        txt = L.embed_tokens(params["embed"], batch["tokens"], cfg)
        x = jnp.concatenate([img, txt], axis=1)
    else:
        x = _embed_tokens(params, batch["tokens"], cfg)
    return constrain(x)


def _logits(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = L.apply_norm(params["final_norm"], x, cfg)
    return L.lm_logits(params["embed"], params.get("lm_head"), x, cfg)


# -- losses -------------------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Token-mean CE in fp32. Returns (loss, accuracy)."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32), labels[..., None],
                               axis=-1)[..., 0]
    nll = lse - gold
    correct = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    return (nll * mask).sum() / denom, (correct * mask).sum() / denom


# -- forward passes -----------------------------------------------------------------

def _precision(cfg: ModelConfig):
    """The model's matmuls at ``cfg.matmul_precision`` where it is set (their
    gradients inherit it); JAX's default (one bfloat16 pass per float32
    matmul on a TPU) otherwise.  Training in float32 on a TPU needs
    "highest" where the first gradient must be checked against a float32
    reference: at the default it reads as far off as bfloat16 compute."""
    if cfg.matmul_precision is None:
        return contextlib.nullcontext()
    return jax.default_matmul_precision(cfg.matmul_precision)


def forward_train(params: Params, batch: Dict[str, jax.Array],
                  cfg: ModelConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Returns (total_loss, metrics).  batch needs family-appropriate inputs
    plus "labels" (and optional "loss_mask")."""
    with _precision(cfg):
        x = _embed_inputs(params, batch, cfg)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        x, _, aux = T.apply_stack(params["stack"], cfg, x, positions, None, mode="train")
        logits = _logits(params, x, cfg)

        labels = batch["labels"]
        mask = batch.get("loss_mask")
        if cfg.frontend == "vision_stub":
            # loss only over the text region (after the image prefix)
            P = batch["patch_embeds"].shape[1]
            logits = logits[:, P:]
        ce, acc = cross_entropy(logits, labels, mask)
        aux_coef = cfg.moe.aux_loss_coef if cfg.moe else 0.0
        loss = ce + aux_coef * aux
        return loss, {"loss": ce, "aux_loss": aux, "accuracy": acc}


def forward_encode(params: Params, batch: Dict[str, jax.Array],
                   cfg: ModelConfig) -> jax.Array:
    """Encoder-only / no-cache forward returning full logits (hubert prefill)."""
    with _precision(cfg):
        x = _embed_inputs(params, batch, cfg)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        x, _, _ = T.apply_stack(params["stack"], cfg, x, positions, None, mode="train")
        return _logits(params, x, cfg)


def prefill(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            max_len: int) -> Tuple[jax.Array, List[Any]]:
    """Process a prompt, fill caches sized ``max_len``; return (last-token
    logits (B, V), caches)."""
    with _precision(cfg):
        x = _embed_inputs(params, batch, cfg)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        caches = T.init_caches(cfg, B, max_len)
        x, caches, _ = T.apply_stack(params["stack"], cfg, x, positions, caches,
                                     mode="prefill")
        logits = _logits(params, x[:, -1:], cfg)
    return logits[:, 0], caches


def decode_step(params: Params, caches: List[Any], tokens: jax.Array,
                pos: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, List[Any]]:
    """One synchronized decode step.  tokens (B,) int32, pos scalar int32.
    Returns (logits (B, V), updated caches)."""
    with _precision(cfg):
        x = _embed_tokens(params, tokens[:, None], cfg)
        B = x.shape[0]
        positions = jnp.broadcast_to(pos.astype(jnp.int32)[None, None], (B, 1))
        x, caches, _ = T.apply_stack(params["stack"], cfg, x, positions, caches,
                                     mode="decode")
        logits = _logits(params, x, cfg)
    return logits[:, 0], caches
