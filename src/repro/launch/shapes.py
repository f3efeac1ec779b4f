"""Assigned input shapes and per-(arch, shape) input specs.

``input_specs`` returns ShapeDtypeStruct stand-ins for every model input — the
dry-run lowers against these (weak-type-correct, shardable, no allocation).

Applicability (DESIGN.md §4):
  - encoder-only archs (hubert) have no decode step -> decode shapes skipped;
    its ``prefill_32k`` is the encoder forward.
  - ``long_500k`` requires sub-quadratic decode state: SSM / hybrid / SWA only.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models import ModelConfig
from ..models import transformer as T

__all__ = ["ShapeSpec", "SHAPES", "applicable", "skip_reason", "input_specs",
           "dryrun_config"]


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str        # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524_288, 1),
}


def skip_reason(cfg: ModelConfig, shape: ShapeSpec) -> Optional[str]:
    if shape.kind == "decode":
        if not cfg.supports_decode:
            return "encoder-only: no autoregressive decode"
        if shape.seq_len > 100_000 and not cfg.supports_long_context:
            return "full attention without sub-quadratic variant: long-context skipped"
    return None


def applicable(cfg: ModelConfig, shape: ShapeSpec) -> bool:
    return skip_reason(cfg, shape) is None


def dryrun_config(cfg: ModelConfig) -> ModelConfig:
    """bf16 params/activations, per-layer remat.

    remat=True for every arch at production sequence lengths: per-layer
    activation checkpointing is the standard 4k-training memory policy (the
    §Perf log quantifies its compute-vs-memory trade)."""
    return dataclasses.replace(
        cfg, param_dtype="bfloat16", activation_dtype="bfloat16",
        remat=True)


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def _batch_structs(cfg: ModelConfig, B: int, S: int, with_labels: bool) -> Dict[str, Any]:
    adt = cfg.activation_dtype
    if cfg.frontend == "audio_stub":
        batch = {"features": _sds((B, S, cfg.frontend_dim), adt)}
        if with_labels:
            batch["labels"] = _sds((B, S), "int32")
        return batch
    if cfg.frontend == "vision_stub":
        P_ = cfg.n_prefix_embeds
        text = S - P_
        batch = {
            "patch_embeds": _sds((B, P_, cfg.frontend_dim), adt),
            "tokens": _sds((B, text), "int32"),
        }
        if with_labels:
            batch["labels"] = _sds((B, text), "int32")
        return batch
    batch = {"tokens": _sds((B, S), "int32")}
    if with_labels:
        batch["labels"] = _sds((B, S), "int32")
    return batch


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Abstract inputs for the lowered step of ``shape.kind``.

    train   -> {"batch": ...}                       (state built separately)
    prefill -> {"batch": ...}
    decode  -> {"caches": ..., "tokens": (B,), "pos": ()}
    """
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        return {"batch": _batch_structs(cfg, B, S, with_labels=True)}
    if shape.kind == "prefill":
        return {"batch": _batch_structs(cfg, B, S, with_labels=False)}
    caches = jax.eval_shape(partial(T.init_caches, cfg, B, S))
    return {
        "caches": caches,
        "tokens": _sds((B,), "int32"),
        "pos": _sds((), "int32"),
    }
