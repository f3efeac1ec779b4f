"""Train / eval steps: loss + grad + optimizer apply, with optional
gradient-accumulation microbatching.  Pure functions of (TrainState, batch) —
this is what a Tune Trainable jit-compiles per trial, and what the dry-run
lowers on the production mesh.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models import ModelConfig, forward_train, init_params
from .optimizer import Optimizer, global_norm

__all__ = ["TrainState", "make_train_state", "make_train_step", "make_eval_step"]


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array  # int32 scalar


def make_train_state(key, cfg: ModelConfig, opt: Optimizer) -> TrainState:
    params = init_params(key, cfg)
    return TrainState(params=params, opt_state=opt.init(params),
                      step=jnp.zeros((), jnp.int32))


def make_train_step(cfg: ModelConfig, opt: Optimizer, microbatch: int = 0):
    """Returns train_step(state, batch) -> (state, metrics).

    ``microbatch`` > 0 splits the per-call batch into that many accumulation
    slices along axis 0 (a lax.scan — keeps live activation memory at
    1/microbatch at the price of serialized compute).
    """

    def loss_fn(params, batch):
        # Named scopes label the device ops (backward ones carry
        # ``transpose(jvp(forward))``); they change metadata, not the program.
        with jax.named_scope("forward"):
            loss, metrics = forward_train(params, batch, cfg)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def single(params, batch):
        (loss, metrics), grads = grad_fn(params, batch)
        return loss, metrics, grads

    def accumulated(params, batch):
        def slice_batch(i):
            return jax.tree_util.tree_map(
                lambda x: x.reshape(microbatch, x.shape[0] // microbatch, *x.shape[1:])[i],
                batch)

        def body(carry, i):
            acc_grads, acc_loss, acc_metrics = carry
            loss, metrics, grads = single(params, slice_batch(i))
            acc_grads = jax.tree_util.tree_map(jnp.add, acc_grads, grads)
            acc_loss = acc_loss + loss
            acc_metrics = jax.tree_util.tree_map(jnp.add, acc_metrics, metrics)
            return (acc_grads, acc_loss, acc_metrics), None

        zero_grads = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        loss0, metrics0, grads0 = single(params, slice_batch(0))
        (grads, loss, metrics), _ = jax.lax.scan(
            body, (grads0, loss0, metrics0), jnp.arange(1, microbatch))
        inv = 1.0 / microbatch
        scale = lambda t: jax.tree_util.tree_map(lambda x: x * inv, t)
        return scale(loss), scale(metrics), scale(grads)

    def train_step(state: TrainState, batch: Dict[str, jax.Array]) -> Tuple[TrainState, Dict]:
        if microbatch and microbatch > 1:
            loss, metrics, grads = accumulated(state.params, batch)
        else:
            loss, metrics, grads = single(state.params, batch)
        with jax.named_scope("optimizer"):
            new_params, new_opt = opt.update(grads, state.opt_state, state.params)
        metrics = dict(metrics)
        metrics["grad_norm"] = global_norm(grads)
        metrics["total_loss"] = loss
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return train_step


def make_eval_step(cfg: ModelConfig):
    def eval_step(params, batch):
        loss, metrics = forward_train(params, batch, cfg)
        return metrics
    return eval_step
