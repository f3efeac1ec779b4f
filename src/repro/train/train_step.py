"""Train / eval steps: loss + grad + optimizer apply, with optional
gradient-accumulation microbatching.  Pure functions of (TrainState, batch),
or of (TrainState, batch, hypers) with the optimizer's scalars as an
argument: that form is what the dry-run lowers on the production mesh, and
what every Tune trial of one shape shares, jit-compiled once per process
(``shared_train_step``).
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models import ModelConfig, forward_train, init_params
from .optimizer import Optimizer, global_norm, make_optimizer

__all__ = ["TrainState", "make_train_state", "make_train_step", "make_eval_step",
           "make_hyper_train_step", "shared_train_step", "step_cache_info",
           "step_cache_clear", "StepCacheInfo"]


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


def make_hyper_train_step(cfg: ModelConfig, family: str = "adamw",
                          microbatch: int = 0, moment_dtype: Any = jnp.float32,
                          factory: Optional[Callable] = None):
    """Returns train_step(state, batch, hypers) -> (state, metrics).

    The optimizer of ``family`` is built inside the trace from ``hypers``, a
    flat dict of float32 scalars (``optimizer_hypers``), so one program
    serves every value of them.  ``factory(cfg, opt, microbatch)`` makes the
    (state, batch) step; ``make_train_step`` by default.
    """
    factory = factory or make_train_step

    def train_step(state: TrainState, batch: Dict[str, jax.Array],
                   hypers: Dict[str, jax.Array]) -> Tuple[TrainState, Dict]:
        opt = make_optimizer(family, hypers, moment_dtype)
        return factory(cfg, opt, microbatch)(state, batch)

    return train_step


class StepCacheInfo(NamedTuple):
    hits: int
    misses: int
    maxsize: int
    currsize: int


class _StepCache:
    """Least-recently-used map of key -> jitted step, safe across the
    threads of a concurrent executor."""

    def __init__(self, maxsize: int):
        self.maxsize = maxsize
        self._fns: "OrderedDict[tuple, Any]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = self.misses = 0

    def get(self, key: tuple, build: Callable[[], Any]) -> Tuple[Any, bool]:
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self._fns.move_to_end(key)
                self.hits += 1
                return fn, True
            fn = self._fns[key] = build()
            if len(self._fns) > self.maxsize:
                self._fns.popitem(last=False)
            self.misses += 1
            return fn, False

    def info(self) -> StepCacheInfo:
        with self._lock:
            return StepCacheInfo(self.hits, self.misses, self.maxsize,
                                 len(self._fns))

    def clear(self) -> None:
        with self._lock:
            self._fns.clear()
            self.hits = self.misses = 0


_STEP_CACHE = _StepCache(maxsize=8)


def shared_train_step(cfg: ModelConfig, family: str, hypers: Dict[str, Any],
                      microbatch: int = 0,
                      factory: Optional[Callable] = None) -> Tuple[Any, bool]:
    """(jit of ``make_hyper_train_step``, whether the lookup hit): one jitted
    step for every caller with the same key, so trials that differ only in
    their hyperparameters' values compile once per process.

    The key is the model config, the family, the names in ``hypers`` (which
    say whether the step clips), the microbatch, and the functions the trace
    reads: the step factory and this module's ``forward_train``, so swapping
    either builds a new program instead of reusing one traced from the old.
    JAX's own cache keys the rest: shapes, dtypes and the committed device.
    """
    factory = factory or make_train_step
    key = (cfg, family, tuple(sorted(hypers)), int(microbatch), factory,
           forward_train)
    return _STEP_CACHE.get(key, lambda: jax.jit(make_hyper_train_step(
        cfg, family, microbatch, factory=factory)))


def step_cache_info() -> StepCacheInfo:
    """Hits, misses, maxsize and current size of ``shared_train_step``'s
    cache, as ``functools`` reports a cache."""
    return _STEP_CACHE.info()


def step_cache_clear() -> None:
    """Empty ``shared_train_step``'s cache and zero its counts."""
    _STEP_CACHE.clear()
