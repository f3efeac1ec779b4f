"""Optimizers and LR schedules, from scratch (no optax in this environment).

Pytree-structured states; ``Optimizer`` is an (init, update) pair like optax —
``update`` returns (new_params, new_state) directly (fused apply) to avoid an
extra tree round-trip.  AdamW keeps fp32 master moments regardless of param
dtype (mixed-precision training convention).

Every scalar (learning rate, schedule lengths, betas, momentum, weight decay,
clip norm) may be a Python number or a float32 array traced by a jitted step;
only the family, ``moment_dtype``, ``nesterov`` and clipping on or off
(``grad_clip is None``) change the program.  ``make_optimizer`` builds an
optimizer from one pytree of those scalars (``optimizer_hypers``), so a step
that takes the pytree as an argument serves every trial of its shape.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "Optimizer", "adamw", "sgd", "global_norm", "clip_by_global_norm",
    "cosine_schedule", "linear_warmup_cosine", "constant_schedule",
    "make_optimizer", "optimizer_hypers",
]

Schedule = Callable[[jax.Array], jax.Array]


def constant_schedule(lr: float) -> Schedule:
    return lambda step: jnp.asarray(lr, jnp.float32)


def cosine_schedule(lr: float, total_steps: int, final_frac: float = 0.1) -> Schedule:
    def fn(step):
        t = jnp.minimum(step.astype(jnp.float32) / jnp.maximum(total_steps, 1), 1.0)
        cos = 0.5 * (1 + jnp.cos(math.pi * t))
        return lr * (final_frac + (1 - final_frac) * cos)
    return fn


def linear_warmup_cosine(lr: float, warmup: int, total_steps: int,
                         final_frac: float = 0.1) -> Schedule:
    cos = cosine_schedule(lr, total_steps - warmup, final_frac)
    def fn(step):
        s = step.astype(jnp.float32)
        warm = lr * s / jnp.maximum(warmup, 1)
        return jnp.where(s < warmup, warm, cos(jnp.maximum(s - warmup, 0)))
    return fn


def global_norm(tree: Any) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def clip_by_global_norm(tree: Any, max_norm: float) -> Tuple[Any, jax.Array]:
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree_util.tree_map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                                  tree), norm


@dataclass(frozen=True)
class Optimizer:
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any], Tuple[Any, Any]]  # (grads, state, params) -> (params, state)


def adamw(
    schedule: Schedule | float,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    weight_decay: float = 0.1,
    grad_clip: Optional[float] = 1.0,
    moment_dtype: Any = jnp.float32,
) -> Optimizer:
    """``moment_dtype=bf16`` halves optimizer-state memory (8-bit-Adam-style
    trade, coarser: moments round-trip through bf16 between steps)."""
    sched = constant_schedule(schedule) if isinstance(schedule, (int, float)) else schedule
    mdt = jnp.dtype(moment_dtype)

    def init(params):
        zeros = lambda p: jnp.zeros(p.shape, mdt)
        return {
            "step": jnp.zeros((), jnp.int32),
            "m": jax.tree_util.tree_map(zeros, params),
            "v": jax.tree_util.tree_map(zeros, params),
        }

    def update(grads, state, params):
        if grad_clip is not None:
            grads, _ = clip_by_global_norm(grads, grad_clip)
        step = state["step"] + 1
        lr = sched(step)
        c1 = 1.0 - b1 ** step.astype(jnp.float32)
        c2 = 1.0 - b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
            v32 = b2 * v.astype(jnp.float32) + (1 - b2) * g32 * g32
            mhat = m32 / c1
            vhat = v32 / c2
            delta = mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p.astype(jnp.float32)
            return ((p.astype(jnp.float32) - lr * delta).astype(p.dtype),
                    m32.astype(mdt), v32.astype(mdt))

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state["m"])
        flat_v = treedef.flatten_up_to(state["v"])
        out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = treedef.unflatten([o[0] for o in out])
        new_m = treedef.unflatten([o[1] for o in out])
        new_v = treedef.unflatten([o[2] for o in out])
        return new_p, {"step": step, "m": new_m, "v": new_v}

    return Optimizer(init=init, update=update)


def sgd(
    schedule: Schedule | float,
    momentum: float = 0.9,
    nesterov: bool = False,
    weight_decay: float = 0.0,
    grad_clip: Optional[float] = None,
) -> Optimizer:
    sched = constant_schedule(schedule) if isinstance(schedule, (int, float)) else schedule

    def init(params):
        return {
            "step": jnp.zeros((), jnp.int32),
            "mom": jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        }

    def update(grads, state, params):
        if grad_clip is not None:
            grads, _ = clip_by_global_norm(grads, grad_clip)
        step = state["step"] + 1
        lr = sched(step)

        def upd(p, g, m):
            g32 = g.astype(jnp.float32) + weight_decay * p.astype(jnp.float32)
            m = momentum * m + g32
            d = g32 + momentum * m if nesterov else m
            return (p.astype(jnp.float32) - lr * d).astype(p.dtype), m

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state["mom"])
        out = [upd(p, g, m) for p, g, m in zip(flat_p, flat_g, flat_m)]
        return (treedef.unflatten([o[0] for o in out]),
                {"step": step, "mom": treedef.unflatten([o[1] for o in out])})

    return Optimizer(init=init, update=update)


# The scalar hyperparameters and their defaults, shared by the families and
# then each family's own; a ``grad_clip`` of None turns clipping off.
_SCHEDULE_HYPERS = {"lr": 3e-4, "warmup": 10}
_FAMILY_HYPERS = {
    "adamw": {"b1": 0.9, "b2": 0.95, "weight_decay": 0.1, "grad_clip": 1.0},
    "sgd": {"momentum": 0.9, "weight_decay": 0.0, "grad_clip": None},
}


def optimizer_hypers(family: str, total_steps: int,
                     config: Optional[Mapping[str, Any]] = None) -> Dict[str, float]:
    """The scalar hyperparameters of ``family`` as one flat dict of floats:
    ``config``'s values where it names them (other keys are ignored), else
    the defaults.  ``warmup`` is a whole number of steps.  A ``grad_clip``
    of None is left out, so the dict's keys say whether the step clips."""
    if family not in _FAMILY_HYPERS:
        raise ValueError(f"unknown optimizer {family!r}")
    config = config or {}
    scalars = {k: config.get(k, d)
               for k, d in {**_SCHEDULE_HYPERS, **_FAMILY_HYPERS[family]}.items()}
    scalars["warmup"] = int(scalars["warmup"])
    scalars["total_steps"] = int(total_steps)
    return {k: float(v) for k, v in scalars.items() if v is not None}


def make_optimizer(family: str, hypers: Dict[str, Any],
                   moment_dtype: Any = jnp.float32) -> Optimizer:
    """The optimizer of ``family`` over the scalars of ``hypers`` (floats, or
    float32 arrays traced by the step that calls this): a linear-warmup
    cosine schedule, clipping iff ``hypers`` holds ``grad_clip``."""
    schedule = linear_warmup_cosine(hypers["lr"], hypers["warmup"],
                                    hypers["total_steps"])
    if family == "adamw":
        return adamw(schedule, b1=hypers["b1"], b2=hypers["b2"],
                     weight_decay=hypers["weight_decay"],
                     grad_clip=hypers.get("grad_clip"),
                     moment_dtype=moment_dtype)
    if family == "sgd":
        return sgd(schedule, momentum=hypers["momentum"],
                   weight_decay=hypers["weight_decay"],
                   grad_clip=hypers.get("grad_clip"))
    raise ValueError(f"unknown optimizer {family!r}")
