"""Plain reference for the training cells, and the comparison that decides
``correct``.

Nothing here imports the program.  The reference of a configuration lives
beside its file of sizes (``bench/configs/<name>.py``) and gives
``init_params(key, model)``, which draws the same initial weights from the
same seed as the program's recipe, and ``loss(params, batch, model, dtype)``,
its forward pass and token-mean cross entropy.  This module adds the data
stream, AdamW and the readings.

Readings of one run, each over the first three train steps of one trial:

- ``losses``: the loss of steps 1, 2 and 3;
- ``grad``: per leaf (per layer, for stacked layer parameters) the first
  gradient as the optimizer gets it, after clipping, as a host array;
- ``change``: per leaf the norm of the parameters' change over the three steps.

``compare`` turns two sets of readings into the numbers held against limits.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

STEPS = 3
# Leaves whose reference gradient is under this share of the median leaf's
# move by round-off alone under Adam; they are left out of ``change_gap``.
NEGLIGIBLE_GRAD = 1e-3


# -- data: the program's synthetic LM stream, as the benchmark generates it -----

def synthetic_batch(seed: int, step: int, batch: int, seq_len: int, vocab: int,
                    noise: float = 0.1) -> Dict[str, np.ndarray]:
    """Batch ``step`` of the seeded Markov token stream: token t+1 follows
    token t through a seeded permutation, or is uniform with prob. ``noise``;
    labels are the tokens shifted left by one (wrapping)."""
    perm = np.random.default_rng(seed).permutation(vocab)
    rng = np.random.default_rng((seed * 1_000_003 + step) * 65_537)
    toks = np.empty((batch, seq_len), np.int64)
    toks[:, 0] = rng.integers(0, vocab, size=batch)
    for t in range(1, seq_len):
        follow = perm[toks[:, t - 1]]
        rand = rng.integers(0, vocab, size=batch)
        use = rng.random(batch) < noise
        toks[:, t] = np.where(use, rand, follow)
    tokens = toks.astype(np.int32)
    return {"tokens": tokens, "labels": np.roll(tokens, -1, axis=1)}


# -- leaf norms -------------------------------------------------------------------

def leaf_norms(tree) -> Dict[str, jax.Array]:
    """Norm of each leaf; a leaf under ``stack`` holds one layer per row and
    gets one norm per layer."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        x = jnp.asarray(x, jnp.float32)
        axes = tuple(range(1, x.ndim)) if "stack" in name and x.ndim else None
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    return out


def host_rows(tree) -> Dict[str, np.ndarray]:
    """Each leaf of ``tree`` as a float32 host array, a leaf under ``stack``
    split into one row per layer (``leaf[i]``), as ``flat`` names norms."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]:
        name = jax.tree_util.keystr(path)
        x = np.asarray(x, np.float32)
        if "stack" in name and x.ndim:
            out.update({f"{name}[{i}]": row for i, row in enumerate(x)})
        else:
            out[name] = x
    return out


def _norm(x: np.ndarray) -> float:
    x = np.ravel(x).astype(np.float64)
    return float(np.sqrt(np.dot(x, x)))


def flat(norms: Dict[str, Any]) -> Dict[str, float]:
    """``{leaf: norm}`` with per-layer rows split into ``leaf[i]``."""
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim:
            out.update({f"{name}[{i}]": float(x) for i, x in enumerate(v)})
        else:
            out[name] = float(v)
    return out


def change_norms_fn(init_params: Callable, model: Dict):
    """Jitted ``(params, key) -> leaf norms of params - init(key)``."""
    def fn(params, key):
        p0 = init_params(key, model)
        return leaf_norms(jax.tree_util.tree_map(
            lambda a, b: jnp.asarray(a, jnp.float32) - b, params, p0))
    return jax.jit(fn)


# -- optimizer ----------------------------------------------------------------------

def lr_at(step: int, hp: Dict) -> float:
    """Linear warmup to ``lr`` over ``warmup`` steps, then cosine to a tenth
    of it at ``total_steps``; ``step`` counts from 1."""
    lr, warmup, total = hp["lr"], hp["warmup"], hp["total_steps"]
    if step < warmup:
        return lr * step / max(warmup, 1)
    t = min((step - warmup) / max(total - warmup, 1), 1.0)
    return lr * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * t)))


def make_ref_step(mod, model: Dict, hp: Dict):
    """One AdamW step of the reference: (params, m, v, batch, t, lr) ->
    (params, m, v, loss)."""
    b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
    wd, clip = hp["weight_decay"], hp["grad_clip"]

    def step(params, m, v, batch, t, lr):
        loss, grads = jax.value_and_grad(mod.loss)(params, batch, model)
        leaves = jax.tree_util.tree_leaves(grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        params = jax.tree_util.tree_map(
            lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * p),
            params, m, v)
        return params, m, v, loss

    return jax.jit(step, donate_argnums=(0, 1, 2))


def half_of(batch: Dict) -> Dict:
    """The first half of a batch's rows, or of its positions where it holds
    one row: the fault of half the batch left out."""
    rows = batch["tokens"].shape[0]
    if rows > 1:
        return {k: x[: rows // 2] for k, x in batch.items()}
    return {k: x[:, : x.shape[1] // 2] for k, x in batch.items()}


def run_reference(mod, model: Dict, hp: Dict, init_seed: int, batches: List,
                  half_batch: bool = False, precision: str = "highest") -> Dict:
    """Readings of the reference over ``STEPS`` steps from the seed's weights,
    in float32 under the ``precision`` of matmuls.  The first gradient is
    Adam's first moment after step 1 over ``1 - b1``, read back to the host
    before step 2.  ``half_batch`` leaves half of every batch out (a planted
    fault)."""
    with jax.default_matmul_precision(precision):
        key = jax.random.key(init_seed)
        params = jax.jit(mod.init_params, static_argnums=1)(key, Frozen(model))
        zeros = lambda p: jnp.zeros_like(p)
        m = jax.tree_util.tree_map(zeros, params)
        v = jax.tree_util.tree_map(zeros, params)
        step = make_ref_step(mod, Frozen(model), hp)
        losses, grad = [], None
        for i, b in enumerate(batches[:STEPS]):
            if half_batch:
                b = half_of(b)
            t = i + 1
            params, m, v, loss = step(params, m, v, b, t, lr_at(t, hp))
            losses.append(float(loss))
            if grad is None:
                grad = {n: g / (1.0 - hp["b1"]) for n, g in host_rows(m).items()}
        del m, v
        change = flat(jax.device_get(
            change_norms_fn(mod.init_params, Frozen(model))(params, key)))
    return {"losses": losses, "grad": grad, "change": change}


class Frozen(dict):
    """A hashable dict, so a configuration can be a static jit argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


# -- comparison ------------------------------------------------------------------------

def leaf_gaps(got: Dict[str, float], want: Dict[str, float], keep=None) -> Dict[str, float]:
    """Per leaf, the gap between the two norms over the larger of the
    reference leaf's norm and the median leaf's."""
    med = float(np.median(list(want.values())))
    names = [n for n in want if keep is None or keep(n)]
    missing = [n for n in names if n not in got]
    if missing:
        raise ValueError(f"program readings lack leaves {missing[:3]}")
    return {n: abs(got[n] - want[n]) / max(want[n], med) for n in names}


def leaf_errors(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]) -> Dict[str, float]:
    """Per leaf, the norm of the two arrays' difference over the larger of
    the reference leaf's norm and the median leaf's."""
    norms = {n: _norm(w) for n, w in want.items()}
    med = float(np.median(list(norms.values())))
    missing = [n for n in want if n not in got]
    if missing:
        raise ValueError(f"program readings lack leaves {missing[:3]}")
    return {n: _norm(got[n] - w) / max(norms[n], med) for n, w in want.items()}


def grad_norms(readings: Dict) -> Dict[str, float]:
    return {n: _norm(g) for n, g in readings["grad"].items()}


def compare(got: Dict, want: Dict) -> Dict[str, float]:
    """The numbers a configuration's limits may hold: the widest loss gap of
    the three steps (``loss_gap``) and that of step 1 (``loss1_gap``); by the
    worst leaf, the gaps of the first gradient's norm (``grad_gap``), the
    norm of the first gradient's error (``grad_err``: the norm of the
    difference of the two gradients, where ``grad_gap`` compares their
    norms), and the gap of the three steps' change (``change_gap``, leaves
    that the reference's gradient leaves still excluded); and the median
    leaf's first-gradient error and change gap (``grad_err_median``,
    ``change_gap_median``)."""
    want_norms = grad_norms(want)
    med_grad = float(np.median(list(want_norms.values())))
    moving = lambda n: want_norms.get(n, 0.0) >= NEGLIGIBLE_GRAD * med_grad
    grad = leaf_gaps(grad_norms(got), want_norms)
    change = leaf_gaps(got["change"], want["change"], keep=moving)
    errors = leaf_errors(got["grad"], want["grad"])
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(got["losses"], want["losses"])),
        "loss1_gap": abs(got["losses"][0] - want["losses"][0]),
        "grad_gap": max(grad.values()),
        "grad_err": max(errors.values()),
        "grad_err_median": float(np.median(list(errors.values()))),
        "change_gap": max(change.values()),
        "change_gap_median": float(np.median(list(change.values()))),
    }


def worst_leaves(got: Dict, want: Dict, n: int = 3) -> Dict[str, list]:
    """The ``n`` leaves with the widest gaps, ``[leaf, gap, got, want]``:
    of the first gradient's error, and of the change's norm."""
    want_norms = grad_norms(want)
    errs = leaf_errors(got["grad"], want["grad"])
    top = sorted(errs, key=errs.get, reverse=True)[:n]
    out = {"grad_err": [[k, errs[k], want_norms[k]] for k in top]}
    gaps = leaf_gaps(got["change"], want["change"])
    top = sorted(gaps, key=gaps.get, reverse=True)[:n]
    out["change"] = [[k, gaps[k], got["change"][k], want["change"][k]] for k in top]
    return out
