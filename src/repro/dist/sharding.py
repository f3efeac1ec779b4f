"""Rule-based PartitionSpec engine (DESIGN.md §3).

Parameters are plain nested dicts, so placement is decided from the *tree
path* of each leaf: ``spec_for(path, shape, mesh)`` looks the leaf name up in
a table of named rule templates and resolves abstract roles onto concrete
mesh axes.

Roles (resolved per active strategy, see ``sharding_strategy``):

* ``"fsdp"``  — shard over the data axes (all mesh axes except ``model``),
  expressed as an axis *tuple* so multi-pod meshes map to ``("pod","data")``.
* ``"tp"``    — shard over the ``model`` axis (tensor parallelism).
* ``"expert"``— shard over the ``model`` axis (expert parallelism; MoE layers
  trade TP for EP, so both roles target the same axis).
* ``None``    — replicate this dim.

A rule template names roles for the *trailing* dims of a leaf; leading dims
(the scan-stacked layer axis) replicate.  Each leaf carries an ordered list
of templates; the first whose every sharded dim is divisible by its axes'
total size wins.  If none fits, the first template is taken and the failing
dims are dropped to ``None`` individually — the "divisibility-drop" contract:
sharding degrades per-dim, it never errors and never produces an invalid
spec.

Head-aware attention rules (``_head_aware_rules``) additionally refuse to
tensor-shard q/k/v/o projections when ``n_heads`` / ``n_kv_heads`` do not
divide the model-axis size — splitting inside a head would break GQA/MQA
grouping, so such projections fall back to FSDP-only.

Strategies: ``fsdp_tp`` (default; FSDP over data axes + TP over model) and
``dp_only`` (model axis unused — pure data parallelism; the batch may then
also shard over the idle model axis).
"""
from __future__ import annotations

import contextlib
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = [
    "spec_for", "param_specs", "train_state_specs", "batch_specs",
    "cache_specs", "make_shardings", "constrain", "sharding_strategy",
    "activation_policy", "activations_partitioned", "STRATEGIES",
]

STRATEGIES = ("fsdp_tp", "dp_only")

_MODEL_AXIS = "model"

# -- strategy / activation-policy context ------------------------------------------

_state = {"strategy": "fsdp_tp", "act_mesh": None, "seq_parallel": False}


@contextlib.contextmanager
def sharding_strategy(name: str):
    """Select the active strategy for every spec_* call in the block."""
    if name not in STRATEGIES:
        raise ValueError(f"unknown sharding strategy {name!r}; "
                         f"choose from {STRATEGIES}")
    prev = _state["strategy"]
    _state["strategy"] = name
    try:
        yield
    finally:
        _state["strategy"] = prev


@contextlib.contextmanager
def activation_policy(mesh, seq_parallel: bool = False):
    """Enable ``constrain`` inside model code: activations traced in the block
    are pinned to batch (and optionally sequence) sharding on ``mesh``."""
    prev = (_state["act_mesh"], _state["seq_parallel"])
    _state["act_mesh"] = mesh
    _state["seq_parallel"] = bool(seq_parallel)
    try:
        yield
    finally:
        _state["act_mesh"], _state["seq_parallel"] = prev


def activations_partitioned() -> bool:
    """True while an ``activation_policy`` spreads activations over more than
    one device: the step is then partitioned by XLA, which cannot partition a
    Pallas kernel, so model code takes its jnp path."""
    mesh = _state["act_mesh"]
    return mesh is not None and mesh.size > 1


# -- mesh helpers -------------------------------------------------------------------

def _axis_sizes(mesh) -> Dict[str, int]:
    return dict(mesh.shape)


def _data_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != _MODEL_AXIS)


def _model_size(mesh) -> int:
    return _axis_sizes(mesh).get(_MODEL_AXIS, 1)


def _resolve_role(role: Optional[str], mesh):
    """Map an abstract role to a PartitionSpec entry under the active strategy."""
    strategy = _state["strategy"]
    if role is None:
        return None
    if role == "fsdp":
        axes = _data_axes(mesh)
        return axes if axes else None
    if role in ("tp", "expert"):
        if strategy == "dp_only" or _MODEL_AXIS not in mesh.axis_names:
            return None
        return _MODEL_AXIS
    raise ValueError(f"unknown sharding role {role!r}")


def _entry_size(entry, sizes: Dict[str, int]) -> int:
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        return math.prod(sizes.get(a, 1) for a in entry)
    return sizes.get(entry, 1)


# -- rule tables --------------------------------------------------------------------

# name -> ordered fallback templates (roles for trailing dims).  No template
# replicates everything: when none fits, the divisibility-drop fallback takes
# the FIRST template and nulls failing dims individually, which preserves any
# dim that still divides (e.g. TP survives an odd fan-out).
_RULES: Dict[str, List[Tuple[Optional[str], ...]]] = {
    # embeddings: vocab over model first, fall back to feature-only FSDP
    "tok": [("tp", "fsdp"), (None, "fsdp")],
    # untied LM head (d_model, vocab)
    "w": [("fsdp", "tp"), ("fsdp", None)],
    # gated MLP
    "w_gate": [("fsdp", "tp"), ("fsdp", None)],
    "w_up": [("fsdp", "tp"), ("fsdp", None)],
    "w_down": [("tp", "fsdp"), (None, "fsdp")],
    # plain MLP
    "w_in": [("fsdp", "tp"), ("fsdp", None)],
    "w_out": [("tp", "fsdp"), (None, "fsdp")],
    # MoE router (d_model, n_experts)
    "router": [("fsdp", None)],
    # frontend projections
    "proj": [("fsdp", "tp"), ("fsdp", None)],
}

# expert-parallel overrides when "experts" appears on the path:
# (n_experts, d_model, d_expert) for w_gate/w_up, (n_experts, d_expert, d_model)
# for w_down — experts over the model axis, fan-in FSDP over data.
_EXPERT_RULES: Dict[str, List[Tuple[Optional[str], ...]]] = {
    "w_gate": [("expert", "fsdp", None), (None, "fsdp", None)],
    "w_up": [("expert", "fsdp", None), (None, "fsdp", None)],
    "w_down": [("expert", None, "fsdp"), (None, None, "fsdp")],
}

_ATTN_NAMES = ("wq", "wk", "wv", "wo")


def _head_aware_rules(name: str, path_keys: Sequence[str], cfg,
                      mesh) -> List[Tuple[Optional[str], ...]]:
    """Templates for attention projections, refusing TP when heads don't
    divide the model axis (splitting inside a head breaks GQA grouping)."""
    msize = _model_size(mesh)
    if name in ("wq", "wo"):
        heads = cfg.n_heads
    else:  # wk / wv
        heads = cfg.n_kv_heads or cfg.n_heads
    splittable = msize <= 1 or heads % msize == 0
    if name == "wo":  # (n_heads*hd, d_model): heads on the fan-in dim
        return [("tp", "fsdp")] if splittable else [(None, "fsdp")]
    return [("fsdp", "tp")] if splittable else [("fsdp", None)]


def _path_keys(path: Sequence[Any]) -> List[str]:
    keys = []
    for k in path:
        if hasattr(k, "key"):
            keys.append(str(k.key))
        elif hasattr(k, "name"):
            keys.append(str(k.name))
        elif hasattr(k, "idx"):
            keys.append(str(k.idx))
        else:
            keys.append(str(k))
    return keys


def _rules_for(keys: List[str], shape: Tuple[int, ...], cfg,
               mesh) -> List[Tuple[Optional[str], ...]]:
    name = keys[-1] if keys else ""
    if len(shape) <= 1:  # scalars, norm scales, biases: replicate
        return [()]
    if "experts" in keys and name in _EXPERT_RULES:
        return _EXPERT_RULES[name]
    if name in _ATTN_NAMES and cfg is not None:
        return _head_aware_rules(name, keys, cfg, mesh)
    if name in _ATTN_NAMES:  # no cfg: assume divisible
        return [("tp", "fsdp")] if name == "wo" else [("fsdp", "tp")]
    if name in _RULES:
        return _RULES[name]
    # unknown >=2-dim leaf (recurrent-block params etc.): generic matmul rule
    return [("fsdp", "tp"), ("fsdp", None)]


def spec_for(path: Sequence[Any], shape: Tuple[int, ...], mesh,
             cfg=None) -> P:
    """PartitionSpec for one leaf, by path-based rule lookup + divisibility
    fallback.  ``path`` is a jax key path (or anything with .key/.name)."""
    keys = _path_keys(path)
    shape = tuple(shape)
    if not shape:
        return P()
    sizes = _axis_sizes(mesh)
    templates = _rules_for(keys, shape, cfg, mesh)

    def resolve(rule):
        """Roles for trailing dims -> full per-dim entries, or None if a
        sharded dim is not divisible."""
        entries: List[Any] = [None] * (len(shape) - len(rule))
        entries += [_resolve_role(r, mesh) for r in rule]
        for dim, entry in enumerate(entries):
            if entry is not None and shape[dim] % _entry_size(entry, sizes):
                return None
        return entries

    chosen = None
    for rule in templates:
        if len(rule) > len(shape):
            continue
        resolved = resolve(rule)
        if resolved is not None:
            chosen = resolved
            break
    if chosen is None:
        # divisibility-drop: take the first template that fits the leaf's
        # rank, null out failing dims individually
        rule = next((r for r in templates if len(r) <= len(shape)), ())
        entries = [None] * (len(shape) - len(rule))
        entries += [_resolve_role(r, mesh) for r in rule]
        chosen = [e if (e is None or shape[d] % _entry_size(e, sizes) == 0)
                  else None for d, e in enumerate(entries)]
    return P(*chosen)


# -- tree-level spec builders -------------------------------------------------------

def param_specs(params: Any, mesh, cfg=None) -> Any:
    """PartitionSpec tree mirroring a parameter pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec_for(path, leaf.shape, mesh, cfg), params)


def train_state_specs(state: Any, mesh, cfg=None) -> Any:
    """Specs for a full TrainState (params + optimizer moments + counters).

    Optimizer moments mirror the param tree under an ``m``/``v``/``mom``
    prefix, so the same path rules apply; scalar counters replicate.
    """
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec_for(path, getattr(leaf, "shape", ()), mesh, cfg),
        state)


def _batch_axis_candidates(mesh) -> List[Tuple[str, ...]]:
    """Ordered axis tuples to try for the batch dim: the full data-parallel
    tuple first, then right-trimmed prefixes (the "prefix fallback")."""
    axes = [a for a in _data_axes(mesh) if _axis_sizes(mesh).get(a, 1) > 1]
    if _state["strategy"] == "dp_only" and _model_size(mesh) > 1:
        axes = axes + [_MODEL_AXIS]  # model axis is idle: use it for DP
    cands = []
    while axes:
        cands.append(tuple(axes))
        axes = axes[:-1]
    cands.append(())
    return cands


def _batch_dim_entry(n: int, mesh):
    sizes = _axis_sizes(mesh)
    for cand in _batch_axis_candidates(mesh):
        if not cand:
            return None
        if n % math.prod(sizes.get(a, 1) for a in cand) == 0:
            return cand
    return None


def batch_specs(batch: Any, mesh) -> Any:
    """Shard dim 0 (the global batch) over the data axes; replicate the rest.
    Axes of size 1 are omitted (no sharding benefit on a trivial mesh)."""

    def one(leaf) -> P:
        shape = tuple(getattr(leaf, "shape", ()))
        if not shape:
            return P()
        return P(_batch_dim_entry(shape[0], mesh), *([None] * (len(shape) - 1)))

    return jax.tree_util.tree_map(one, batch)


def cache_specs(caches: Any, mesh, global_batch: int) -> Any:
    """Decode-cache specs: shard the batch dim over the data axes.

    Cache leaves are segment-stacked, so the batch dim (when a leaf has one)
    is always dim 1: (n_layers, B, cap, K, hd) for k/v, (n_layers, B, ...)
    for recurrent states.  ``global_batch`` is required to match as a
    cross-check — layer-stacking means dim sizes alone are ambiguous (a
    position ring (n_layers, cap) could collide).  ``kpos`` rings carry no
    batch dim and replicate by name.
    """

    def one(path, leaf) -> P:
        shape = tuple(getattr(leaf, "shape", ()))
        if not shape:
            return P()
        entries: List[Any] = [None] * len(shape)
        name = _path_keys(path)[-1] if path else ""
        if name != "kpos" and len(shape) >= 2 and shape[1] == global_batch:
            entries[1] = _batch_dim_entry(shape[1], mesh)
        return P(*entries)

    return jax.tree_util.tree_map_with_path(one, caches)


def make_shardings(specs: Any, mesh) -> Any:
    """PartitionSpec tree -> NamedSharding tree on ``mesh``."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


# -- in-model activation constraints ------------------------------------------------

def constrain(x: jax.Array) -> jax.Array:
    """Pin an activation's sharding under the ambient ``activation_policy``.

    No policy active -> identity, so model code is unconditionally
    instrumented and single-device tests pay nothing.  Batch dim shards over
    the data axes; the sequence dim additionally shards over ``model`` when
    the policy enables sequence parallelism — each only if divisible.
    """
    mesh = _state["act_mesh"]
    if mesh is None:
        return x
    shape = x.shape
    if not shape:
        return x
    entries: List[Any] = [_batch_dim_entry(shape[0], mesh)]
    entries += [None] * (len(shape) - 1)
    if (_state["seq_parallel"] and len(shape) >= 2
            and _state["strategy"] != "dp_only"
            and _model_size(mesh) > 1 and shape[1] % _model_size(mesh) == 0):
        entries[1] = _MODEL_AXIS
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*entries)))
