"""ModelTrainable — the bridge between the model zoo and the Tune core.

One Tune *trial* = one ModelTrainable: a train step over a model config with
trial hyperparameters (lr, warmup, weight decay, optimizer choice,
microbatch, ...) pulled from ``config``.  The step is jit-compiled once per
process for every trial of the same shape (``shared_train_step``); the
trial's scalar hyperparameters are its argument, not constants in it.
Implements the full narrow-waist contract: step / save / restore /
reset_config — so every scheduler (HyperBand pause/resume, PBT clone+mutate)
works on real model training.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import numpy as np

from ..core.api import Trainable
from ..data.pipeline import DataConfig, SyntheticLMDataset
from ..launch.mesh import HW
from ..launch.roofline import analyze
from ..models import ModelConfig, param_count
from ..obs import span
from .optimizer import make_optimizer, optimizer_hypers
from .train_step import (TrainState, make_train_state, make_train_step,
                         shared_train_step)

__all__ = ["ModelTrainable", "make_model_trainable", "model_trainable_factory"]


def _slice_device(sl: Any) -> Optional[jax.Device]:
    """The device a trial on slice ``sl`` runs on; None for the default."""
    if sl is None or sl.devices is None:
        return None
    if sl.size != 1:
        raise NotImplementedError(
            f"a model trial on a slice of {sl.size} real devices needs its "
            "step sharded over the slice (ROADMAP 2.1); ModelTrainable "
            "places a trial on one device")
    return sl.devices[0]


class ModelTrainable(Trainable):
    """config keys: model_cfg (ModelConfig), lr/warmup/optimizer/... (hypers),
    batch/seq_len/steps_per_iter/total_steps/data_seed (workload).

    Hardware profile (DESIGN.md §9): after every (re)build the first reported
    result carries a one-shot ``_profile`` entry in its metrics — step-time
    decomposition (first step = compile + execute vs steady state), device
    memory, ``step_cache`` ("hit" when the trial found its shape's step
    already built in this process, "miss" when it built it), and with
    ``profile_roofline=True`` an achieved-vs-predicted
    roofline tag from ``launch/roofline.py``.  The runner pops it off the
    metric stream and publishes it as trial metadata (``trial.profile``) plus
    a PROFILE event, so it rides the existing result transport across all
    executor tiers.  Disable with ``profile=False``.

    Placement: on a slice of real devices (``config["_slice"]`` from a
    device-mode ``SlicePool``) the state, batches and step live on the
    slice's device; a virtual slice, or none, leaves them on the default
    device.  A trial spans one device only until the step is sharded over its
    slice (ROADMAP 2.1), so a wider real slice is refused."""

    def setup(self, config: Dict[str, Any]) -> None:
        self.model_cfg: ModelConfig = config["model_cfg"]
        self.batch = int(config.get("batch", 8))
        self.seq_len = int(config.get("seq_len", 128))
        self.steps_per_iter = int(config.get("steps_per_iter", 5))
        self.total_steps = int(config.get("total_steps", 1000))
        self._data = SyntheticLMDataset(DataConfig(
            global_batch=self.batch, seq_len=self.seq_len,
            vocab_size=self.model_cfg.vocab_size,
            seed=int(config.get("data_seed", 0))))
        self._global_step = 0
        self._device = _slice_device(config.get("_slice"))
        self._build(config)

    def _build(self, hp: Dict[str, Any]) -> None:
        self._configure(hp)
        seed = int(hp.get("init_seed", 0))
        with jax.default_device(self._device):
            state = make_train_state(jax.random.key(seed), self.model_cfg,
                                     self._opt)
        self.state = jax.device_put(state, self._device)
        self._arm(hp)

    def _configure(self, hp: Dict[str, Any]) -> None:
        """The trial's optimizer and its shared step.  The scalars go to the
        device once, as float32 arrays: a Python float would be weakly typed
        (another trace) and cross from the host on every call."""
        family = hp.get("optimizer", "adamw")
        hypers = optimizer_hypers(family, self.total_steps, hp)
        self._opt = make_optimizer(family, hypers)
        self._shared_step, hit = shared_train_step(
            self.model_cfg, family, hypers,
            microbatch=int(hp.get("microbatch", 0)), factory=make_train_step)
        self._step_cache = "hit" if hit else "miss"
        self._hypers = jax.device_put(
            {k: np.float32(v) for k, v in hypers.items()}, self._device)

    def _arm(self, hp: Dict[str, Any]) -> None:
        """Bind the step function to this trial's scalars and re-arm the
        one-shot profile."""
        self._pending_profile = bool(hp.get("profile", True))
        self._compiled = None
        self._compile_s: Optional[float] = None
        run = self._shared_step
        if hp.get("profile_roofline"):
            # AOT compile: one explicit lower+compile that doubles as the
            # step function and hands the roofline walk the post-fusion HLO
            # it needs — a traced-only jit exposes StableHLO, which the cost
            # regexes cannot parse.
            batch = self._batch(self._global_step)
            p0 = time.perf_counter()
            self._compiled = run.lower(self.state, batch, self._hypers).compile()
            self._compile_s = time.perf_counter() - p0
            run = self._compiled
        hypers = self._hypers
        self._step_fn = lambda state, batch: run(state, batch, hypers)

    def _batch(self, step: int) -> Dict[str, jax.Array]:
        return jax.device_put(self._data.batch_at(step), self._device)

    # -- narrow-waist contract ---------------------------------------------------
    def step(self) -> Dict[str, Any]:
        t0 = time.time()
        step_times = [] if self._pending_profile else None
        for _ in range(self.steps_per_iter):
            with span("data", cat="data", step=self._global_step):
                batch = self._batch(self._global_step)
            if step_times is None:
                self.state, metrics = self._step_fn(self.state, batch)
            else:
                # Profiled iteration only: synchronous per-step timing so the
                # first-step (compile) vs steady-state split is real, not a
                # dispatch-queue artifact.
                p0 = time.perf_counter()
                self.state, metrics = self._step_fn(self.state, batch)
                jax.block_until_ready(metrics["loss"])
                step_times.append(time.perf_counter() - p0)
            self._global_step += 1
        loss = float(metrics["loss"])
        out = {
            "loss": loss,
            "accuracy": float(metrics["accuracy"]),
            "grad_norm": float(metrics["grad_norm"]),
            "step": self._global_step,
            "steps_per_s": self.steps_per_iter / max(time.time() - t0, 1e-9),
        }
        if step_times:
            self._pending_profile = False
            out["_profile"] = self._make_profile(step_times)
        return out

    def _make_profile(self, step_times) -> Dict[str, Any]:
        first = step_times[0]
        steady = min(step_times[1:]) if len(step_times) > 1 else first
        params = jax.tree_util.tree_leaves(self.state.params)
        devices = sorted({d for x in params for d in x.devices()},
                         key=lambda d: d.id)
        prof: Dict[str, Any] = {
            "first_step_s": round(first, 6),
            "steady_step_s": round(steady, 6),
            # AOT path: the measured explicit compile; jit path: the first
            # step carries the compile, so the split is the estimate.
            "compile_s": round(self._compile_s if self._compile_s is not None
                               else max(0.0, first - steady), 6),
            "param_count": int(param_count(self.state.params)),
            "batch": self.batch,
            "seq_len": self.seq_len,
            "devices": [f"{d.platform}:{d.id}" for d in devices],
            "step_cache": self._step_cache,
        }
        dev = devices[0]
        stats = dev.memory_stats() or {}  # None on the CPU backend
        for key in ("bytes_in_use", "peak_bytes_in_use"):
            if key in stats:
                prof[f"device_{key}"] = int(stats[key])
        if self._compiled is not None:
            ma = self._compiled.memory_analysis()
            prof["arg_bytes"] = int(ma.argument_size_in_bytes)
            prof["temp_bytes"] = int(ma.temp_size_in_bytes)
            prof["output_bytes"] = int(ma.output_size_in_bytes)
            if dev.device_kind in HW:  # no peaks, no roofline (e.g. the CPU)
                rep = analyze(
                    arch=self.model_cfg.arch_id, shape_name="trial",
                    mesh_name="local", chips=1, compiled=self._compiled,
                    n_params_active=int(param_count(self.state.params)),
                    n_tokens=self.batch * self.seq_len, kind="train",
                    device_kind=dev.device_kind)
                prof["predicted_step_s"] = round(rep.step_time_s, 6)
                prof["dominant"] = rep.dominant
                prof["roofline_compute_s"] = round(rep.compute_s, 6)
                prof["roofline_memory_s"] = round(rep.memory_s, 6)
                prof["roofline_collective_s"] = round(rep.collective_s, 6)
                if rep.step_time_s > 0:
                    prof["achieved_vs_predicted"] = round(
                        steady / rep.step_time_s, 4)
        return prof

    def save(self) -> Any:
        return {
            "state": jax.device_get(self.state._asdict()),
            "global_step": self._global_step,
        }

    def restore(self, snapshot: Any) -> None:
        """The snapshot's state; the hyperparameters stay this trial's (a
        PBT exploit restores a donor after ``reset_config``)."""
        state = TrainState(**jax.device_put(snapshot["state"], self._device))
        # A PBT mutation may have switched optimizer family: if the donor's
        # opt_state tree doesn't match this trainable's optimizer, re-init it
        # (params are what cloning is about; moments restart harmlessly).
        expect = jax.eval_shape(self._opt.init, state.params)
        if (jax.tree_util.tree_structure(expect)
                != jax.tree_util.tree_structure(state.opt_state)):
            state = state._replace(opt_state=self._fresh_opt_state(state.params))
        self.state = state
        self._global_step = int(snapshot["global_step"])

    def _fresh_opt_state(self, params: Any) -> Any:
        # Made as ``_build`` makes it, so its eager ops are the compiled ones,
        # and committed to the trial's device like the rest of the state, so
        # the step's call signature, and so its compiled program, is unchanged.
        with jax.default_device(self._device):
            return jax.device_put(self._opt.init(params), self._device)

    def reset_config(self, new_config: Dict[str, Any]) -> bool:
        """PBT mutation: new hyperparameters and a fresh optimizer state,
        same params.  The step is the shared one of the new config's key, so
        a mutation of values alone compiles nothing."""
        self.config = dict(new_config)
        self._configure(new_config)
        self.state = self.state._replace(
            opt_state=self._fresh_opt_state(self.state.params))
        self._arm(new_config)
        return True


def make_model_trainable(model_cfg: ModelConfig, **workload) -> type:
    """Bind a model config (and workload sizes) into a Trainable subclass."""
    defaults = dict(workload)

    class Bound(ModelTrainable):
        def setup(self, config: Dict[str, Any]) -> None:
            merged = {**defaults, "model_cfg": model_cfg, **config}
            super().setup(merged)

    Bound.__name__ = f"ModelTrainable[{model_cfg.arch_id}]"
    return Bound


def model_trainable_factory(model_cfg: ModelConfig, **workload):
    """Spawn-safe recipe for ``make_model_trainable`` — process workers rebuild
    the bound class in the child by re-importing this module and calling
    ``make_model_trainable(model_cfg, **workload)`` there (the class returned
    by ``make_model_trainable`` itself is function-local, so it cannot be
    pickled across a spawn boundary).  ``model_cfg`` and the workload kwargs
    ride along as pickled plain data."""
    from ..core.workers import TrainableFactory

    return TrainableFactory(
        target="repro.train.trainable:make_model_trainable",
        kwargs={"model_cfg": model_cfg, **workload},
        call=True,
    )
