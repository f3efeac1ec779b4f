"""The benchmark's harness: one run of one cell.

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: the configuration as run (``model`` holds
  the program's model settings, ``batch`` and ``seq_len`` the trial's
  shapes, ``limits`` the limits of the comparison and
  ``reference_precision`` the reference's matmul precision), with its plain reference
  beside it in ``bench/configs/<config>.py``;
- ``bench/flops/<config>.py``: ``flops_per_token(config)``;
- ``bench/traffic/<traffic>.json``: the sweep (the scheduler, by its class
  name in ``repro.core`` and its keyword arguments; search space, iteration
  size, checkpointing, when the window opens);
- ``bench/metrics/<metric>.py``: ``read(ctx)``, the number or ``None``.

A run drives Tune's entry point, ``run_experiments`` -> serial executor ->
``ModelTrainable``, through the public API.  Set-up (imports, device
initialisation, a warm-up of the cell's shapes) ends when the window opens:
at the sweep's launch for a traffic with ``"window_opens": "launch"``, at the
trial's first result for ``"first_result"``.  Once ``seconds`` have passed the
searcher suggests no new trial; a trial in flight runs on to the end of its
budget (the traffic's ``max_t``) or, where the traffic gives none, stops at its
next result.  The window closes at the last result, so it holds whole trials
and a rate never counts part of one.  Where the traffic says
``"compile_cache_in_window": false`` the persistent compilation cache is off
while the window is open, so every trial compiles as a sweep with fresh
hyperparameters does.

``correct`` compares the first three train steps of the window's first trial
(its losses, first gradient and three steps' change, read from the trial's
own state) with the plain reference run after the window.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = "bench"


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


# -- the cell, from its files ---------------------------------------------------------

@dataclass
class Cell:
    name: str
    root: Path
    chips: int
    config: Dict
    traffic: Dict
    reference: Any
    flops: Any
    end_to_end: List[Dict]
    per_layer: List[Dict]
    readers: Dict[str, Callable]


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = _read_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: {sorted(by_name)}")
    entry = by_name[name]
    conf_entry = {c["name"]: c for c in spec["configs"]}[entry["config"]]
    conf_file = root / conf_entry["file"]
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    layer = [m for m in spec["per_layer"] if _applies(m, name)]
    readers = {m["name"]: _load_module(root / BENCH / "metrics" / f"{m['name']}.py").read
               for m in e2e + layer}
    return Cell(
        name=name, root=root, chips=int(entry["chips"]),
        config=_read_json(conf_file),
        traffic=_read_json(root / BENCH / "traffic" / f"{entry['traffic']}.json"),
        reference=_load_module(conf_file.with_suffix(".py")),
        flops=_load_module(root / BENCH / "flops" / f"{entry['config']}.py"),
        end_to_end=e2e, per_layer=layer, readers=readers)


def model_config(config: Dict):
    """The program's ``ModelConfig`` of a configuration file."""
    import dataclasses

    from repro.models import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(**{k: v for k, v in config["model"].items() if k in fields}).validate()


def hyper_samples(traffic: Dict) -> List[Dict]:
    """The sweep's hyperparameters, in launch order.  Drawn from the traffic's
    own ``sample_seed``, so every run offers the same trials whatever its
    ``--seed``; the seed gives the weights and the data."""
    rng = np.random.default_rng(traffic.get("sample_seed", 0))
    out = []
    for _ in range(traffic["num_samples"]):
        hp = dict(traffic.get("fixed", {}))
        for key in sorted(traffic.get("space", {})):
            kind, lo, hi = traffic["space"][key]
            if kind == "loguniform":
                hp[key] = float(math.exp(rng.uniform(math.log(lo), math.log(hi))))
            elif kind == "uniform":
                hp[key] = float(rng.uniform(lo, hi))
            else:
                raise ValueError(f"unknown distribution {kind!r} for {key}")
        out.append(hp)
    return out


def optimizer_hypers(hp: Dict, traffic: Dict) -> Dict:
    """The optimizer settings a trial of ``hp`` runs with, as the reference
    needs them (the trainable's defaults where ``hp`` is silent)."""
    return {"lr": float(hp.get("lr", 3e-4)), "warmup": int(hp.get("warmup", 10)),
            "total_steps": int(traffic["total_steps"]),
            "weight_decay": float(hp.get("weight_decay", 0.1)),
            "b1": float(hp.get("b1", 0.9)), "b2": float(hp.get("b2", 0.95)),
            "eps": 1e-8, "grad_clip": float(hp.get("grad_clip", 1.0))}


def seeds_of(seed: int) -> Dict[str, int]:
    """Weights and data seeds of a run, within 31 bits whatever ``--seed``."""
    ss = np.random.SeedSequence(int(seed)).generate_state(2)
    return {"init_seed": int(ss[0] >> 1), "data_seed": int(ss[1] >> 1)}


# -- what the run records ------------------------------------------------------------------

class Capture:
    """Reads the first three train steps of one trial from its own state:
    each step's loss, the optimizer's first moment after step 1 (the first
    gradient, times 1 - b1), kept on the device until the window has closed,
    and the norms of the parameters' change over the three steps, taken on
    the device by a jitted function that set-up has compiled, so the window
    compiles nothing for it.  A ``warm`` capture calls that function on the
    trial's initial weights as it attaches, before the trial's first step,
    and keeps only the first moment."""

    def __init__(self, change_fn, init_key, warm: bool = False):
        self.change_fn, self.init_key = change_fn, init_key
        self.warm = warm
        self.losses: List[Any] = []
        self.grad_m = None
        self.change = None
        self.n = 0

    def attach(self, trainable) -> None:
        from .reference import STEPS

        inner = trainable._step_fn
        self.config = dict(trainable.config)

        if self.warm:
            self.change = self.change_fn(trainable.state.params, self.init_key)

        def stepped(state, batch):
            new_state, metrics = inner(state, batch)
            i, self.n = self.n, self.n + 1
            self.losses.append(metrics["loss"])
            if i == 0:
                self.grad_m = new_state.opt_state["m"]
            if i == STEPS - 1:
                self.change = self.change_fn(new_state.params, self.init_key)
            if self.n >= STEPS or self.warm:
                trainable._step_fn = inner
            return new_state, metrics

        trainable._step_fn = stepped

    @property
    def complete(self) -> bool:
        return self.change is not None and self.grad_m is not None

    def readings(self, b1: float) -> Dict:
        """The readings, on the host; the device copies are dropped."""
        import jax

        from .reference import STEPS, flat, host_rows

        grad = {k: v / (1.0 - b1) for k, v in host_rows(self.grad_m).items()}
        out = {"losses": [float(x) for x in self.losses[:STEPS]], "grad": grad,
               "change": flat(jax.device_get(self.change))}
        self.grad_m = self.change = None
        return out


class Recorder:
    """Host-clock record of one run: trial launches, results, the window, and
    the profiler trace when ``trace_dir`` is set."""

    def __init__(self, seconds: float, trace_dir: Optional[str] = None):
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.open_on_first_result = False
        self.launch: Dict[int, float] = {}
        self.first: Dict[int, float] = {}
        self.results: List[tuple] = []          # (time, trial key, iteration)
        self.capture: Optional[Capture] = None
        self.capture_factory: Optional[Callable[[], Capture]] = None
        self.before_first_step: Optional[Callable[[], None]] = None
        self._window_span = None

    @property
    def past_deadline(self) -> bool:
        return (self.t_open is not None
                and time.perf_counter() >= self.t_open + self.seconds)

    def open(self) -> None:
        import jax

        if self.trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._window_span = jax.profiler.TraceAnnotation("bench.window")
            self._window_span.__enter__()
        self.t_open = time.perf_counter()

    def close(self, t: float) -> None:
        self.t_close = t
        if self._window_span is not None:
            self._window_span.__exit__(None, None, None)
            self._window_span = None

    def on_launch(self, trainable) -> None:
        if self.capture is None and self.capture_factory is not None and (
                self.t_open is not None or self.open_on_first_result):
            self.capture = self.capture_factory()
            self.capture.attach(trainable)

    def on_result(self, key: int, iteration: int) -> None:
        t = time.perf_counter()
        self.first.setdefault(key, t)
        self.results.append((t, key, iteration))
        if self.t_open is None and self.open_on_first_result:
            self.open()

    def in_window(self, t: float) -> bool:
        return self.t_open is not None and self.t_open < t <= (self.t_close or -1.0)


def bench_trainable(model_cfg, workload: Dict, recorder: Recorder):
    """``make_model_trainable``'s class with the benchmark's host spans
    (``jax.profiler.TraceAnnotation``, on the profiler's clock) around
    ``setup``, ``step``, ``save`` and ``restore``.  It adds no work to a
    trial: it stamps the host clock and, for the one trial it checks, wraps
    the step function to keep the readings ``Capture`` takes."""
    import itertools

    from jax.profiler import TraceAnnotation

    from repro.train.trainable import make_model_trainable

    base = make_model_trainable(model_cfg, **workload)
    keys = itertools.count()

    class BenchTrainable(base):
        def setup(self, config):
            self._bench_key = next(keys)
            recorder.launch[self._bench_key] = time.perf_counter()
            with TraceAnnotation("bench.setup"):
                super().setup(config)
            recorder.on_launch(self)
            self._bench_first = True

        def step(self):
            if self._bench_first and recorder.before_first_step is not None:
                recorder.before_first_step()
            name = "bench.step.first" if self._bench_first else "bench.step"
            with TraceAnnotation(name):
                out = super().step()
            self._bench_first = False
            recorder.on_result(self._bench_key, self.iteration + 1)
            return out

        def save(self):
            with TraceAnnotation("bench.save"):
                return super().save()

        def restore(self, snapshot):
            with TraceAnnotation("bench.restore"):
                return super().restore(snapshot)

    BenchTrainable.__name__ = f"Bench[{model_cfg.arch_id}]"
    return BenchTrainable


# -- scheduler and searcher that end the window ------------------------------------------------

def make_scheduler(traffic: Dict, recorder: Recorder):
    """The traffic's scheduler: the class of ``repro.core`` that
    ``traffic["scheduler"]`` names, with ``traffic["scheduler_kwargs"]``.
    Once the deadline has passed it stops a trial that has no budget
    (``max_t``) at its next result."""
    import repro.core
    from repro.core import SchedulerDecision, TrialScheduler

    cls = getattr(repro.core, traffic["scheduler"], None)
    if not (isinstance(cls, type) and issubclass(cls, TrialScheduler)):
        raise ValueError(f"no scheduler {traffic['scheduler']!r} in repro.core")
    budgeted = "max_t" in traffic

    class Windowed(cls):
        def on_result(self, runner, trial, result):
            if recorder.past_deadline and not budgeted:
                return SchedulerDecision.STOP
            return super().on_result(runner, trial, result)

    return Windowed(metric="loss", mode="min", **traffic.get("scheduler_kwargs", {}))


def make_searcher(samples: List[Dict], recorder: Recorder):
    from repro.core import Searcher

    class Sampled(Searcher):
        """Hands out ``samples`` in order; none once the deadline has passed."""

        def __init__(self):
            super().__init__({}, metric="loss", mode="min")
            self._next = 0

        def suggest(self, trial_id):
            if recorder.past_deadline or self._next >= len(samples):
                return None
            hp = dict(samples[self._next])
            self._next += 1
            return hp

    return Sampled()


# -- one run -----------------------------------------------------------------------------------

class CompileLog:
    """Backend compilations, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.events: List[tuple] = []   # (end time, seconds)

    def __call__(self, event, duration, **kw):
        if event == self.EVENT:
            self.events.append((time.perf_counter(), float(duration)))

    def between(self, t0: float, t1: float) -> List[float]:
        return [d for t, d in self.events if t0 < t <= t1]


def _cache_enabled(on: bool) -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()


def _sweep(cell: Cell, trainable, samples, recorder, traffic, devices, log_dir,
           obs=None):
    from repro.core import Resources, run_experiments
    from repro.dist.submesh import SlicePool

    pool = SlicePool(devices=devices[: cell.chips])
    stop = {"training_iteration": traffic.get("max_t", 10**9)}
    return run_experiments(
        trainable, None, scheduler=make_scheduler(traffic, recorder),
        searcher=make_searcher(samples, recorder), stop=stop,
        resources_per_trial=Resources(cpu=1, devices=1),
        total_devices=pool.n_total, slice_pool=pool,
        checkpoint_freq=traffic["checkpoint_freq"], log_dir=log_dir,
        executor=traffic.get("executor", "serial"), obs=obs)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, out_dir: Optional[Path] = None) -> Dict:
    """One run of ``cell``; returns the result line's object.  The
    persistent compilation cache is the caller's to set up."""
    import jax

    from .reference import (STEPS, change_norms_fn, compare, Frozen, run_reference,
                            synthetic_batch)

    conf, traffic = cell.config, cell.traffic
    out_dir = Path(out_dir or cell.root / ".bench_out")
    run_dir = out_dir / f"run-{os.getpid()}"
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    cache_was = jax.config.jax_enable_compilation_cache
    try:
        devices = jax.devices()[: cell.chips]
        model = Frozen(conf["model"])
        mcfg = model_config(conf)
        seeds = seeds_of(seed)
        samples = hyper_samples(traffic)
        workload = dict(batch=conf["batch"], seq_len=conf["seq_len"],
                        steps_per_iter=traffic["steps_per_iter"],
                        total_steps=traffic["total_steps"], **seeds)
        tokens_per_iter = conf["batch"] * conf["seq_len"] * traffic["steps_per_iter"]
        recorder = Recorder(seconds, str(run_dir / "trace") if trace else None)
        trainable = bench_trainable(mcfg, workload, recorder)
        change_fn = change_norms_fn(cell.reference.init_params, model)
        init_key = jax.random.key(seeds["init_seed"])

        if traffic["window_opens"] == "launch":
            # Warm-up: one trial of the cell's shapes through the same path
            # (one iteration, with its checkpoint where the traffic saves),
            # which also loads the capture's norms from the cache.
            warm_rec = Recorder(math.inf)
            warm_rec.capture_factory = lambda: Capture(change_fn, init_key, warm=True)
            warm_rec.open_on_first_result = True
            warm = bench_trainable(mcfg, workload, warm_rec)
            warm_traffic = dict(traffic, max_t=1, scheduler="FIFOScheduler",
                                scheduler_kwargs={})
            if not traffic.get("compile_cache_in_window", True):
                # The warm-up's build loads its programs from the cache; its
                # step compiles for real, as the window's will: a process that
                # has loaded every program from the cache has a cold compiler,
                # and its first trial would start seconds late.
                warm_rec.before_first_step = lambda: _cache_enabled(False)
            _sweep(cell, warm, [traffic["warmup_config"]], warm_rec, warm_traffic,
                   devices, str(run_dir / "warmup"))
            jax.block_until_ready(warm_rec.capture.change)
            del warm, warm_rec
            recorder.capture_factory = lambda: Capture(change_fn, init_key)
            recorder.open()
        else:
            recorder.capture_factory = lambda: Capture(change_fn, init_key)
            recorder.open_on_first_result = True

        obs = None
        if trace:
            from repro.obs import Observability

            obs = Observability(trace=True)
        analysis = _sweep(cell, trainable, samples, recorder, traffic, devices,
                          str(run_dir / "sweep"), obs)
        recorder.close(recorder.results[-1][0] if recorder.results else time.perf_counter())
        stats = [d.memory_stats() or {} for d in devices]
        peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
        reduced = None
        if trace:
            from .trace_reduce import reduce_dir

            jax.profiler.stop_trace()
            reduced = reduce_dir(str(run_dir / "trace"))
        if jax.config.jax_enable_compilation_cache != cache_was:
            _cache_enabled(cache_was)

        # -- correct: the checked trial against the reference ------------------------------
        checks: Dict[str, Dict[str, float]] = {}
        notes: List[str] = []
        cap = recorder.capture
        errored = [t for t in analysis.trials if t.status.value == "ERROR"]
        if cap is None or not cap.complete:
            notes.append("the checked trial did not reach its third step")
        else:
            checked_hp = optimizer_hypers(cap.config, traffic)
            got = cap.readings(checked_hp["b1"])
            del analysis
            batches = [synthetic_batch(seeds["data_seed"], i, conf["batch"],
                                       conf["seq_len"], conf["model"]["vocab_size"])
                       for i in range(STEPS)]
            t_ref = time.perf_counter()
            want = run_reference(cell.reference, dict(model), checked_hp,
                                 seeds["init_seed"], batches,
                                 precision=conf["reference_precision"])
            numbers = compare(got, want)
            print(f"reference: {time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
            print(f"readings: {json.dumps(numbers)}", file=sys.stderr)
            for key, limit in conf["limits"].items():
                checks[key] = {"value": numbers[key], "limit": limit}
        ok = (not notes and not errored
              and all(c["value"] <= c["limit"] for c in checks.values()))

        # -- metrics ----------------------------------------------------------------------------
        t_open, t_close = recorder.t_open, recorder.t_close
        window = [r for r in recorder.results if recorder.in_window(r[0])]
        started = [k for k, t in recorder.launch.items()
                   if t_open is not None and t_open <= t and k in recorder.first
                   and recorder.in_window(recorder.first[k])]
        ctx = {
            "cell": cell.name, "config": conf, "traffic": traffic,
            "setup_s": (t_open - t_start) if t_open is not None else None,
            "window_s": (t_close - t_open) if t_close and t_open else None,
            "tokens": len(window) * tokens_per_iter,
            "results": window,
            "trial_starts": [recorder.first[k] - recorder.launch[k] for k in started],
            "spans": obs.tracer.spans if obs is not None else None,
            "compiles": compiles.between(t_open or 0.0, t_close or 0.0),
            "trace": reduced,
            "flops_per_token": cell.flops.flops_per_token(conf),
            "tokens_per_step": conf["batch"] * conf["seq_len"],
            "device_kind": devices[0].device_kind,
        }
        which = cell.per_layer if trace else cell.end_to_end
        metrics = {}
        for m in which:
            value = cell.readers[m["name"]](ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
        print(f"compilations in the window: {len(ctx['compiles'])} "
              f"({sum(ctx['compiles']):.3f} s)", file=sys.stderr)
        print("results (s after the opening, trial, iteration): "
              + json.dumps([[round(t - (t_open or 0.0), 3), k, it]
                            for t, k, it in recorder.results]), file=sys.stderr)
        result = {"correct": ok, "attempted": len(window) + len(errored),
                  "failed": len(errored), "metrics": metrics, "device": device}
        if reduced is not None:
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
        for note in notes:
            print(f"not correct: {note}", file=sys.stderr)
        for t in errored:
            print(f"trial {t.trial_id} ended in ERROR: {str(t.error)[-1500:]}",
                  file=sys.stderr)
        for key, c in checks.items():
            print(f"{key} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
        result["checks"] = checks
        return result
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
        if jax.config.jax_enable_compilation_cache != cache_was:
            _cache_enabled(cache_was)
        shutil.rmtree(run_dir, ignore_errors=True)
