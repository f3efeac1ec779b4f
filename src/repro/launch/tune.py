"""Distributed hyperparameter search launcher — the paper's workload.

    PYTHONPATH=src python -m repro.launch.tune --arch smollm-135m --reduced \
        --scheduler asha --num-samples 16 --max-iters 20 --executor concurrent \
        --elastic greedy

Runs a Tune experiment over a model's optimizer hyperparameters with any of
the six built-in schedulers, optionally driven by a searcher (TPE/random),
with trials placed on mesh slices via the SlicePool.  By default the pool
holds this host's devices and each trial takes one (``--devices-per-trial``);
``--total-devices N`` swaps in a virtual pool of N devices for rehearsals on
the CPU.  The process and cluster tiers always place on virtual pools, and
the launcher then leaves the devices to the worker processes.  The exit code
is non-zero when no trial produced a result or any trial ended in ERROR.

``--executor`` picks the execution tier: ``serial`` (host time-slicing),
``concurrent`` (one worker thread per trial, overlapped JAX dispatch across
disjoint slices, heartbeat straggler detection), ``process`` (one spawned
worker *process* per trial — GIL-free host stepping, checkpoint bytes over
the ObjectStore spill surface, and kill-on-straggle reclamation after
``--straggler-deadline`` seconds),
``cluster`` (worker processes scheduled across a roster of hosts over the
length-prefixed socket transport — per-host SlicePools, host heartbeats,
content-addressed checkpoint fetch, host eviction; DESIGN.md §11), or
``vmap`` (homogeneous sweeps as one SPMD program).  ``--max-failures``
restarts a crashed trial from its last checkpoint.

Cluster quickstart (3 simulated hosts on loopback sockets)::

    PYTHONPATH=src python -m repro.launch.tune --arch smollm-135m --reduced \
        --scheduler asha --num-samples 8 --executor cluster --hosts 3x8 \
        --devices-per-trial 4 --max-failures 2

``--hosts`` shapes the roster (``3x8`` = three hosts of eight devices;
``a:8,b:16`` names heterogeneous ones) and ``--placement roofline``
right-sizes each trial's slice per host from its roofline profile, falling
back to ``--devices-per-trial``.  A host that stops heartbeating is evicted;
its trials restart from their last fetched checkpoint under the same
``--max-failures`` budget.

``--elastic greedy`` turns on the elastic control plane (DESIGN.md §6):
slices of early-stopped trials are absorbed by survivors at their next
checkpoint boundary (``fair`` rebalances instead); ``--lookahead K`` lets
workers run K results ahead of the scheduler on throughput-bound FIFO
sweeps (auto-clamped to 1 for schedulers that stop/perturb trials).

Observability (DESIGN.md §8-§9) quickstart::

    PYTHONPATH=src python -m repro.launch.tune --arch smollm-135m --reduced \
        --scheduler asha --num-samples 8 --executor concurrent \
        --trace trace.json --metrics-interval 5 --log-dir runs/demo \
        --live-table --report

``--trace PATH`` records a span for every lifecycle phase (schedule decision,
slice acquire, build, step, checkpoint save/restore, resize, restart) and
exports Chrome trace-event JSON at PATH — open it in Perfetto
(https://ui.perfetto.dev) or chrome://tracing.  ``--metrics-interval S``
snapshots the control-plane metrics registry (bus depth/fan-in latency,
scheduler decision latency, pool utilization, checkpoint bytes+latency,
restart/kill/resize counters) every S seconds to ``<log-dir>/metrics.jsonl``
and prints a status table at experiment end.

``--live-table`` renders the paper's live trial table (status / iteration /
metric / slice devices / restarts) as results stream in; ``--report`` writes
the self-contained HTML run report (metric curves, lifecycle gantt, fault
timeline, best-config table) to ``<log-dir>/report.html`` when the run ends —
even when it aborts.  Re-render any past run's artifacts offline with
``python -m repro.launch.report <log-dir>``.

Durable resume (DESIGN.md §12) quickstart — kill a sweep, continue it::

    PYTHONPATH=src python -m repro.launch.tune --arch smollm-135m --reduced \
        --scheduler asha --num-samples 16 --executor concurrent \
        --log-dir runs/sweep
    # ... ^C / OOM-kill / kill -9 the controller mid-sweep, then:
    PYTHONPATH=src python -m repro.launch.tune --arch smollm-135m --reduced \
        --scheduler asha --num-samples 16 --executor concurrent \
        --log-dir runs/sweep --resume

``--resume`` rebuilds the experiment from the run's durable artifacts:
trial statuses, iteration counts and metric histories replay from
``<log-dir>/events.jsonl`` (torn tail from the kill repaired), scheduler and
searcher state load from the watermarked ``<log-dir>/search_state.json``
snapshot, and weights restore from the per-trial checkpoint mirrors under
``<log-dir>/ckpt``.  Finished trials keep their results; interrupted trials
continue from their last valid checkpoint; trials with none restart from
scratch.  Pass the SAME sweep arguments as the original run — the space is
only used to regenerate trial identities, and a conflicting --num-samples
is rejected.  The journal is appended, never truncated.
"""
from __future__ import annotations

import argparse
import json

import jax

from ..configs import get_config, list_archs
from ..core import (ASHAScheduler, FIFOScheduler, GPSearcher,
                    HyperBandScheduler, MedianStoppingRule,
                    PopulationBasedTraining, Resources, TPESearcher,
                    RandomSearcher, TrialStatus, loguniform, run_experiments,
                    uniform)
from ..dist.submesh import SlicePool
from ..train.trainable import make_model_trainable, model_trainable_factory
from .compile_cache import setup_compile_cache

# Capacity of the virtual pool of the process and cluster tiers when
# --total-devices is not given: they must not touch the chips themselves.
_VIRTUAL_DEVICES = 256


def build_vmap_executor(cfg, args, total_devices: int):
    """Model selection as one SPMD program: N lanes of the same tiny LM,
    vmapped over (lr, weight_decay) with momentum SGD (see bench_vmap.py)."""
    import jax.numpy as jnp

    from ..core import CheckpointManager, ObjectStore
    from ..core.vmap_executor import VectorTrainableSpec, VmapExecutor
    from ..data import DataConfig, SyntheticLMDataset
    from ..models import forward_train, init_params

    data = SyntheticLMDataset(DataConfig(global_batch=args.batch,
                                         seq_len=args.seq_len,
                                         vocab_size=cfg.vocab_size))
    n_banked = 8
    batches = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[jax.tree_util.tree_map(jnp.asarray, data.batch_at(i))
          for i in range(n_banked)])

    def init_fn(seed, hypers):
        params = init_params(jax.random.key(seed), cfg)
        mom = jax.tree_util.tree_map(jnp.zeros_like, params)
        return {"p": params, "m": mom, "i": jnp.zeros((), jnp.int32)}

    def step_fn(state, hypers):
        batch = jax.tree_util.tree_map(lambda x: x[state["i"] % n_banked], batches)
        (_, metrics), grads = jax.value_and_grad(
            lambda p: forward_train(p, batch, cfg), has_aux=True)(state["p"])
        m = jax.tree_util.tree_map(lambda mo, g: 0.9 * mo + g, state["m"], grads)
        p = jax.tree_util.tree_map(
            lambda w, mo: w - hypers["lr"] * (mo + hypers["weight_decay"] * w),
            state["p"], m)
        return {"p": p, "m": m, "i": state["i"] + 1}, {"loss": metrics["loss"]}

    spec = VectorTrainableSpec(init_fn, step_fn, ("lr", "weight_decay"),
                               steps_per_iter=args.steps_per_iter)
    return VmapExecutor(spec, CheckpointManager(ObjectStore()),
                        n_lanes=min(args.num_samples, 8),
                        total_devices=total_devices)


def build_scheduler(name: str, max_iters: int):
    if name == "fifo":
        return FIFOScheduler(metric="loss", mode="min")
    if name == "asha":
        return ASHAScheduler(metric="loss", mode="min", max_t=max_iters,
                             grace_period=max(1, max_iters // 8),
                             reduction_factor=3)
    if name == "hyperband":
        return HyperBandScheduler(metric="loss", mode="min", max_t=max_iters)
    if name == "median":
        return MedianStoppingRule(metric="loss", mode="min", grace_period=2)
    if name == "pbt":
        return PopulationBasedTraining(
            metric="loss", mode="min",
            perturbation_interval=max(2, max_iters // 5),
            hyperparam_mutations={"lr": loguniform(1e-4, 1e-1)})
    raise ValueError(name)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m", choices=list_archs())
    ap.add_argument("--scheduler", default="asha",
                    choices=["fifo", "asha", "hyperband", "median", "pbt"])
    ap.add_argument("--searcher", default=None, choices=[None, "tpe", "gp", "random"])
    ap.add_argument("--num-samples", type=int, default=8)
    ap.add_argument("--max-iters", type=int, default=10)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--steps-per-iter", type=int, default=3)
    ap.add_argument("--devices-per-trial", type=int, default=1)
    ap.add_argument("--total-devices", type=int, default=None,
                    help="a virtual pool of N devices (CPU rehearsals); "
                         "unset, the pool is this host's devices")
    ap.add_argument("--executor", default="serial",
                    choices=["serial", "concurrent", "process", "cluster",
                             "vmap"])
    ap.add_argument("--hosts", default="2x8",
                    help="cluster executor roster: N (hosts x 8 devices), "
                         "'3x8', or 'name:devs,...' per host (see "
                         "repro.cluster.parse_hosts)")
    ap.add_argument("--placement", default="roofline",
                    choices=["roofline", "fixed"],
                    help="cluster executor: right-size slices from roofline "
                         "cost profiles, or place the requested width as-is")
    ap.add_argument("--max-failures", type=int, default=0,
                    help="restart a crashed trial from its last checkpoint up "
                         "to N times before marking it ERROR")
    ap.add_argument("--max-experiment-failures", type=int, default=0,
                    help="abort the experiment once more than N trials errored "
                         "(0 = never)")
    ap.add_argument("--heartbeat-timeout", type=float, default=60.0,
                    help="concurrent/process executors: seconds before a "
                         "stalled step emits HEARTBEAT_MISSED")
    ap.add_argument("--straggler-deadline", type=float, default=300.0,
                    help="process executor: hard per-step deadline after which "
                         "a straggling worker is SIGKILLed, its slice returned "
                         "to the pool, and the trial requeued from its last "
                         "checkpoint under --max-failures (0 disables)")
    ap.add_argument("--elastic", default="off",
                    choices=["off", "greedy", "fair"],
                    help="elastic slice resize at checkpoint boundaries: "
                         "'greedy' grows survivors into capacity freed by "
                         "early-stopped trials, 'fair' rebalances the pool "
                         "across running trials (needs a slice pool; no-op "
                         "with --executor vmap)")
    ap.add_argument("--lookahead", type=int, default=1,
                    help="max un-consumed results a worker may run ahead of "
                         "the scheduler (saves a control-plane round-trip per "
                         "step for process workers); automatically clamped to "
                         "1 unless the scheduler never stops/perturbs trials "
                         "(fifo)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export a Chrome trace-event JSON of every control-"
                         "plane span (schedule decision, slice acquire, "
                         "build, step, ckpt save/restore, resize, restart) "
                         "to PATH; view in Perfetto or chrome://tracing")
    ap.add_argument("--metrics-interval", type=float, default=0.0,
                    help="snapshot the control-plane metrics registry every "
                         "S seconds to <log-dir>/metrics.jsonl and print a "
                         "status table at experiment end (0 disables)")
    ap.add_argument("--live-table", action="store_true",
                    help="render the live trial status table (status / iter / "
                         "metric / devices / restarts) as results stream in")
    ap.add_argument("--report", action="store_true",
                    help="write the self-contained HTML run report to "
                         "<log-dir>/report.html at experiment end (requires "
                         "--log-dir; survives an aborting sweep)")
    ap.add_argument("--decisions", default="on",
                    choices=["on", "full", "off"],
                    help="journal scheduler/searcher verdicts as typed "
                         "DECISION records with their inputs (DESIGN.md §10); "
                         "'full' includes CONTINUE verdicts, 'off' disables "
                         "(query them post-hoc with repro.launch.explain)")
    ap.add_argument("--flightrec", default=None, metavar="DIR",
                    help="dump a crash-forensics bundle (last-N events + "
                         "decisions, scheduler/searcher state, trial table) "
                         "to DIR on SIGTERM/abort; defaults to "
                         "<log-dir>/flightrec when --log-dir is set")
    ap.add_argument("--resume", action="store_true",
                    help="continue an interrupted (even kill -9'd) sweep from "
                         "<log-dir>'s durable artifacts: journal replay + "
                         "search-state snapshot + checkpoint mirrors "
                         "(DESIGN.md §12); pass the same sweep arguments as "
                         "the original run")
    ap.add_argument("--log-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.report and not args.log_dir:
        ap.error("--report requires --log-dir (the JSONL journal feeds it)")
    if args.resume and not args.log_dir:
        ap.error("--resume requires --log-dir (the run's artifacts live there)")

    setup_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    workload = dict(batch=args.batch, seq_len=args.seq_len,
                    steps_per_iter=args.steps_per_iter,
                    total_steps=args.max_iters * args.steps_per_iter)
    if args.executor in ("process", "cluster"):
        # Spawn-safe recipe: worker processes rebuild the bound trainable by
        # re-importing make_model_trainable in the child.
        trainable = model_trainable_factory(cfg, **workload)
    else:
        trainable = make_model_trainable(cfg, **workload)

    space = {"lr": loguniform(1e-4, 1e-1), "warmup": 5,
             "weight_decay": uniform(0.0, 0.2)}
    searcher = None
    if args.searcher == "tpe":
        searcher = TPESearcher(space, metric="loss", mode="min",
                               max_trials=args.num_samples, seed=args.seed)
    elif args.searcher == "gp":
        searcher = GPSearcher(space, metric="loss", mode="min",
                              max_trials=args.num_samples, seed=args.seed)
    elif args.searcher == "random":
        searcher = RandomSearcher(space, metric="loss", mode="min",
                                  max_trials=args.num_samples, seed=args.seed)

    if args.executor in ("process", "cluster"):
        # Worker processes own the devices; the launcher keeps off them.
        total = args.total_devices or _VIRTUAL_DEVICES
        # cluster: per-host pools, the roster is the capacity
        pool = SlicePool(n_virtual=total) if args.executor == "process" else None
    else:
        pool = (SlicePool(devices=jax.devices()) if args.total_devices is None
                else SlicePool(n_virtual=args.total_devices))
        total = pool.n_total
    executor = args.executor
    if args.executor == "vmap":
        executor = build_vmap_executor(cfg, args, total)
        pool = None  # lanes replace slices; placement is the stacked program's
    analysis = run_experiments(
        trainable,
        None if searcher else space,
        scheduler=build_scheduler(args.scheduler, args.max_iters),
        searcher=searcher,
        num_samples=args.num_samples if not searcher else 1,
        stop={"training_iteration": args.max_iters},
        resources_per_trial=Resources(cpu=1, devices=args.devices_per_trial),
        total_devices=total,
        slice_pool=pool,
        executor=executor,
        hosts=args.hosts if args.executor == "cluster" else None,
        placement=args.placement,
        max_failures=args.max_failures,
        max_experiment_failures=args.max_experiment_failures,
        heartbeat_timeout=args.heartbeat_timeout,
        straggler_deadline=args.straggler_deadline,
        elastic=args.elastic,
        lookahead=args.lookahead,
        trace=args.trace,
        metrics_interval=args.metrics_interval,
        log_dir=args.log_dir,
        report=args.report,
        decisions={"on": True, "full": "full", "off": False}[args.decisions],
        flight_recorder=args.flightrec,
        live_table=args.live_table,
        resume=args.resume,
        verbose=True,
        seed=args.seed,
    )

    print("\n[tune] results:")
    for row in analysis.results_table():
        cfg_str = {k: (round(v, 5) if isinstance(v, float) else v)
                   for k, v in row["config"].items()
                   if isinstance(v, (int, float, str))}
        best = "   n/a" if row["best"] is None else f"{row['best']:.4f}"
        print(f"  {row['trial_id']}: {row['status']:10s} iters={row['iterations']:3d} "
              f"best={best} {cfg_str}")
    if analysis.best_value() is None:
        print("[tune] no trial produced a result (check that "
              "--devices-per-trial fits --total-devices)")
        raise SystemExit(1)
    print(f"[tune] best config: {json.dumps({k: v for k, v in analysis.best_config().items() if isinstance(v, (int, float, str))})}")
    print(f"[tune] best loss:   {analysis.best_value():.4f}")
    print(f"[tune] total training iterations across trials: {analysis.total_iterations()}")
    errored = [t.trial_id for t in analysis.trials
               if t.status == TrialStatus.ERROR]
    if errored:
        print(f"[tune] {len(errored)} trial(s) ended in ERROR: {errored}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
