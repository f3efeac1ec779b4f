"""Readings that the limits of ``correct`` are set from.

    python bench/calibrate.py --workload smollm-135m.long --seeds 1 2 3 ... \\
        [--controls 3] [--against highest default] [--control-runs 3 --seconds 10]

For each seed, in one process: the cell's checked trial (the same trainable
class, hyperparameters and first three train steps as a run's window gives
it) against the plain float32 reference, the numbers ``compare`` gives (the
program's readings, whose largest over a dozen seeds is a limit's lower
end).  For the first ``--controls`` seeds also the control,
``bf16_activations``: the reference's forward pass and loss in bfloat16
put in the program's train step, whose weights and optimizer stay in
float32; ``program_bf16``, the program with its own bfloat16 path
(weights, optimizer state and activations) switched on; and the planted
fault of half the batch left out (the mean taken over the rest, in the
reference), each against the same reference (their smallest readings
bound a limit from above).  A state left unchanged reads 1 in
``change_gap`` by construction and needs no run.  Each reading is taken
against the reference at every matmul precision ``--against`` names.
``--control-runs`` then makes whole runs of the cell (``run_cell``, a
``--seconds`` window) with the control in the program's place, on the
first seeds, and prints whether each was ``correct``: it has to come out
false.

One JSON line per reading on standard output.  Runs on whatever JAX finds,
so it is also what the CPU tests drive at a small size.
"""
import argparse
import contextlib
import copy
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# The program's own bfloat16 path: weights, optimizer state and activations.
CONTROL = {"param_dtype": "bfloat16", "activation_dtype": "bfloat16"}


def with_control(cell):
    """``cell`` with the program's bfloat16 path switched on."""
    cell = copy.copy(cell)
    cell.config = dict(cell.config, model=dict(cell.config["model"], **CONTROL))
    return cell


@contextlib.contextmanager
def bf16_activations(cell):
    """The program's train step with its forward pass and loss replaced by
    the reference's, computed in bfloat16 (activations and matmul inputs);
    weights, gradients and the optimizer stay the program's, in float32.
    (The program's own ``activation_dtype`` of bfloat16 fails to trace with
    float32 weights.)"""
    import jax.numpy as jnp

    from bench.reference import Frozen
    from repro.train import train_step

    model = Frozen(cell.config["model"])

    def forward_train(params, batch, cfg):
        loss = cell.reference.loss(params, batch, model, jnp.bfloat16)
        zero = jnp.zeros((), jnp.float32)
        return loss, {"loss": loss, "aux_loss": zero, "accuracy": zero}

    program = train_step.forward_train
    train_step.forward_train = forward_train
    try:
        yield
    finally:
        train_step.forward_train = program


def program_readings(cell, seed: int, precision=None):
    """(readings, optimizer hypers, seeds) of the checked trial's first
    steps; ``precision`` sets the default matmul precision the program runs
    at."""
    import jax

    from bench import harness
    from bench.reference import change_norms_fn, Frozen

    conf, traffic = cell.config, cell.traffic
    seeds = harness.seeds_of(seed)
    hp = harness.hyper_samples(traffic)[0]
    workload = dict(batch=conf["batch"], seq_len=conf["seq_len"],
                    steps_per_iter=traffic["steps_per_iter"],
                    total_steps=traffic["total_steps"], **seeds)
    rec = harness.Recorder(math.inf)
    rec.open_on_first_result = True
    init_key = jax.random.key(seeds["init_seed"])
    change_fn = change_norms_fn(cell.reference.init_params, Frozen(conf["model"]))
    rec.capture_factory = lambda: harness.Capture(change_fn, init_key)
    cls = harness.bench_trainable(harness.model_config(conf), workload, rec)
    with jax.default_matmul_precision(precision):
        trainable = cls(dict(hp))
        while not rec.capture.complete:
            trainable.train()
    hypers = harness.optimizer_hypers(hp, traffic)
    got = rec.capture.readings(hypers["b1"])
    trainable.cleanup()
    return got, hypers, seeds


def calibrate(cell, seeds, controls: int, witnesses: int = 0, out=None,
              against=("highest",)):
    from bench.reference import (STEPS, compare, run_reference, synthetic_batch,
                                 worst_leaves)

    conf = cell.config
    for n, seed in enumerate(seeds):
        t0 = time.perf_counter()
        got, hypers, s = program_readings(cell, seed)
        readings = [("program", got)]
        if n < controls:
            readings.append(("program_bf16", program_readings(with_control(cell), seed)[0]))
            with bf16_activations(cell):
                readings.append(("bf16_activations", program_readings(cell, seed)[0]))
        if n < witnesses:
            readings.append(("program_highest",
                             program_readings(cell, seed, "highest")[0]))
        batches = [synthetic_batch(s["data_seed"], i, conf["batch"], conf["seq_len"],
                                   conf["model"]["vocab_size"]) for i in range(STEPS)]
        for precision in against:
            ref = lambda **kw: run_reference(cell.reference, dict(conf["model"]), hypers,
                                             s["init_seed"], batches, precision=precision,
                                             **kw)
            want = ref()
            faults = [("fault_half_batch", ref(half_batch=True))] if n < controls else []
            for kind, got in readings + faults:
                line = {"cell": cell.name, "seed": seed, "kind": kind, "against": precision,
                        **compare(got, want), "losses": got["losses"],
                        "ref_losses": want["losses"],
                        "worst": worst_leaves(got, want),
                        "seconds": time.perf_counter() - t0}
                print(json.dumps(line), file=out or sys.stdout, flush=True)


def control_runs(cell, seeds, seconds: float, out=None, out_dir=None):
    """Whole runs of ``cell`` with the control, ``bf16_activations``, in the
    program's place."""
    from bench import harness

    for seed in seeds:
        with bf16_activations(cell):
            res = harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                                   out_dir=out_dir)
        line = {"cell": cell.name, "seed": seed, "kind": "control_run",
                "correct": res["correct"], "checks": res["checks"]}
        print(json.dumps(line), file=out or sys.stdout, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--witnesses", type=int, default=0,
                    help="seeds that also run the program at the highest "
                         "matmul precision, a witness for the reference")
    ap.add_argument("--against", nargs="+", default=None,
                    help="matmul precisions of the reference")
    ap.add_argument("--control-runs", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    import jax

    from bench import harness
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload, ROOT)
    calibrate(cell, args.seeds, args.controls, args.witnesses,
              against=args.against or [cell.config["reference_precision"]])
    control_runs(cell, args.seeds[: args.control_runs], args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
