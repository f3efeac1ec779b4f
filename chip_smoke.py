"""Chip smoke test: Tune's main path on a TPU, at SmolLM-135M's full width.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one host of four chips

One chip runs three phases, each through the entry points a user calls:

1. sweeps: ``run_experiments`` -> serial executor -> ``ModelTrainable``
   over ``SlicePool(devices=jax.devices())``, at SmolLM-135M's published
   widths (30 layers, d_model 576, 9 heads / 3 KV heads, d_ff 1536, vocab
   49152) with remat, batch 8, sequence 2048, random weights from a seed.
   An ASHA sweep of two trials, then a PBT population of two with a
   perturbation interval of 1, so that checkpoint save, restore and
   ``reset_config`` run on the chip.  Every trial must end TERMINATED (at
   the iteration budget, or where ASHA stopped it) with finite losses, its
   first loss within 0.5 of ln(vocab), its last below its first, and its
   parameters on the TPU.
2. reference: three train steps of the reduced SmolLM on the same batches
   and the same initial state, on the chip at the default matmul precision
   (what trials use) and on the CPU at "highest"; the losses must agree
   within REFERENCE_ATOL.
3. kernels: each Pallas kernel compiled for the chip (no interpret mode) at
   a real width, against its ``kernels/ref.py`` oracle on the same chip.

Four chips run two checks only: the sharded train step of
``dist/sharding.py`` (fsdp_tp on a 2x2 data x model mesh) against the same
steps on one chip, and four concurrent one-chip trials, each of which must
hold its parameters on a chip of its own.

The last line of standard output is one JSON object naming the device, and
is printed only when every check passed.  Any failure raises, so the exit
code is non-zero; with no TPU the script stops before any phase, and it never
falls back to the CPU.  The seconds it prints are smoke timings of a cold or
warm compile cache, not benchmark numbers.
"""
import os

# The CPU is listed for the reference phase only; the TPU must come first.
os.environ.setdefault("JAX_PLATFORMS", "tpu,cpu")

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import (ASHAScheduler, FIFOScheduler,  # noqa: E402
                        PopulationBasedTraining, Resources, TrialStatus,
                        grid_search, loguniform, run_experiments)
from repro.data import DataConfig, SyntheticLMDataset  # noqa: E402
from repro.dist import sharding as S  # noqa: E402
from repro.dist.submesh import SlicePool  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh, peaks  # noqa: E402
from repro.train import adamw, make_train_state, make_train_step  # noqa: E402
from repro.train.trainable import make_model_trainable  # noqa: E402

FULL = dataclasses.replace(get_config("smollm-135m"), remat=True)
BATCH, SEQ = 8, 2048
ITERS, STEPS_PER_ITER = 3, 6
# Tolerances, about 3x what a v5e showed.  Reference: chip at the default
# matmul precision vs CPU at "highest", reduced model (measured 2.4e-4).
REFERENCE_ATOL = 1e-3
# Kernel vs oracle on the chip, max abs error.  Measured: flash attention
# 7.8e-3 (bf16 output), RWKV-6 0.066 on outputs up to 20 (f32 dots at the
# default precision), RG-LRU 0, MoE router weights 1.8e-7.
KERNEL_ATOL = {"flash_attention": 2e-2, "rwkv6_scan": 0.2,
               "rglru_scan": 1e-4, "moe_router": 1e-5}


class SmokeFailure(AssertionError):
    pass


def check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


def cache_files(path):
    return sum(len(files) for _, _, files in os.walk(path))


# -- phase 1: sweeps --------------------------------------------------------------

def run_sweep(name, cfg, batch, seq_len, scheduler, space, platform, **kw):
    """One ``run_experiments`` call over all of this host's devices; checks
    and prints every trial.  Returns the trials."""
    pool = SlicePool(devices=jax.devices())
    trainable = make_model_trainable(
        cfg, batch=batch, seq_len=seq_len, steps_per_iter=STEPS_PER_ITER,
        total_steps=ITERS * STEPS_PER_ITER)
    t0 = time.perf_counter()
    analysis = run_experiments(
        trainable, space, scheduler=scheduler,
        stop={"training_iteration": ITERS},
        resources_per_trial=Resources(cpu=1, devices=1),
        total_devices=pool.n_total, slice_pool=pool, seed=0, **kw)
    print(f"[{name}] {len(analysis.trials)} trials in "
          f"{time.perf_counter() - t0:.1f} s")
    for t in analysis.trials:
        check(t.status == TrialStatus.TERMINATED,
              f"{name}: {t.trial_id} ended {t.status.value}: {t.error}")
        losses = [r.metrics["loss"] for r in t.results]
        prof = t.profile or {}
        print(f"[{name}] {t.trial_id} lr={t.config['lr']:.5g} "
              f"iters={t.training_iteration} losses={losses} "
              f"devices={prof.get('devices')} "
              f"compile_s={prof.get('compile_s')} "
              f"steady_step_s={prof.get('steady_step_s')} (smoke timing) "
              f"peak_bytes_in_use={prof.get('device_peak_bytes_in_use')}")
        check(losses and all(math.isfinite(x) for x in losses),
              f"{name}: {t.trial_id} losses {losses}")
        check(abs(losses[0] - math.log(cfg.vocab_size)) < 0.5,
              f"{name}: {t.trial_id} first loss {losses[0]} is not within "
              f"0.5 of ln({cfg.vocab_size})")
        check(len(losses) == 1 or losses[-1] < losses[0],
              f"{name}: {t.trial_id} did not learn: {losses}")
        devs = prof.get("devices") or []
        check(len(devs) == 1 and devs[0].startswith(platform + ":"),
              f"{name}: {t.trial_id} parameters on {devs}, not one {platform}")
    check(any(t.training_iteration == ITERS for t in analysis.trials),
          f"{name}: no trial reached iteration {ITERS}")
    return analysis.trials


def phase_sweeps(cfg, batch, seq_len, platform):
    space = {"lr": grid_search([1e-3, 3e-3]), "warmup": 2,
             "weight_decay": 0.1}
    run_sweep("asha", cfg, batch, seq_len,
              ASHAScheduler(metric="loss", mode="min", max_t=ITERS,
                            grace_period=1, reduction_factor=2),
              space, platform, checkpoint_freq=0)
    pbt = PopulationBasedTraining(
        metric="loss", mode="min", perturbation_interval=1,
        hyperparam_mutations={"lr": loguniform(5e-4, 5e-3)})
    # Checkpoints of the full-width state (1.6 GB each) spill under log_dir.
    with tempfile.TemporaryDirectory(prefix=".smoke_", dir=ROOT) as log_dir:
        run_sweep("pbt", cfg, batch, seq_len, pbt, space, platform,
                  checkpoint_freq=1, log_dir=log_dir)
    print(f"[pbt] exploits: {pbt.n_exploits}")
    check(pbt.n_exploits >= 1, "pbt: no exploit, so restore and "
          "reset_config never ran")


# -- phase 2: reference --------------------------------------------------------------

def train_losses(cfg, opt, state, batches, device=None, shardings=None):
    """Losses of ``len(batches)`` train steps from ``state``, on ``device``
    or with ``shardings`` (state, batch) over a mesh."""
    if shardings is None:
        step = jax.jit(make_train_step(cfg, opt))
        put_state = put_batch = device
    else:
        step = jax.jit(make_train_step(cfg, opt), in_shardings=shardings,
                       out_shardings=(shardings[0], None))
        put_state, put_batch = shardings
    state = jax.device_put(state, put_state)
    losses = []
    for b in batches:
        state, m = step(state, jax.device_put(b, put_batch))
        losses.append(float(m["loss"]))
    return losses


def phase_reference(device, cpu):
    cfg = get_config("smollm-135m").reduced()
    opt = adamw(1e-3)
    data = SyntheticLMDataset(DataConfig(global_batch=8, seq_len=256,
                                         vocab_size=cfg.vocab_size))
    batches = [data.batch_at(i) for i in range(3)]
    with jax.default_device(cpu):
        state = make_train_state(jax.random.key(0), cfg, opt)
    chip = train_losses(cfg, opt, state, batches, device)
    with jax.default_matmul_precision("highest"):
        host = train_losses(cfg, opt, state, batches, cpu)
    err = max(abs(a - b) for a, b in zip(chip, host))
    print(f"[reference] {device.platform} losses={chip}")
    print(f"[reference] cpu losses={host}")
    print(f"[reference] max |diff| = {err:.3g} (atol {REFERENCE_ATOL})")
    check(all(math.isfinite(x) for x in chip + host), "reference: non-finite")
    check(err <= REFERENCE_ATOL, f"reference: chip and cpu differ by {err}")


# -- phase 3: kernels ----------------------------------------------------------------

def kernel_cases(batch, seq_len):
    """name -> (kernel call, oracle call): each a thunk on fixed inputs."""
    key = jax.random.key(1)

    def rand(i, shape, dtype=jnp.float32, scale=1.0):
        x = jax.random.normal(jax.random.fold_in(key, i), shape) * scale
        return x.astype(dtype)

    B, S = batch, seq_len
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    # flash attention at SmolLM-135M widths: 9 heads, 3 KV heads, hd 64
    q, k, v = (rand(i, (B, S, h, 64), jnp.bfloat16)
               for i, h in ((0, 9), (1, 3), (2, 3)))
    # rwkv6-1.6b: 32 heads x 64
    r6 = [rand(i, (B, S, 32, 64), scale=0.5) for i in (3, 4, 5)]
    logw = -jnp.exp(rand(6, (B, S, 32, 64), scale=0.5) - 2.0)
    u, s0 = rand(7, (32, 64), scale=0.3), rand(8, (B, 32, 64, 64), scale=0.2)
    # recurrentgemma-9b: R = 4096
    a = jax.nn.sigmoid(rand(9, (B, S, 4096)))
    b, h0 = rand(10, (B, S, 4096), scale=0.3), rand(11, (B, 4096), scale=0.2)
    # deepseek-moe-16b: 64 experts, top-6
    logits = rand(12, (B * S, 64), scale=2.0)
    return {
        "flash_attention": (lambda: ops.flash_attention(q, k, v, pos, pos),
                            lambda: ref.flash_attention_ref(q, k, v, pos, pos)),
        "rwkv6_scan": (lambda: ops.rwkv6_scan(*r6, logw, u, s0),
                       lambda: ref.rwkv6_scan_ref(*r6, logw, u, s0)),
        "rglru_scan": (lambda: ops.rglru_scan(a, b, h0),
                       lambda: ref.rglru_scan_ref(a, b, h0)),
        "moe_router": (lambda: ops.moe_router(logits, 6),
                       lambda: ref.moe_router_ref(logits, 6)),
    }


def phase_kernels(batch, seq_len, platform):
    interpret = ops.use_interpret()
    check(interpret == (platform == "cpu"),
          f"kernels: interpret={interpret} on {platform}")
    for name, (kernel, oracle) in kernel_cases(batch, seq_len).items():
        t0 = time.perf_counter()
        got = jax.block_until_ready(kernel())
        first_s = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(oracle())
        got = got if isinstance(got, tuple) else (got,)
        want = want if isinstance(want, tuple) else (want,)
        err = max(float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                        - w.astype(jnp.float32))))
                  for g, w in zip(got, want))
        scale = max(float(jnp.max(jnp.abs(w.astype(jnp.float32))))
                    for w in want)
        print(f"[kernels] {name}: max |err| {err:.3g} (atol "
              f"{KERNEL_ATOL[name]}, max |ref| {scale:.3g}), first call "
              f"{first_s:.2f} s incl. compile (smoke timing)")
        check(all(bool(jnp.all(jnp.isfinite(g))) for g in got),
              f"kernels: {name} not finite")
        check(err <= KERNEL_ATOL[name], f"kernels: {name} error {err}")
        if name == "moe_router":
            check(bool(jnp.all(got[1] == want[1])),
                  "kernels: moe_router picked other experts than the oracle")


# -- four chips ----------------------------------------------------------------------

def four_chip_sharded_step(cfg, batch, seq_len, devices):
    """fsdp_tp over a 2x2 (data, model) mesh vs the same steps on one chip,
    from one initial state."""
    opt = adamw(1e-3)
    data = SyntheticLMDataset(DataConfig(global_batch=batch, seq_len=seq_len,
                                         vocab_size=cfg.vocab_size, noise=0.05))
    batches = [data.batch_at(i) for i in range(3)]
    with jax.default_device(devices[0]):
        state = make_train_state(jax.random.key(0), cfg, opt)
    out = {}
    for shape in ((1, 1), (2, 2)):
        mesh = make_mesh(shape, ("data", "model"))
        with S.sharding_strategy("fsdp_tp"), S.activation_policy(mesh):
            shardings = (S.make_shardings(S.train_state_specs(state, mesh, cfg),
                                          mesh),
                         S.make_shardings(S.batch_specs(batches[0], mesh), mesh))
            t0 = time.perf_counter()
            out[shape] = train_losses(cfg, opt, state, batches,
                                      shardings=shardings)
        print(f"[sharded] mesh {shape}: losses={out[shape]} "
              f"({time.perf_counter() - t0:.1f} s incl. compile)")
    one, four = out[(1, 1)], out[(2, 2)]
    rel = max(abs(a - b) / abs(a) for a, b in zip(one, four))
    print(f"[sharded] max relative |diff| 4 chips vs 1 = {rel:.3g} (rtol 2e-3)")
    check(all(math.isfinite(x) for x in one + four), "sharded: non-finite")
    check(rel <= 2e-3, f"sharded: 4-chip losses differ from 1-chip by {rel}")


def four_chip_concurrent_trials(cfg, batch, seq_len, devices):
    pool = SlicePool(devices=devices)
    trainable = make_model_trainable(cfg, batch=batch, seq_len=seq_len,
                                     steps_per_iter=2, total_steps=2)
    analysis = run_experiments(
        trainable, {"lr": 1e-3, "init_seed": grid_search(list(range(4)))},
        scheduler=FIFOScheduler(metric="loss", mode="min"),
        stop={"training_iteration": 1},
        resources_per_trial=Resources(cpu=1, devices=1),
        total_devices=pool.n_total, slice_pool=pool, executor="concurrent",
        checkpoint_freq=0, seed=0)
    seen = []
    for t in analysis.trials:
        check(t.status == TrialStatus.TERMINATED,
              f"concurrent: {t.trial_id} ended {t.status.value}: {t.error}")
        loss = t.last_result.metrics["loss"]
        devs = t.profile["devices"]
        print(f"[concurrent] {t.trial_id} init_seed={t.config['init_seed']} "
              f"loss={loss} devices={devs}")
        check(math.isfinite(loss), f"concurrent: {t.trial_id} loss {loss}")
        seen += devs
    check(len(seen) == len(devices) == len(set(seen)),
          f"concurrent: trials share chips: {seen}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the sharded step and the concurrent trials on "
                         "a host of four chips, and nothing else")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX's first device is {dev}); "
                         "not running on the CPU")
    if args.four_chips and len(devices) != 4:
        raise SystemExit(f"chip_smoke: --four-chips needs 4 chips, found "
                         f"{len(devices)}")
    cache = setup_compile_cache()
    print(f"device: {dev.device_kind} x{len(devices)}; peaks "
          f"{peaks(dev.device_kind)}")
    print(f"compile cache: {cache} ({cache_files(cache)} files at start)")

    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_sharded_step(FULL, BATCH, SEQ, devices)
        four_chip_concurrent_trials(FULL, BATCH, SEQ, devices)
    else:
        phase_sweeps(FULL, BATCH, SEQ, dev.platform)
        print(f"[sweeps] done at {time.perf_counter() - t0:.1f} s; chip "
              f"peak_bytes_in_use {dev.memory_stats()['peak_bytes_in_use']}")
        phase_reference(dev, jax.devices("cpu")[0])
        phase_kernels(BATCH, SEQ, dev.platform)
    print(f"all checks passed in {time.perf_counter() - t0:.1f} s; compile "
          f"cache {cache} holds {cache_files(cache)} files")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
