"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 200 --batch 8 --seq-len 128 --reduced

``--reduced`` trains the smoke-scale variant (CPU-feasible); without it the
full config is used (TPU-scale — on this container use the dry-run instead).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from ..configs import get_config, list_archs
from ..data.pipeline import DataConfig, SyntheticLMDataset, synthetic_batch
from ..models import param_count
from ..train import (make_hyper_train_step, make_optimizer, make_train_state,
                     optimizer_hypers)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list_archs() + ["all"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced smoke-scale variant")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--out", default=None, help="JSONL metrics path")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    hypers = optimizer_hypers("adamw", args.steps,
                              {"lr": args.lr, "warmup": args.warmup})
    state = make_train_state(jax.random.key(0), cfg,
                             make_optimizer("adamw", hypers))
    step = jax.jit(make_hyper_train_step(cfg, "adamw"))
    hypers = {k: jnp.float32(v) for k, v in hypers.items()}
    print(f"[train] {cfg.arch_id} ({'reduced' if args.reduced else 'full'}): "
          f"{param_count(state.params):,} params")

    if cfg.frontend is None:
        data = SyntheticLMDataset(DataConfig(
            global_batch=args.batch, seq_len=args.seq_len,
            vocab_size=cfg.vocab_size))
        batch_at = lambda i: data.batch_at(i)
    else:
        batch_at = lambda i: synthetic_batch(cfg, args.batch, args.seq_len, seed=i)

    out_f = open(args.out, "w") if args.out else None
    t0 = time.time()
    for i in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in batch_at(i).items()}
        state, metrics = step(state, batch, hypers)
        if i % args.log_every == 0 or i == args.steps - 1:
            row = {"step": i, "loss": float(metrics["loss"]),
                   "accuracy": float(metrics["accuracy"]),
                   "grad_norm": float(metrics["grad_norm"]),
                   "elapsed_s": round(time.time() - t0, 2)}
            print(f"[train] {json.dumps(row)}")
            if out_f:
                out_f.write(json.dumps(row) + "\n")
    if out_f:
        out_f.close()
    final = float(metrics["loss"])
    print(f"[train] done: final loss {final:.4f} in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
