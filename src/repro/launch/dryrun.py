"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) combo.

Proves the distribution config is coherent without hardware: for each combo we
``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` on the production mesh
(single-pod 16x16 and multi-pod 2x16x16), print ``memory_analysis()`` /
``cost_analysis()``, and derive roofline terms (launch/roofline.py).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out benchmarks/results
"""
# The host platform must present 512 placeholder devices BEFORE jax initializes;
# these two lines must precede every other import (including repro.*).
import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
).strip()

import argparse
import dataclasses
import json
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs import get_config, list_archs
from ..dist.sharding import (batch_specs, cache_specs, make_shardings,
                             param_specs, train_state_specs)
from ..models import ModelConfig, decode_step, forward_encode, init_params, prefill
from ..train import (make_hyper_train_step, make_optimizer, make_train_state,
                     optimizer_hypers)
from .mesh import V5E, make_production_mesh
from .roofline import analyze
from .shapes import SHAPES, ShapeSpec, dryrun_config, input_specs, skip_reason


def _tree_bytes(tree: Any) -> int:
    return sum(int(x.size) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def active_param_count(cfg: ModelConfig) -> int:
    """Total params, counting only top_k/n_experts of routed expert weights."""
    shapes = jax.eval_shape(partial(init_params, jax.random.key(0), cfg))
    total = sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
    if cfg.moe is None:
        return total
    expert = 0
    def count_experts(path, leaf):
        nonlocal expert
        keys = [getattr(k, "key", None) for k in path]
        if "experts" in keys:
            expert += int(leaf.size)
        return leaf
    jax.tree_util.tree_map_with_path(count_experts, shapes)
    frac = cfg.moe.top_k / cfg.moe.n_experts
    return int(total - expert * (1.0 - frac))


def lower_one(
    arch: str, shape: ShapeSpec, mesh, mesh_name: str,
    verbose: bool = True, compile_: bool = True,
    strategy: str = "fsdp_tp", seq_parallel: bool = False,
    cfg_overrides: Optional[Dict[str, Any]] = None,
    variant: str = "",
) -> Optional[Dict[str, Any]]:
    """Lower+compile one combo.  ``strategy``/``seq_parallel``/``cfg_overrides``
    parameterize §Perf variants; ``variant`` labels the record."""
    cfg = dryrun_config(get_config(arch))
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    reason = skip_reason(cfg, shape)
    if reason is not None:
        if verbose:
            print(f"[dryrun] SKIP {arch} x {shape.name}: {reason}")
        return {"arch": arch, "shape": shape.name, "mesh": mesh_name,
                "status": "skipped", "reason": reason}

    chips = mesh.devices.size
    specs = input_specs(cfg, shape)
    t0 = time.time()
    from ..dist.sharding import activation_policy, sharding_strategy
    strat_ctx = sharding_strategy(strategy)
    strat_ctx.__enter__()
    policy_ctx = activation_policy(mesh, seq_parallel=seq_parallel)
    policy_ctx.__enter__()

    if shape.kind == "train":
        # The Tune trial's step: the optimizer's scalars are an argument.
        hypers = optimizer_hypers("adamw", 10_000, {"lr": 3e-4, "warmup": 100})
        opt = make_optimizer("adamw", hypers, moment_dtype=cfg.opt_moment_dtype)
        state_shapes = jax.eval_shape(
            partial(make_train_state, jax.random.key(0), cfg, opt))
        state_sh = make_shardings(train_state_specs(state_shapes, mesh, cfg), mesh)
        batch_sh = make_shardings(batch_specs(specs["batch"], mesh), mesh)
        step = make_hyper_train_step(cfg, "adamw", microbatch=cfg.train_microbatch,
                                     moment_dtype=cfg.opt_moment_dtype)
        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh, None),
                         out_shardings=(state_sh, None), donate_argnums=(0,))
        hyper_shapes = {k: jax.ShapeDtypeStruct((), jnp.float32) for k in hypers}
        lowered = jitted.lower(state_shapes, specs["batch"], hyper_shapes)
        n_tokens = shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        params_shapes = jax.eval_shape(partial(init_params, jax.random.key(0), cfg))
        param_sh = make_shardings(param_specs(params_shapes, mesh, cfg), mesh)
        batch_sh = make_shardings(batch_specs(specs["batch"], mesh), mesh)
        if cfg.encoder_only:
            fn = lambda p, b: forward_encode(p, b, cfg)
        else:
            fn = lambda p, b: prefill(p, b, cfg, shape.seq_len)
        jitted = jax.jit(fn, in_shardings=(param_sh, batch_sh))
        lowered = jitted.lower(params_shapes, specs["batch"])
        n_tokens = shape.global_batch * shape.seq_len
    else:  # decode
        params_shapes = jax.eval_shape(partial(init_params, jax.random.key(0), cfg))
        param_sh = make_shardings(param_specs(params_shapes, mesh, cfg), mesh)
        cache_sh = make_shardings(
            cache_specs(specs["caches"], mesh, shape.global_batch), mesh)
        tok_sh = make_shardings(batch_specs(specs["tokens"], mesh), mesh)
        fn = lambda p, c, t, pos: decode_step(p, c, t, pos, cfg)
        jitted = jax.jit(fn, in_shardings=(param_sh, cache_sh, tok_sh, None),
                         donate_argnums=(1,))
        lowered = jitted.lower(params_shapes, specs["caches"],
                               specs["tokens"], specs["pos"])
        n_tokens = shape.global_batch  # one new token per sequence

    policy_ctx.__exit__(None, None, None)
    strat_ctx.__exit__(None, None, None)
    t_lower = time.time() - t0
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape.name, "mesh": mesh_name, "chips": int(chips),
        "status": "lowered", "t_lower_s": round(t_lower, 2),
        "variant": variant or "baseline",
    }
    if not compile_:
        if verbose:
            print(f"[dryrun] {arch} x {shape.name} x {mesh_name}: lowered "
                  f"in {t_lower:.1f}s (compile skipped)")
        return record

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    # The placeholder devices are CPUs; the program stands for a v5e pod.
    report = analyze(
        arch, shape.name, mesh_name, int(chips), compiled,
        n_params_active=active_param_count(cfg), n_tokens=n_tokens,
        kind=shape.kind, device_kind=V5E)
    record.update(status="compiled", t_compile_s=round(t_compile, 2),
                  **report.to_dict())

    if verbose:
        ma = compiled.memory_analysis()
        print(f"[dryrun] {arch} x {shape.name} x {mesh_name} "
              f"(lower {t_lower:.1f}s, compile {t_compile:.1f}s)")
        print(f"  memory_analysis: {ma}")
        ca = compiled.cost_analysis() or {}
        print(f"  cost_analysis: flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e}")
        print(f"  roofline: compute={report.compute_s*1e3:.2f}ms "
              f"memory={report.memory_s*1e3:.2f}ms "
              f"collective={report.collective_s*1e3:.2f}ms "
              f"-> {report.dominant}-bound; "
              f"useful-flops={report.useful_flops_ratio:.2f} "
              f"hbm/dev={report.hbm_per_device_gib:.2f}GiB")
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", choices=["all"] + list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="all archs x shapes")
    ap.add_argument("--out", default=None, help="directory for JSON records")
    ap.add_argument("--no-compile", action="store_true", help="lower only")
    args = ap.parse_args()

    archs = list_archs() if (args.arch == "all" or args.all) else [args.arch]
    shapes = list(SHAPES.values()) if (args.shape == "all" or args.all) \
        else [SHAPES[args.shape]]
    mesh_names = {"single": ["pod16x16"], "multi": ["pods2x16x16"],
                  "both": ["pod16x16", "pods2x16x16"]}[args.mesh]

    records = []
    for mesh_name in mesh_names:
        mesh = make_production_mesh(multi_pod=(mesh_name == "pods2x16x16"))
        for arch in archs:
            for shape in shapes:
                try:
                    rec = lower_one(arch, shape, mesh, mesh_name,
                                    compile_=not args.no_compile)
                except Exception as e:  # noqa: BLE001 — record and continue
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape.name, "mesh": mesh_name,
                           "status": "error", "error": f"{type(e).__name__}: {e}"}
                if rec is not None:
                    records.append(rec)
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    path = os.path.join(args.out, f"dryrun_{args.mesh}.json")
                    with open(path, "w") as f:
                        json.dump(records, f, indent=1, default=str)

    n_ok = sum(1 for r in records if r["status"] == "compiled")
    n_skip = sum(1 for r in records if r["status"] == "skipped")
    n_err = sum(1 for r in records if r["status"] == "error")
    print(f"\n[dryrun] {n_ok} compiled, {n_skip} skipped (documented), {n_err} errors")
    if n_err:
        for r in records:
            if r["status"] == "error":
                print(f"  ERROR {r['arch']} x {r['shape']} x {r['mesh']}: {r['error']}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
