"""Compile the Pallas kernels and the SmolLM-135M train step for a TPU v5e.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a chip
that is described and not attached, and refuses what the chip would refuse
(blocks not aligned to the tiling, primitives Mosaic cannot lower, programs
that do not fit the chip's memory).  Interpret-mode tests cannot see any of
these.  The kernels are compiled at the widths the chip smoke test runs them
at; the step at full width, batch 8, sequence 2048, with remat.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.launch.mesh import HW, V5E
from repro.train import (make_hyper_train_step, make_optimizer, make_train_state,
                         optimizer_hypers)

fa, rg, rw, mr = (importlib.import_module(f"repro.kernels.{name}") for name in
                  ("flash_attention", "rglru_scan", "rwkv6_scan", "moe_router"))


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(chip, fn, *shapes):
    """Compile ``fn`` for one v5e chip; returns the compiled program."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_described_chip_is_v5e(topo):
    assert topo.devices[0].device_kind == V5E


@pytest.mark.parametrize("name", ["flash_attention", "rwkv6_scan",
                                  "rglru_scan", "moe_router"])
def test_kernel_compiles(chip, name):
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    B, S = 8, 2048
    if name == "flash_attention":  # SmolLM-135M: 9 heads, 3 KV heads, hd 64
        fn = lambda q, k, v, qp, kp: fa.flash_attention_pallas(q, k, v, qp, kp)
        shapes = [((B, S, 9, 64), bf16), ((B, S, 3, 64), bf16),
                  ((B, S, 3, 64), bf16), ((B, S), i32), ((B, S), i32)]
    elif name == "rwkv6_scan":  # rwkv6-1.6b: 32 heads x 64
        fn = lambda r, k, v, w, u, s: rw.rwkv6_scan_pallas(r, k, v, w, u, s)
        shapes = [((B, S, 32, 64), bf16)] * 3 + [
            ((B, S, 32, 64), f32), ((32, 64), f32), ((B, 32, 64, 64), f32)]
    elif name == "rglru_scan":  # recurrentgemma-9b: R = 4096
        fn = lambda a, b, h0: rg.rglru_scan_pallas(a, b, h0)
        shapes = [((B, S, 4096), f32)] * 2 + [((B, 4096), f32)]
    else:  # deepseek-moe-16b: 64 experts, top-6
        fn = lambda logits: mr.moe_router_pallas(logits, 6)
        shapes = [((B * S, 64), f32)]
    compiled = _compile(chip, fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()  # a Mosaic kernel, not a fallback


def test_smollm_step_fits_one_chip(chip):
    cfg = dataclasses.replace(get_config("smollm-135m"), remat=True)
    # The step a Tune trial runs: the optimizer's scalars are an argument.
    hypers = optimizer_hypers("adamw", 1000, {"lr": 3e-4, "warmup": 10})
    opt = make_optimizer("adamw", hypers)
    state = jax.eval_shape(lambda: make_train_state(jax.random.key(0), cfg, opt))
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), state)
    tok = jax.ShapeDtypeStruct((8, 2048), jnp.int32, sharding=chip)
    scalars = {k: jax.ShapeDtypeStruct((), jnp.float32, sharding=chip) for k in hypers}
    compiled = jax.jit(make_hyper_train_step(cfg, "adamw")).lower(
        state, {"tokens": tok, "labels": tok}, scalars).compile()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used <= HW[V5E].hbm_bytes, f"{used / 2**30:.2f} GiB > 16 GiB"


def test_rwkv6_step_fits_one_chip(chip):
    """The benchmark's RWKV-6 trial: every published width, 4 of the 24
    layers, one sequence of 4096, remat, float32 with its matmuls at
    HIGHEST."""
    cfg = dataclasses.replace(get_config("rwkv6-1.6b"), n_layers=4, remat=True,
                              matmul_precision="highest")
    hypers = optimizer_hypers("adamw", 1000, {"lr": 1e-3, "warmup": 2})
    opt = make_optimizer("adamw", hypers)
    state = jax.eval_shape(lambda: make_train_state(jax.random.key(0), cfg, opt))
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), state)
    tok = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=chip)
    scalars = {k: jax.ShapeDtypeStruct((), jnp.float32, sharding=chip) for k in hypers}
    compiled = jax.jit(make_hyper_train_step(cfg, "adamw")).lower(
        state, {"tokens": tok, "labels": tok}, scalars).compile()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used <= HW[V5E].hbm_bytes, f"{used / 2**30:.2f} GiB > 16 GiB"
