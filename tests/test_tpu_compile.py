"""Compile the Pallas kernels and the SmolLM-135M train step for a TPU v5e.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a chip
that is described and not attached, and refuses what the chip would refuse
(blocks not aligned to the tiling, primitives Mosaic cannot lower, programs
that do not fit the chip's memory).  Interpret-mode tests cannot see any of
these.  The kernels are compiled at the widths the chip smoke test runs them
at, the flash attention backward at the SmolLM cell's and at gemma-2b's
head_dim 256; the step at full width, batch 8, sequence 2048, with remat,
with naive attention and as the chip traces it, with the flash kernel.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import dataclasses
import importlib
import re

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


@pytest.mark.parametrize("precision", [None, "highest"])
@pytest.mark.parametrize("heads", [(9, 3, 64), (8, 1, 256)],
                         ids=["smollm-135m", "gemma-2b"])
def test_flash_attention_backward_compiles(chip, heads, precision):
    """The forward, dq and dk/dv kernels at batch 8 x 2048 in float32, at
    both of the kernel's precisions, with the blocks ``block_sizes`` gives:
    at the SmolLM cell's widths (9 heads, 3 KV heads, hd 64) and at the
    widest head of the configurations (gemma-2b: 8 heads, 1 KV head, hd
    256), where 1024 x 1024 blocks overflow the chip's scoped VMEM."""
    f32, i32 = jnp.float32, jnp.int32
    B, S = 8, 2048
    H, K, hd = heads

    def grad(q, k, v, qp, kp):
        loss = lambda q, k, v: jnp.sum(fa.flash_attention_pallas(
            q, k, v, qp, kp, precision=precision))
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    text = _compile(chip, grad, ((B, S, H, hd), f32), ((B, S, K, hd), f32),
                    ((B, S, K, hd), f32), ((B, S), i32), ((B, S), i32)).as_text()
    for kernel in ("flash_attention_fwd", "flash_attention_dq",
                   "flash_attention_dkv"):
        assert kernel in text, kernel


def _smollm_step(chip):
    """The SmolLM-135M step a Tune trial runs at the cell's shape, compiled
    for one v5e: batch 8 x 2048, remat, the optimizer's scalars an
    argument; returns (compiled, bytes it needs on the chip)."""
    cfg = dataclasses.replace(get_config("smollm-135m"), remat=True)
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
    return compiled, (ma.argument_size_in_bytes + ma.output_size_in_bytes
                      + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


@pytest.fixture(scope="module")
def naive_smollm_step(chip):
    """The step as this CPU process traces it: ``auto`` attention is naive
    off the chip."""
    return _smollm_step(chip)


def test_smollm_step_fits_one_chip(naive_smollm_step):
    _, used = naive_smollm_step
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


def test_smollm_flash_step_fits_one_chip_and_drops_the_scores(
        chip, naive_smollm_step, monkeypatch):
    """The step as it is traced on the chip, where ``auto`` attention takes
    the flash kernel: it fits, holds its Mosaic kernels, and no (.., 2048,
    2048) score or mask tensor, where the naive step holds float32 (8, 3, 3,
    2048, 2048) ones.  Its temporaries do not fall by their 1.2e9 B: the
    step's peak is the head's (8, 2048, 49152) float32 logits, which the
    naive step's scores never overlapped."""
    scores = re.compile(r"\[[0-9,]*2048,2048\]")
    naive, _ = naive_smollm_step
    assert "f32[8,3,3,2048,2048]" in naive.as_text()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, used = _smollm_step(chip)
    assert used <= HW[V5E].hbm_bytes, f"{used / 2**30:.2f} GiB > 16 GiB"
    text = compiled.as_text()
    assert "flash_attention_fwd" in text and "flash_attention_dkv" in text
    assert not scores.search(text), scores.search(text)
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= 1.05 * naive.memory_analysis().temp_size_in_bytes)
