"""Launch-layer coverage: shape specs, applicability matrix, input structs,
active-param accounting, per-chip peaks, compile cache."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, list_archs
from repro.launch.mesh import HW, V5E, peaks
from repro.launch.shapes import (SHAPES, applicable, dryrun_config, input_specs,
                                 skip_reason)


class TestShapes:
    def test_assigned_shapes_exact(self):
        assert (SHAPES["train_4k"].seq_len, SHAPES["train_4k"].global_batch) == (4096, 256)
        assert (SHAPES["prefill_32k"].seq_len, SHAPES["prefill_32k"].global_batch) == (32768, 32)
        assert (SHAPES["decode_32k"].seq_len, SHAPES["decode_32k"].global_batch) == (32768, 128)
        assert (SHAPES["long_500k"].seq_len, SHAPES["long_500k"].global_batch) == (524288, 1)

    def test_applicability_matrix(self):
        """10x4 = 40 pairs: 32 applicable + 8 documented skips."""
        n_app = n_skip = 0
        for arch in list_archs():
            cfg = get_config(arch)
            for shape in SHAPES.values():
                if applicable(cfg, shape):
                    n_app += 1
                else:
                    n_skip += 1
                    assert skip_reason(cfg, shape)
        assert (n_app, n_skip) == (32, 8)

    def test_encoder_skips_decode(self):
        cfg = get_config("hubert-xlarge")
        assert not applicable(cfg, SHAPES["decode_32k"])
        assert not applicable(cfg, SHAPES["long_500k"])
        assert applicable(cfg, SHAPES["prefill_32k"])

    def test_long_context_only_subquadratic(self):
        runs = {a for a in list_archs()
                if applicable(get_config(a), SHAPES["long_500k"])}
        assert runs == {"rwkv6-1.6b", "recurrentgemma-9b", "h2o-danube-1.8b"}


class TestInputSpecs:
    def test_train_structs_lm(self):
        cfg = dryrun_config(get_config("smollm-135m"))
        specs = input_specs(cfg, SHAPES["train_4k"])
        assert specs["batch"]["tokens"].shape == (256, 4096)
        assert specs["batch"]["labels"].dtype == jnp.int32

    def test_train_structs_vlm(self):
        cfg = dryrun_config(get_config("paligemma-3b"))
        specs = input_specs(cfg, SHAPES["train_4k"])
        assert specs["batch"]["patch_embeds"].shape == (256, 256, 1152)
        assert specs["batch"]["tokens"].shape == (256, 4096 - 256)

    def test_train_structs_audio(self):
        cfg = dryrun_config(get_config("hubert-xlarge"))
        specs = input_specs(cfg, SHAPES["train_4k"])
        assert specs["batch"]["features"].shape == (256, 4096, 512)

    def test_decode_structs_have_caches(self):
        cfg = dryrun_config(get_config("gemma-2b"))
        specs = input_specs(cfg, SHAPES["decode_32k"])
        assert specs["tokens"].shape == (128,)
        assert specs["pos"].shape == ()
        leaves = jax.tree_util.tree_leaves(specs["caches"])
        assert leaves and all(hasattr(l, "shape") for l in leaves)

    def test_window_cache_capped(self):
        """SWA caches are O(window), not O(seq): the long_500k enabler."""
        cfg = dryrun_config(get_config("h2o-danube-1.8b"))
        specs = input_specs(cfg, SHAPES["long_500k"])
        k_shapes = [l.shape for p, l in
                    jax.tree_util.tree_leaves_with_path(specs["caches"])
                    if getattr(p[-1], "key", None) == "k"]
        assert k_shapes and all(s[2] == cfg.sliding_window for s in k_shapes)

    def test_rwkv_state_o1(self):
        cfg = dryrun_config(get_config("rwkv6-1.6b"))
        specs = input_specs(cfg, SHAPES["long_500k"])
        total = sum(l.size for l in jax.tree_util.tree_leaves(specs["caches"]))
        # O(1) in seq: state bytes independent of the 524288 context
        assert total < 50e6

    def test_dryrun_config_is_bf16_remat(self):
        cfg = dryrun_config(get_config("smollm-135m"))
        assert cfg.param_dtype == "bfloat16" and cfg.remat


class TestActiveParams:
    def test_dense_equals_total(self):
        from repro.launch.dryrun import active_param_count
        from repro.models import init_params, param_count
        cfg = get_config("smollm-135m").reduced()
        assert active_param_count(cfg) == param_count(
            init_params(jax.random.key(0), cfg))

    def test_moe_counts_topk_fraction(self):
        import dataclasses
        from repro.launch.dryrun import active_param_count
        from repro.models import init_params, param_count
        base = get_config("deepseek-moe-16b").reduced()
        # reduced() clamps to 4 experts top-4 (frac 1): widen to top-1 of 4
        cfg = dataclasses.replace(base, moe=dataclasses.replace(base.moe, top_k=1))
        total = param_count(init_params(jax.random.key(0), cfg))
        active = active_param_count(cfg)
        assert active < total
        frac = cfg.moe.top_k / cfg.moe.n_experts
        assert total * frac <= active  # non-expert params keep it above frac


class TestHW:
    def test_v5e_constants(self):
        v5e = HW[V5E]
        assert v5e.flops_bf16 == 197e12
        assert v5e.hbm_bw == 819e9
        assert v5e.hbm_bytes == 16 * 2**30
        assert v5e.ici_bw == 50e9
        assert peaks(V5E) is v5e

    def test_unknown_device_kind_is_an_error(self):
        with pytest.raises(ValueError, match="no peak rates"):
            peaks(jax.devices()[0].device_kind)  # the CPU: not a chip


_CACHE_PROBE = r"""
import jax
from repro.launch.compile_cache import DEFAULT_CACHE_DIR, setup_compile_cache
print(setup_compile_cache() == (jax.config.jax_compilation_cache_dir or ""),
      setup_compile_cache() == DEFAULT_CACHE_DIR)
"""


class TestCompileCache:
    """In a child process: the cache directory is global JAX state."""

    @pytest.mark.parametrize("env_dir,expect", [
        (None, "True True"), ("/nonexistent/jax-cache", "True False")])
    def test_cache_dir(self, env_dir, expect):
        import os
        import subprocess
        import sys
        env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if env_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                             cwd=root, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == expect

    def test_import_leaves_cache_off(self):
        import repro.launch.compile_cache  # noqa: F401
        import repro.launch.tune  # noqa: F401
        assert jax.config.jax_compilation_cache_dir is None


class TestTuneLauncher:
    @pytest.mark.parametrize("pool_args", [[], ["--total-devices", "1"]],
                             ids=["host-devices", "virtual"])
    def test_exit_nonzero_without_results(self, pool_args, tmp_path):
        """A trial wider than the pool never runs: the launcher must fail."""
        import os
        import subprocess
        import sys
        env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path))
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.tune", "--reduced",
             "--num-samples", "1", "--max-iters", "1", "--batch", "2",
             "--seq-len", "16", "--devices-per-trial", "2", *pool_args],
            env=env, cwd=root, capture_output=True, text=True, timeout=300)
        assert out.returncode == 1, out.stdout + out.stderr
        assert "no trial produced a result" in out.stdout
