"""Coverage for the §Perf-added features: pallas model paths, padded vocab,
dp_only strategy, scatter MoE, bf16 moments, batch-spec prefix fallback."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.models import (ModelConfig, MoEConfig, forward_encode, forward_train,
                          init_params)
from repro.models.moe import apply_moe_layer, init_moe_layer
from repro.train import adamw, make_train_state, make_train_step

V = 100


def lm_cfg(**kw):
    base = dict(arch_id="t", family="dense", n_layers=2, d_model=64,
                n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=V)
    base.update(kw)
    return ModelConfig(**base).validate()


class TestPallasModelPaths:
    """Models with kernel_impl='pallas' / attn_impl='pallas' (interpret mode)
    must match the jnp paths — kernels as a real system layer."""

    def _compare(self, cfg_jnp, cfg_pl, atol=2e-3):
        params = init_params(jax.random.key(0), cfg_jnp)
        toks = jax.random.randint(jax.random.key(1), (2, 64), 0, V)
        a = forward_encode(params, {"tokens": toks}, cfg_jnp)
        b = forward_encode(params, {"tokens": toks}, cfg_pl)
        np.testing.assert_allclose(a, b, atol=atol)

    # At "highest" both paths compute in float32; at the default the kernel
    # rounds its MXU operands to bfloat16, as a TPU does (tests/test_models.py).
    def test_flash_attention_in_model(self):
        cfg = lm_cfg(attn_impl="naive", matmul_precision="highest")
        self._compare(cfg, dataclasses.replace(cfg, attn_impl="pallas"))

    def test_flash_attention_swa_in_model(self):
        cfg = lm_cfg(attn_impl="naive", sliding_window=16, matmul_precision="highest")
        self._compare(cfg, dataclasses.replace(cfg, attn_impl="pallas"))

    def test_rwkv6_pallas_scan_in_model(self):
        cfg = lm_cfg(family="ssm", n_heads=2, rwkv_head_dim=32)
        self._compare(cfg, dataclasses.replace(cfg, kernel_impl="pallas"))

    def test_rglru_pallas_scan_in_model(self):
        cfg = lm_cfg(family="hybrid", n_layers=3, n_kv_heads=1,
                     block_pattern=("rglru", "rglru", "local_attn"),
                     sliding_window=16, rglru_d_rnn=64)
        self._compare(cfg, dataclasses.replace(cfg, kernel_impl="pallas"))


class TestPaddedVocab:
    def test_loss_matches_unpadded(self):
        import math
        cfg = lm_cfg(padded_vocab=128)
        params = init_params(jax.random.key(0), cfg)
        assert params["embed"]["tok"].shape[0] == 128
        toks = jax.random.randint(jax.random.key(1), (2, 32), 0, V)
        loss, m = forward_train(params, {"tokens": toks, "labels": toks}, cfg)
        # at init, CE ~= ln(real vocab), NOT ln(padded vocab)
        assert abs(float(loss) - math.log(V)) < 0.4

    def test_argmax_never_in_padding(self):
        cfg = lm_cfg(padded_vocab=128)
        params = init_params(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (2, 32), 0, V)
        logits = forward_encode(params, {"tokens": toks}, cfg)
        assert int(jnp.argmax(logits, -1).max()) < V

    def test_trains(self):
        cfg = lm_cfg(padded_vocab=128)
        opt = adamw(3e-3)
        state = make_train_state(jax.random.key(0), cfg, opt)
        step = jax.jit(make_train_step(cfg, opt))
        toks = jax.random.randint(jax.random.key(1), (4, 32), 0, V)
        batch = {"tokens": toks, "labels": toks}
        l0 = None
        for _ in range(10):
            state, m = step(state, batch)
            l0 = l0 or float(m["loss"])
        assert float(m["loss"]) < l0


class TestScatterMoE:
    def test_matches_einsum_no_drops(self):
        moe = MoEConfig(n_experts=4, top_k=2, d_expert=32, group_size=32,
                        capacity_factor=4.0)
        cfg_e = lm_cfg(family="moe", moe=moe)
        cfg_s = dataclasses.replace(
            cfg_e, moe=dataclasses.replace(moe, impl="scatter"))
        p = init_moe_layer(jax.random.key(0), cfg_e)
        x = jax.random.normal(jax.random.key(1), (2, 64, 64))
        out_e, aux_e = apply_moe_layer(p, x, cfg_e)
        out_s, aux_s = apply_moe_layer(p, x, cfg_s)
        np.testing.assert_allclose(out_e, out_s, atol=1e-4, rtol=1e-3)
        np.testing.assert_allclose(aux_e, aux_s, atol=1e-5)

    def test_scatter_with_drops_finite_grads(self):
        moe = MoEConfig(n_experts=4, top_k=2, d_expert=16, group_size=16,
                        capacity_factor=0.5, impl="scatter")
        cfg = lm_cfg(family="moe", moe=moe)
        p = init_moe_layer(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(1), (1, 64, 64))

        def loss(p):
            o, a = apply_moe_layer(p, x, cfg)
            return (o ** 2).mean() + 0.01 * a

        g = jax.grad(loss)(p)
        for leaf in jax.tree_util.tree_leaves(g):
            assert jnp.isfinite(leaf).all()

    def test_rank_within_expert(self):
        from repro.models.moe import _rank_within_expert
        e = jnp.asarray([[2, 0, 2, 1, 0, 2]])
        rank = _rank_within_expert(e)
        np.testing.assert_array_equal(rank[0], [0, 0, 1, 0, 1, 2])


class TestBF16Moments:
    def test_state_dtype_and_convergence(self):
        cfg = lm_cfg()
        opt = adamw(3e-3, moment_dtype="bfloat16")
        state = make_train_state(jax.random.key(0), cfg, opt)
        for leaf in jax.tree_util.tree_leaves(state.opt_state["m"]):
            assert leaf.dtype == jnp.bfloat16
        step = jax.jit(make_train_step(cfg, opt))
        toks = jax.random.randint(jax.random.key(1), (4, 32), 0, V)
        batch = {"tokens": toks, "labels": toks}
        losses = []
        for _ in range(15):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] * 0.8


class TestStrategyAndBatchSpecs:
    def test_dp_only_replicates_tp(self):
        from repro.dist.sharding import sharding_strategy, spec_for
        class MockMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}
        class K:
            def __init__(self, key):
                self.key = key
        with sharding_strategy("dp_only"):
            spec = spec_for([K("mlp"), K("w_gate")], (64, 128), MockMesh())
            assert spec == P(("data",), None)  # no model-axis sharding

    def test_batch_prefix_fallback(self):
        from repro.dist.sharding import batch_specs, sharding_strategy
        class MockMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}
        batch = {"tokens": jax.ShapeDtypeStruct((32, 8), np.int32)}
        with sharding_strategy("dp_only"):
            specs = batch_specs(batch, MockMesh())
        # 32 doesn't divide 256 but divides 16: shard over ("data",)
        assert specs["tokens"] == P(("data",), None)

    def test_unknown_strategy_rejected(self):
        from repro.dist.sharding import sharding_strategy
        with pytest.raises(ValueError):
            with sharding_strategy("nope"):
                pass
