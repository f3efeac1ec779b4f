"""Model zoo correctness: decode==forward consistency, chunked-scan
equivalence, family-specific behaviours."""
import contextlib
import dataclasses
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import (ModelConfig, MoEConfig, decode_step, forward_encode,
                          forward_train, init_params, layers, prefill)
from repro.models.rwkv6 import _wkv_chunked, _wkv_step
from repro.models.rglru import _rglru_scan
from repro.models.moe import apply_moe_layer, init_moe_layer
from repro.dist.sharding import activation_policy

V = 96


def lm_cfg(**kw):
    base = dict(arch_id="t", family="dense", n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=V)
    base.update(kw)
    return ModelConfig(**base).validate()


def check_decode_matches_forward(cfg, S=17, n_decode=4, atol=2e-3):
    params = init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, S + n_decode), 0, cfg.vocab_size)
    full = forward_encode(params, {"tokens": toks}, cfg)
    logits, caches = prefill(params, {"tokens": toks[:, :S]}, cfg, max_len=S + n_decode)
    errs = [float(jnp.abs(logits - full[:, S - 1]).max())]
    for i in range(n_decode):
        logits, caches = decode_step(params, caches, toks[:, S + i],
                                     jnp.asarray(S + i), cfg)
        errs.append(float(jnp.abs(logits - full[:, S + i]).max()))
    assert max(errs) < atol, f"decode diverges from teacher-forcing: {errs}"


class TestDecodeConsistency:
    def test_dense_gqa(self):
        check_decode_matches_forward(lm_cfg())

    def test_dense_mqa_headdim(self):
        check_decode_matches_forward(lm_cfg(n_heads=2, n_kv_heads=1, head_dim=48))

    def test_sliding_window(self):
        check_decode_matches_forward(lm_cfg(sliding_window=8), S=21)

    def test_qkv_bias_layernorm(self):
        check_decode_matches_forward(lm_cfg(qkv_bias=True, norm="layernorm"))

    def test_rwkv6(self):
        check_decode_matches_forward(lm_cfg(
            family="ssm", n_heads=2, rwkv_head_dim=32))

    def test_hybrid_rglru(self):
        check_decode_matches_forward(lm_cfg(
            family="hybrid", n_layers=5, n_kv_heads=1,
            block_pattern=("rglru", "rglru", "local_attn"),
            sliding_window=8, rglru_d_rnn=64))

    def test_geglu_tied_scaled(self):
        check_decode_matches_forward(lm_cfg(
            activation="geglu", tie_embeddings=True, embedding_scale=True))


class TestRWKVChunking:
    @pytest.mark.parametrize("chunk", [1, 7, 16, 37, 64])
    def test_chunked_equals_sequential(self, chunk):
        key = jax.random.key(3)
        B, S, H, N = 2, 37, 2, 8
        r, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, S, H, N)) * 0.5
                   for i in range(3))
        logw = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 4), (B, S, H, N)) * 0.5 - 2)
        u = jax.random.normal(jax.random.fold_in(key, 5), (H, N)) * 0.3
        s0 = jax.random.normal(jax.random.fold_in(key, 6), (B, H, N, N)) * 0.2
        ys, st = [], s0
        for t in range(S):
            y, st = _wkv_step(r[:, t], k[:, t], v[:, t], logw[:, t], u, st)
            ys.append(y)
        y_ref = jnp.stack(ys, 1)
        y_c, st_c = _wkv_chunked(r, k, v, logw, u, s0, chunk)
        np.testing.assert_allclose(y_c, y_ref, atol=1e-4)
        np.testing.assert_allclose(st_c, st, atol=1e-4)

    def test_chunked_gradient_equals_sequential(self):
        """Each chunk is recomputed in the backward pass; the gradient is
        the single-step recurrence's."""
        key = jax.random.key(4)
        B, S, H, N = 1, 21, 2, 8
        r, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, S, H, N)) * 0.5
                   for i in range(3))
        logw = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 4), (B, S, H, N)) * 0.5 - 2)
        u = jax.random.normal(jax.random.fold_in(key, 5), (H, N)) * 0.3
        s0 = jnp.zeros((B, H, N, N))
        w = jax.random.normal(jax.random.fold_in(key, 6), (B, S, H, N))

        def sequential(r, k, v, logw, u):
            ys, st = [], s0
            for t in range(S):
                y, st = _wkv_step(r[:, t], k[:, t], v[:, t], logw[:, t], u, st)
                ys.append(y)
            return jnp.sum(jnp.stack(ys, 1) * w)

        def chunked(r, k, v, logw, u):
            return jnp.sum(_wkv_chunked(r, k, v, logw, u, s0, 8)[0] * w)

        args = (r, k, v, logw, u)
        want = jax.grad(sequential, argnums=range(5))(*args)
        got = jax.grad(chunked, argnums=range(5))(*args)
        for g, e in zip(got, want):
            np.testing.assert_allclose(g, e, atol=1e-4)

    def test_decay_cap_keeps_huge_log_decay_finite(self):
        """w0 far above the cap: the decay is 0, not NaN."""
        cfg = lm_cfg(family="ssm", n_heads=2, rwkv_head_dim=32, norm="layernorm")
        params = init_params(jax.random.key(0), cfg)
        params["stack"][0]["blocks"][0]["tm"]["w0"] = jnp.full_like(
            params["stack"][0]["blocks"][0]["tm"]["w0"], 200.0)
        toks = jax.random.randint(jax.random.key(1), (2, 40), 0, V)
        loss, grads = jax.value_and_grad(
            lambda p: forward_train(p, {"tokens": toks, "labels": toks}, cfg)[0])(params)
        assert np.isfinite(float(loss))
        assert all(bool(jnp.isfinite(g).all()) for g in jax.tree_util.tree_leaves(grads))

    def test_scopes_name_the_time_mix_wkv_and_channel_mix(self):
        """The compiled train step's ops carry the named scopes, forward and
        backward, for a device trace to attribute."""
        cfg = lm_cfg(family="ssm", n_layers=1, n_heads=2, rwkv_head_dim=32,
                     norm="layernorm")
        params = init_params(jax.random.key(0), cfg)
        toks = jnp.zeros((1, 16), jnp.int32)
        grad = jax.jit(jax.grad(
            lambda p: forward_train(p, {"tokens": toks, "labels": toks}, cfg)[0]))
        ops = set(re.findall(r'op_name="([^"]*)"', grad.lower(params).compile().as_text()))
        for scope in ("rwkv6.time_mix/", "rwkv6.time_mix/rwkv6.wkv/", "rwkv6.channel_mix/"):
            assert any(scope in op and "transpose(" not in op for op in ops), scope
            assert any(scope in op and "transpose(" in op for op in ops), scope


class TestNorms:
    @pytest.mark.parametrize("norm", ["layernorm", "rmsnorm"])
    def test_norm_eps_is_the_configs(self, norm):
        from repro.models.layers import apply_norm, init_norm

        x = jax.random.normal(jax.random.key(0), (3, 64)) * 1e-2
        out = {}
        for eps in (1e-6, 1e-3):
            cfg = lm_cfg(norm=norm, norm_eps=eps)
            out[eps] = apply_norm(init_norm(64, cfg), x, cfg)
            mean = x.mean(-1, keepdims=True) if norm == "layernorm" else 0.0
            var = jnp.square(x - mean).mean(-1, keepdims=True)
            np.testing.assert_allclose(out[eps], (x - mean) / jnp.sqrt(var + eps),
                                       rtol=1e-4, atol=1e-5)
        assert not np.allclose(out[1e-6], out[1e-3])

    def test_default_eps_is_1e6(self):
        assert ModelConfig.norm_eps == 1e-6

    def test_ln0_only_in_rwkv(self):
        """RWKV normalises the embeddings (``ln0``) before block 0; no
        other family has the parameter."""
        ssm = lm_cfg(family="ssm", n_heads=2, rwkv_head_dim=32, norm="layernorm")
        assert "ln0" in init_params(jax.random.key(0), ssm)
        assert "ln0" not in init_params(jax.random.key(0), lm_cfg())
        params = init_params(jax.random.key(0), ssm)
        toks = jax.random.randint(jax.random.key(1), (2, 12), 0, V)
        base = forward_encode(params, {"tokens": toks}, ssm)
        params["ln0"]["bias"] = params["ln0"]["bias"] + 0.5
        assert not np.allclose(forward_encode(params, {"tokens": toks}, ssm), base)


class TestMatmulPrecision:
    @staticmethod
    def _dot_precisions(cfg):
        """The precision attribute of every dot in the lowered train
        gradient (None where a dot has none, JAX's default)."""
        params = init_params(jax.random.key(0), cfg)
        toks = jnp.zeros((1, 16), jnp.int32)
        grad = jax.jit(jax.grad(
            lambda p: forward_train(p, {"tokens": toks, "labels": toks}, cfg)[0]))
        dots = [ln for ln in grad.lower(params).as_text().splitlines()
                if "stablehlo.dot_general" in ln]
        assert dots
        return {(re.search(r"precision = \[(\w+)", ln) or [None, None])[1] for ln in dots}

    @pytest.mark.parametrize("family", ["dense", "ssm"])
    def test_unset_leaves_jax_default(self, family):
        cfg = lm_cfg(family=family, n_heads=2, rwkv_head_dim=32, norm="layernorm")
        assert self._dot_precisions(cfg) <= {None, "DEFAULT"}

    def test_highest_reaches_every_dot_forward_and_backward(self):
        cfg = lm_cfg(family="ssm", n_heads=2, rwkv_head_dim=32, norm="layernorm",
                     matmul_precision="highest")
        assert self._dot_precisions(cfg) == {"HIGHEST"}

    @pytest.mark.parametrize("precision", ["high", "fp64"])
    def test_other_precisions_are_refused(self, precision):
        with pytest.raises(ValueError, match="matmul_precision"):
            lm_cfg(matmul_precision=precision)


class TestAttentionDispatch:
    @pytest.mark.parametrize("backend,partitioned,sq,sk,path", [
        ("tpu", False, 2048, 2048, "flash"),    # the train step on a chip
        ("tpu", False, 7, 4096, "flash"),       # prefill
        ("tpu", False, 1, 2048, "naive"),       # decode
        ("cpu", False, 2048, 2048, "naive"),
        ("cpu", False, 4096, 4096, "chunked"),
        ("cpu", False, 1, 4096, "naive"),
        ("tpu", True, 1, 2048, "naive"),        # a step XLA partitions
        ("tpu", True, 2048, 2048, "naive"),
        ("tpu", True, 4096, 4096, "chunked"),
        ("tpu", True, 1, 4096, "naive"),
        ("cpu", False, 16, 2048, "naive"),      # the key boundary
        ("cpu", False, 16, 2049, "chunked"),
        ("tpu", True, 16, 2049, "chunked"),
    ])
    def test_path_from_backend_and_shape(self, monkeypatch, backend,
                                         partitioned, sq, sk, path):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        policy = (activation_policy(SimpleNamespace(size=4)) if partitioned
                  else contextlib.nullcontext())
        with policy:
            assert layers.attention_path(sq, sk) == path

    def test_partitioned_step_keeps_the_jnp_path(self, monkeypatch):
        """XLA cannot partition a Pallas kernel: under an activation policy
        over several devices, the step stays on the jnp paths."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with activation_policy(SimpleNamespace(size=4)):
            assert layers.attention_path(2048, 2048) == "naive"
        with activation_policy(SimpleNamespace(size=1)):
            assert layers.attention_path(2048, 2048) == "flash"

    def test_scopes_name_the_path(self, monkeypatch):
        monkeypatch.setattr(layers, "attention_path", lambda sq, sk: "flash")
        cfg = lm_cfg(n_layers=1)
        params = init_params(jax.random.key(0), cfg)
        toks = jnp.zeros((1, 16), jnp.int32)
        grad = jax.jit(jax.grad(
            lambda p: forward_train(p, {"tokens": toks, "labels": toks}, cfg)[0]))
        text = grad.lower(params).as_text(debug_info=True)
        assert "attention.flash" in text and "attention.naive" not in text

    def test_reduced_smollm_kernel_matches_naive(self, monkeypatch):
        """A reduced SmolLM stack (GQA, remat): loss and gradients through the
        flash kernel (interpret mode, bfloat16 MXU operands at the default
        precision) against the naive path in float32."""
        from repro.configs import get_config

        cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                                  remat=True)
        params = init_params(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (2, 128), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}

        def loss_grad(c):
            return jax.jit(jax.value_and_grad(
                lambda p: forward_train(p, batch, c)[0]))(params)

        loss, grads = loss_grad(cfg)
        monkeypatch.setattr(layers, "attention_path", lambda sq, sk: "flash")
        loss_k, grads_k = loss_grad(cfg)
        assert abs(float(loss_k) - float(loss)) < 1e-3 * float(loss)
        norms = [float(jnp.linalg.norm(g)) for g in jax.tree_util.tree_leaves(grads)]
        floor = float(np.median(norms))
        for g, gk, n in zip(jax.tree_util.tree_leaves(grads),
                            jax.tree_util.tree_leaves(grads_k), norms):
            assert float(jnp.linalg.norm(gk - g)) < 2e-2 * max(n, floor)


class TestRGLRU:
    def test_associative_scan_matches_loop(self):
        key = jax.random.key(0)
        B, S, R = 2, 33, 8
        a = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 1), (B, S, R)))
        b = jax.random.normal(jax.random.fold_in(key, 2), (B, S, R)) * 0.3
        h0 = jax.random.normal(jax.random.fold_in(key, 3), (B, R)) * 0.1
        h = _rglru_scan(a, b, h0)
        hh, out = h0, []
        for t in range(S):
            hh = a[:, t] * hh + b[:, t]
            out.append(hh)
        np.testing.assert_allclose(h, jnp.stack(out, 1), atol=1e-5)

    def test_state_bounded(self):
        """|h| stays bounded: a in (0,1) with sqrt(1-a^2) input normalization."""
        cfg = lm_cfg(family="hybrid", n_layers=3, n_kv_heads=1,
                     block_pattern=("rglru", "rglru", "local_attn"),
                     sliding_window=8, rglru_d_rnn=64)
        params = init_params(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (2, 64), 0, V)
        logits = forward_encode(params, {"tokens": toks}, cfg)
        assert jnp.isfinite(logits).all()


class TestMoE:
    def _cfg(self, **kw):
        moe = MoEConfig(n_experts=4, top_k=2, d_expert=32, group_size=16, **kw)
        return lm_cfg(family="moe", moe=moe)

    def test_output_shape_and_aux(self):
        cfg = self._cfg()
        p = init_moe_layer(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(1), (2, 32, 64))
        out, aux = apply_moe_layer(p, x, cfg)
        assert out.shape == x.shape
        assert jnp.isfinite(out).all() and jnp.isfinite(aux)
        # Switch aux loss is ~1 for near-uniform routing at init
        assert 0.5 < float(aux) < 4.0

    def test_shared_experts_add(self):
        cfg = self._cfg(n_shared=1)
        p = init_moe_layer(jax.random.key(0), cfg)
        assert "shared" in p
        x = jax.random.normal(jax.random.key(1), (2, 32, 64))
        out, _ = apply_moe_layer(p, x, cfg)
        assert jnp.isfinite(out).all()

    def test_capacity_drops_dont_nan(self):
        """Tiny capacity forces token drops; output must stay finite."""
        moe = MoEConfig(n_experts=4, top_k=2, d_expert=16, group_size=16,
                        capacity_factor=0.25)
        cfg = lm_cfg(family="moe", moe=moe)
        p = init_moe_layer(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(1), (1, 64, 64))
        out, aux = apply_moe_layer(p, x, cfg)
        assert jnp.isfinite(out).all()

    def test_moe_gradients_flow_to_experts(self):
        cfg = self._cfg()
        p = init_moe_layer(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(1), (1, 32, 64))

        def loss(p):
            out, aux = apply_moe_layer(p, x, cfg)
            return (out ** 2).mean() + 0.01 * aux

        g = jax.grad(loss)(p)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(l))
                             for l in jax.tree_util.tree_leaves(g)))
        assert float(gnorm) > 0


class TestFrontendStubs:
    def test_audio_masked_loss(self):
        cfg = lm_cfg(family="audio", encoder_only=True, frontend="audio_stub",
                     frontend_dim=24, n_heads=4, n_kv_heads=4)
        params = init_params(jax.random.key(0), cfg)
        feats = jax.random.normal(jax.random.key(1), (2, 32, 24))
        labels = jax.random.randint(jax.random.key(2), (2, 32), 0, V)
        mask = (jnp.arange(32) % 3 == 0)[None, :] * jnp.ones((2, 1))
        loss, m = forward_train(params, {"features": feats, "labels": labels,
                                         "loss_mask": mask}, cfg)
        assert jnp.isfinite(loss)

    def test_vlm_prefix_excluded_from_loss(self):
        cfg = lm_cfg(family="vlm", n_kv_heads=1, frontend="vision_stub",
                     frontend_dim=24, n_prefix_embeds=4)
        params = init_params(jax.random.key(0), cfg)
        pe = jax.random.normal(jax.random.key(1), (2, 4, 24))
        toks = jax.random.randint(jax.random.key(2), (2, 12), 0, V)
        loss, m = forward_train(params, {"patch_embeds": pe, "tokens": toks,
                                         "labels": toks}, cfg)
        assert jnp.isfinite(loss)


class TestEncoderBidirectional:
    def test_encoder_sees_future(self):
        """Bidirectional: changing a future token changes an earlier logit."""
        cfg = lm_cfg(encoder_only=True)
        params = init_params(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (1, 16), 0, V)
        toks2 = toks.at[0, 12].set((toks[0, 12] + 1) % V)
        a = forward_encode(params, {"tokens": toks}, cfg)
        b = forward_encode(params, {"tokens": toks2}, cfg)
        assert float(jnp.abs(a[0, 3] - b[0, 3]).max()) > 0

    def test_causal_does_not_see_future(self):
        cfg = lm_cfg()
        params = init_params(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (1, 16), 0, V)
        toks2 = toks.at[0, 12].set((toks[0, 12] + 1) % V)
        a = forward_encode(params, {"tokens": toks}, cfg)
        b = forward_encode(params, {"tokens": toks2}, cfg)
        np.testing.assert_allclose(a[0, :12], b[0, :12], atol=1e-6)
