"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, assert_allclose.

Kernels execute in interpret mode on CPU (the kernel body is what's tested;
tiling is TPU-side).  Hypothesis drives shape fuzzing on top of the explicit
parametrized sweeps.
"""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref

fa = importlib.import_module("repro.kernels.flash_attention")

KEY = jax.random.key(0)


def randn(i, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(jax.random.fold_in(KEY, i), shape) * scale).astype(dtype)


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_use_interpret_only_on_cpu(monkeypatch, backend, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="interpreted on cpu"):
            ops.use_interpret()
    else:
        assert ops.use_interpret() is interpret


class TestFlashAttention:
    @pytest.mark.parametrize("B,Sq,Sk,H,K,hd", [
        (1, 64, 64, 1, 1, 32),       # minimal MHA
        (2, 128, 128, 4, 2, 64),     # GQA
        (2, 96, 160, 4, 1, 64),      # MQA, padded odd sizes
        (1, 256, 256, 8, 8, 32),     # full heads
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shape_dtype_sweep(self, B, Sq, Sk, H, K, hd, dtype):
        q = randn(1, (B, Sq, H, hd), dtype)
        k = randn(2, (B, Sk, K, hd), dtype)
        v = randn(3, (B, Sk, K, hd), dtype)
        qp = jnp.broadcast_to(jnp.arange(Sk - Sq, Sk)[None], (B, Sq))
        kp = jnp.broadcast_to(jnp.arange(Sk)[None], (B, Sk))
        exp = ref.flash_attention_ref(q, k, v, qp, kp, causal=True)
        # "highest" keeps float32 operands; at the default the MXU operands
        # are bfloat16 (what JAX's default does to a float32 matmul on a TPU).
        for precision in ("highest", None):
            out = ops.flash_attention(q, k, v, qp, kp, causal=True,
                                      precision=precision, block_q=64, block_k=64)
            atol = 2e-5 if dtype == jnp.float32 and precision else 2e-2
            np.testing.assert_allclose(np.asarray(out, np.float32),
                                       np.asarray(exp, np.float32), atol=atol)

    @pytest.mark.parametrize("causal,window,softcap", [
        (True, None, None), (False, None, None),
        (True, 32, None), (True, None, 20.0), (True, 16, 20.0),
    ])
    def test_mask_variants(self, causal, window, softcap):
        B, S, H, K, hd = 2, 128, 2, 2, 32
        q, k, v = (randn(i, (B, S, H if i == 1 else K, hd)) for i in (1, 2, 3))
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        out = ops.flash_attention(q, k, v, pos, pos, causal=causal,
                                  window=window, softcap=softcap,
                                  precision="highest", block_q=64, block_k=64)
        exp = ref.flash_attention_ref(q, k, v, pos, pos, causal=causal,
                                      window=window, softcap=softcap)
        np.testing.assert_allclose(out, exp, atol=2e-5)

    def test_ring_cache_invalid_slots_masked(self):
        """k_pos == -1 slots (unfilled ring entries) contribute nothing."""
        B, Sq, Sk, H, hd = 1, 64, 128, 2, 32
        q = randn(1, (B, Sq, H, hd))
        k = randn(2, (B, Sk, H, hd))
        v = randn(3, (B, Sk, H, hd))
        qp = jnp.broadcast_to(jnp.arange(100, 100 + Sq)[None], (B, Sq))
        kp_full = jnp.broadcast_to(jnp.arange(36, 36 + Sk)[None], (B, Sk))
        kp_holes = kp_full.at[:, 64:].set(-1)
        out = ops.flash_attention(q, k, v, qp, kp_holes, causal=True,
                                  precision="highest", block_q=64, block_k=64)
        exp = ref.flash_attention_ref(q, k[:, :64], v[:, :64], qp,
                                      kp_full[:, :64], causal=True)
        np.testing.assert_allclose(out, exp, atol=2e-5)

    def test_decode_single_query(self):
        B, Sk, H, K, hd = 4, 128, 4, 2, 64
        q = randn(1, (B, 1, H, hd))
        k = randn(2, (B, Sk, K, hd))
        v = randn(3, (B, Sk, K, hd))
        qp = jnp.full((B, 1), Sk - 1)
        kp = jnp.broadcast_to(jnp.arange(Sk)[None], (B, Sk))
        out = ops.flash_attention(q, k, v, qp, kp, causal=True,
                                  precision="highest")
        exp = ref.flash_attention_ref(q, k, v, qp, kp, causal=True)
        np.testing.assert_allclose(out, exp, atol=2e-5)

    @given(st.integers(1, 3), st.integers(1, 4), st.integers(8, 80))
    @settings(max_examples=10, deadline=None)
    def test_fuzz_shapes(self, B, K, Sq):
        H, hd, Sk = K * 2, 16, 96
        q = randn(1, (B, Sq, H, hd))
        k = randn(2, (B, Sk, K, hd))
        v = randn(3, (B, Sk, K, hd))
        qp = jnp.broadcast_to(jnp.arange(Sk - Sq, Sk)[None], (B, Sq))
        kp = jnp.broadcast_to(jnp.arange(Sk)[None], (B, Sk))
        out = ops.flash_attention(q, k, v, qp, kp, precision="highest",
                                  block_q=32, block_k=32)
        exp = ref.flash_attention_ref(q, k, v, qp, kp)
        np.testing.assert_allclose(out, exp, atol=3e-5)

    # (causal, window, softcap, G, Sq, Sk, holes): masks, GQA groups, ragged
    # lengths that pad to the 64-blocks, and ring-cache holes.
    GRAD_CASES = [
        (True, None, None, 1, 128, 128, False),
        (True, None, None, 3, 128, 128, False),
        (False, None, None, 3, 128, 128, False),
        (True, 32, None, 3, 128, 128, False),
        (True, None, 20.0, 1, 128, 128, False),
        (True, None, None, 3, 96, 160, False),
        (True, None, None, 1, 64, 128, True),
    ]

    @staticmethod
    def _grad_inputs(G, Sq, Sk, holes, B=2, K=1, hd=32):
        q = randn(1, (B, Sq, K * G, hd))
        k = randn(2, (B, Sk, K, hd))
        v = randn(3, (B, Sk, K, hd))
        qp = jnp.broadcast_to(jnp.arange(Sk - Sq, Sk)[None], (B, Sq))
        kp = jnp.broadcast_to(jnp.arange(Sk)[None], (B, Sk))
        if holes:  # unfilled ring slots, a whole key block among them
            kp = kp.at[:, 40:110].set(-1)
        cot = randn(4, q.shape)  # a cotangent that weighs every output
        return (q, k, v), qp, kp, cot

    @pytest.mark.parametrize("causal,window,softcap,G,Sq,Sk,holes", GRAD_CASES)
    @pytest.mark.parametrize("precision", ["highest", None])
    def test_gradients(self, causal, window, softcap, G, Sq, Sk, holes, precision):
        """jax.grad through the kernel's custom VJP against the grad of the
        plain reference at HIGHEST: tight at "highest", within bfloat16
        operand rounding at the default."""
        qkv, qp, kp, cot = self._grad_inputs(G, Sq, Sk, holes)
        kw = dict(causal=causal, window=window, softcap=softcap)

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v, qp, kp, **kw) * cot)

        got = jax.grad(loss(functools.partial(
            ops.flash_attention, precision=precision, block_q=64, block_k=64)),
            (0, 1, 2))(*qkv)
        with jax.default_matmul_precision("highest"):
            want = jax.grad(loss(ref.flash_attention_ref), (0, 1, 2))(*qkv)
        rtol = 2e-6 if precision else 2e-2
        for name, g, w in zip("qkv", got, want):
            err = float(jnp.abs(g - w).max() / jnp.abs(w).max())
            assert err < rtol, f"d{name}: {err:.2e}"

    def test_causal_block_skipping_matches_unskipped(self, monkeypatch):
        """Causal blocks above the diagonal do no work; with every block run
        (masked element by element) the output and gradients are the same."""
        qkv, qp, kp, cot = self._grad_inputs(3, 256, 256, False)

        def run():
            f = lambda q, k, v: ops.flash_attention(q, k, v, qp, kp, block_q=64,
                                                    block_k=64)
            out, vjp = jax.vjp(f, *qkv)
            return (out,) + vjp(cot)

        by_q, by_k = fa._block_spans(
            qp, kp, fa._Static(True, None, None, None, 64, 64, True))
        # (first live, last live) per sequence and block: live up to the
        # diagonal block, and nothing above it.
        want = ([0, 0, 0, 0], [0, 1, 2, 3], [0, 1, 2, 3], [3, 3, 3, 3])
        for got, w in zip(by_q + by_k, want):
            np.testing.assert_array_equal(got, w * 2)
        skipped = run()
        everything = lambda q_pos, k_pos, st: ((jnp.zeros(8, jnp.int32),
                                                jnp.full(8, 3, jnp.int32)),) * 2
        monkeypatch.setattr(fa, "_block_spans", everything)
        for a, b in zip(skipped, run()):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("hd,precision,want", [
        (64, None, 1024), (80, None, 1024), (128, None, 1024), (256, None, 512),
        (512, None, 256), (64, "highest", 512), (256, "highest", 256),
        (1024, "highest", 128)])
    def test_block_sizes_shrink_with_the_head(self, hd, precision, want):
        """A block's VMEM stays what it is at head_dim 128: the block halves
        for each doubling of the head beyond it, and again at "highest"."""
        assert fa.block_sizes(4096, 4096, hd, precision) == (want, want)
        # A shorter length is one block.
        assert fa.block_sizes(want // 2 + 8, 2048, hd, precision) == (
            want // 2 + 8, want)


class TestRWKV6Scan:
    def _inputs(self, B, S, H, N, dtype=jnp.float32):
        r = randn(1, (B, S, H, N), dtype, 0.5)
        k = randn(2, (B, S, H, N), dtype, 0.5)
        v = randn(3, (B, S, H, N), dtype, 0.5)
        logw = -jnp.exp(randn(4, (B, S, H, N), jnp.float32, 0.5) - 2.0)
        u = randn(5, (H, N), jnp.float32, 0.3)
        s0 = randn(6, (B, H, N, N), jnp.float32, 0.2)
        return r, k, v, logw, u, s0

    @pytest.mark.parametrize("B,S,H,N,chunk", [
        (1, 32, 1, 8, 8), (2, 50, 3, 16, 16), (2, 64, 2, 32, 32),
        (1, 100, 2, 16, 64),
    ])
    def test_shape_sweep(self, B, S, H, N, chunk):
        r, k, v, logw, u, s0 = self._inputs(B, S, H, N)
        y, sf = ops.rwkv6_scan(r, k, v, logw, u, s0, chunk=chunk)
        y_ref, sf_ref = ref.rwkv6_scan_ref(r, k, v, logw, u, s0)
        np.testing.assert_allclose(y, y_ref, atol=1e-4)
        np.testing.assert_allclose(sf, sf_ref, atol=1e-4)

    def test_bfloat16_inputs(self):
        r, k, v, logw, u, s0 = self._inputs(2, 32, 2, 16, jnp.bfloat16)
        y, sf = ops.rwkv6_scan(r, k, v, logw, u, s0, chunk=16)
        y_ref, sf_ref = ref.rwkv6_scan_ref(r, k, v, logw, u, s0)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y_ref, np.float32), atol=5e-2)

    def test_state_chaining(self):
        """Running two halves with carried state == one full run."""
        r, k, v, logw, u, s0 = self._inputs(1, 64, 2, 8)
        y_full, s_full = ops.rwkv6_scan(r, k, v, logw, u, s0, chunk=16)
        y1, s_mid = ops.rwkv6_scan(r[:, :32], k[:, :32], v[:, :32],
                                   logw[:, :32], u, s0, chunk=16)
        y2, s_end = ops.rwkv6_scan(r[:, 32:], k[:, 32:], v[:, 32:],
                                   logw[:, 32:], u, s_mid, chunk=16)
        np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full, atol=1e-4)
        np.testing.assert_allclose(s_end, s_full, atol=1e-4)


class TestRGLRUScan:
    @pytest.mark.parametrize("B,S,R,ct,br", [
        (1, 32, 16, 16, 16), (3, 77, 40, 32, 16), (2, 128, 64, 64, 64),
    ])
    def test_shape_sweep(self, B, S, R, ct, br):
        a = jax.nn.sigmoid(randn(7, (B, S, R)))
        b = randn(8, (B, S, R), scale=0.3)
        h0 = randn(9, (B, R), scale=0.2)
        h = ops.rglru_scan(a, b, h0, chunk_t=ct, block_r=br)
        np.testing.assert_allclose(h, ref.rglru_scan_ref(a, b, h0), atol=1e-5)

    def test_no_initial_state(self):
        a = jax.nn.sigmoid(randn(7, (2, 40, 8)))
        b = randn(8, (2, 40, 8), scale=0.3)
        h = ops.rglru_scan(a, b, None, chunk_t=16, block_r=8)
        np.testing.assert_allclose(h, ref.rglru_scan_ref(a, b, None), atol=1e-5)

    @given(st.integers(1, 3), st.integers(5, 60), st.integers(4, 24))
    @settings(max_examples=10, deadline=None)
    def test_fuzz(self, B, S, R):
        a = jax.nn.sigmoid(randn(7, (B, S, R)))
        b = randn(8, (B, S, R), scale=0.5)
        h = ops.rglru_scan(a, b, None, chunk_t=16, block_r=8)
        np.testing.assert_allclose(h, ref.rglru_scan_ref(a, b, None), atol=1e-5)


class TestMoERouter:
    @pytest.mark.parametrize("T,E,k", [(64, 8, 2), (100, 64, 6), (256, 40, 8)])
    def test_shape_sweep(self, T, E, k):
        logits = randn(10, (T, E), scale=2.0)
        w, idx = ops.moe_router(logits, k, block_t=64)
        w_ref, idx_ref = ref.moe_router_ref(logits, k)
        np.testing.assert_allclose(w, w_ref, atol=1e-5)
        assert (idx == idx_ref).all()

    def test_weights_normalized_and_sorted(self):
        logits = randn(11, (32, 16), scale=3.0)
        w, idx = ops.moe_router(logits, 4)
        np.testing.assert_allclose(w.sum(-1), np.ones(32), atol=1e-5)
        assert (np.diff(np.asarray(w), axis=-1) <= 1e-7).all()  # descending

    def test_indices_unique_per_token(self):
        logits = randn(12, (64, 24), scale=2.0)
        _, idx = ops.moe_router(logits, 6)
        for row in np.asarray(idx):
            assert len(set(row.tolist())) == 6
