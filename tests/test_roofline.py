"""HLO cost-walker validation: trip-weighted flops/bytes/collectives against
analytically known programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.roofline import RooflineReport, hlo_costs, model_flops


def compile_text(fn, *shapes):
    structs = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    return jax.jit(fn).lower(*structs).compile().as_text()


class TestDotFlops:
    def test_plain_matmul(self):
        txt = compile_text(lambda a, b: a @ b, (64, 128), (128, 32))
        costs = hlo_costs(txt)
        assert costs["dot_flops"] == 2 * 64 * 128 * 32

    def test_scan_trip_weighting(self):
        N, L = 128, 7

        def f(ws, x):
            def body(x, w):
                return jnp.tanh(x @ w), None
            x, _ = jax.lax.scan(body, x, ws)
            return x.sum()

        txt = compile_text(f, (L, N, N), (N, N))
        costs = hlo_costs(txt)
        assert costs["dot_flops"] == pytest.approx(2 * N**3 * L, rel=1e-6)

    def test_grad_is_3x(self):
        N, L = 64, 5

        def f(ws, x):
            def body(x, w):
                return jnp.tanh(x @ w), None
            x, _ = jax.lax.scan(body, x, ws)
            return x.sum()

        txt = compile_text(lambda w, x: jax.grad(f)(w, x).sum(), (L, N, N), (N, N))
        costs = hlo_costs(txt)
        assert costs["dot_flops"] == pytest.approx(6 * N**3 * L, rel=1e-6)

    def test_remat_is_4x(self):
        N, L = 64, 5

        def f(ws, x):
            @jax.checkpoint
            def body(x, w):
                return jnp.tanh(x @ w), None
            x, _ = jax.lax.scan(body, x, ws)
            return x.sum()

        txt = compile_text(lambda w, x: jax.grad(f)(w, x).sum(), (L, N, N), (N, N))
        costs = hlo_costs(txt)
        assert costs["dot_flops"] == pytest.approx(8 * N**3 * L, rel=1e-6)

    def test_batched_dot(self):
        txt = compile_text(lambda a, b: jnp.einsum("bij,bjk->bik", a, b),
                           (4, 32, 16), (4, 16, 8))
        costs = hlo_costs(txt)
        assert costs["dot_flops"] == 2 * 4 * 32 * 16 * 8


class TestModelFlops:
    def test_train_6nd(self):
        assert model_flops(1000, 50, "train") == 6 * 1000 * 50

    def test_inference_2nd(self):
        assert model_flops(1000, 50, "decode") == 2 * 1000 * 50


class TestReport:
    def _report(self, **kw):
        base = dict(arch="a", shape="s", mesh="m", chips=256,
                    device_kind="TPU v5 lite", device_flops=1e12, device_bytes=1e11,
                    collective_bytes=1e9, collectives_by_kind={},
                    ca_flops_raw=0, ca_bytes_raw=0,
                    arg_bytes=2**30, temp_bytes=2**30, output_bytes=0,
                    model_flops_total=2.56e14, n_tokens=1000)
        base.update(kw)
        return RooflineReport(**base)

    def test_terms_and_dominant(self):
        r = self._report()
        assert r.compute_s == pytest.approx(1e12 / 197e12)
        assert r.memory_s == pytest.approx(1e11 / 819e9)
        assert r.collective_s == pytest.approx(1e9 / 50e9)
        assert r.dominant == "memory"
        assert r.useful_flops_ratio == pytest.approx(2.56e14 / (1e12 * 256))
        assert r.hbm_per_device_gib == pytest.approx(2.0)
        assert r.step_time_s == r.memory_s

    def test_unknown_device_kind_raises(self):
        with pytest.raises(ValueError, match="no peak rates"):
            self._report(device_kind="cpu").compute_s

    def test_dict_roundtrip_keys(self):
        d = self._report().to_dict()
        for k in ("compute_s", "memory_s", "collective_s", "dominant",
                  "useful_flops_ratio", "step_time_s"):
            assert k in d
