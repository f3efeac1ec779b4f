"""Search-space DSL: sampling bounds, grid expansion, TPE behaviour."""
import math

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.search.space import (Categorical, GridSearch, LogUniform,
                                     Normal, QRandInt, RandInt, Uniform,
                                     choice, grid_search, loguniform, normal,
                                     qrandint, randint, sample_from,
                                     sample_space, space_signature, uniform)
from repro.core.search.variants import (count_grid_variants, format_variant_tag,
                                        generate_variants)
from repro.core.search.tpe import TPESearcher
from repro.core.search.basic import GridSearcher, RandomSearcher


class TestDomains:
    def test_uniform_bounds_validation(self):
        with pytest.raises(ValueError):
            uniform(1.0, 1.0)
        with pytest.raises(ValueError):
            loguniform(0.0, 1.0)
        with pytest.raises(ValueError):
            randint(5, 5)

    @given(st.floats(-100, 100), st.floats(0.001, 100), st.integers(0, 2**31 - 1))
    @settings(max_examples=50, deadline=None)
    def test_uniform_within_bounds(self, low, width, seed):
        rng = np.random.default_rng(seed)
        d = uniform(low, low + width)
        v = d.sample(rng)
        assert low <= v < low + width

    @given(st.floats(1e-6, 1.0), st.floats(1.5, 1e6), st.integers(0, 2**31 - 1))
    @settings(max_examples=50, deadline=None)
    def test_loguniform_within_bounds(self, low, ratio, seed):
        rng = np.random.default_rng(seed)
        d = loguniform(low, low * ratio)
        v = d.sample(rng)
        assert low <= v <= low * ratio * (1 + 1e-9)

    @given(st.integers(-50, 50), st.integers(1, 100), st.integers(0, 2**31 - 1))
    @settings(max_examples=50, deadline=None)
    def test_randint_within_bounds(self, low, width, seed):
        rng = np.random.default_rng(seed)
        v = randint(low, low + width).sample(rng)
        assert low <= v < low + width
        assert isinstance(v, int)

    def test_choice_returns_member(self):
        rng = np.random.default_rng(0)
        vals = ["a", "b", "c"]
        for _ in range(20):
            assert choice(vals).sample(rng) in vals

    def test_qrandint_quantized(self):
        rng = np.random.default_rng(0)
        for _ in range(20):
            assert qrandint(0, 100, q=10).sample(rng) % 10 == 0


class TestSampleSpace:
    def test_constants_pass_through(self):
        rng = np.random.default_rng(0)
        out = sample_space({"a": 1, "b": "x", "c": uniform(0, 1)}, rng)
        assert out["a"] == 1 and out["b"] == "x" and 0 <= out["c"] < 1

    def test_nested(self):
        rng = np.random.default_rng(0)
        out = sample_space({"opt": {"lr": loguniform(1e-4, 1e-1)}}, rng)
        assert 1e-4 <= out["opt"]["lr"] <= 1e-1

    def test_sample_from_sees_other_values(self):
        rng = np.random.default_rng(0)
        out = sample_space({"a": uniform(1, 2),
                            "b": sample_from(lambda cfg: cfg["a"] * 10)}, rng)
        assert out["b"] == out["a"] * 10

    def test_grid_in_sample_space_raises(self):
        with pytest.raises(ValueError):
            sample_space({"a": grid_search([1, 2])}, np.random.default_rng(0))

    def test_signature_sorted_flat(self):
        sig = space_signature({"b": 1, "a": {"z": 2, "y": 3}})
        assert sig == ["a/y", "a/z", "b"]


class TestVariants:
    def test_grid_cross_product(self):
        space = {"lr": grid_search([0.1, 0.01, 0.001]),
                 "act": grid_search(["relu", "tanh"])}
        variants = list(generate_variants(space))
        assert len(variants) == 6 == count_grid_variants(space)
        assert len({(v["lr"], v["act"]) for v in variants}) == 6

    def test_num_samples_resamples_stochastic(self):
        space = {"lr": uniform(0, 1), "g": grid_search([1, 2])}
        variants = list(generate_variants(space, num_samples=3, seed=0))
        assert len(variants) == 6
        lrs = {v["lr"] for v in variants}
        assert len(lrs) == 6  # all distinct draws

    def test_deterministic_by_seed(self):
        space = {"lr": uniform(0, 1)}
        a = [v["lr"] for v in generate_variants(space, num_samples=5, seed=42)]
        b = [v["lr"] for v in generate_variants(space, num_samples=5, seed=42)]
        assert a == b

    def test_tag(self):
        assert "lr=0.1" in format_variant_tag({"lr": 0.1, "b": 2})


class TestSearchers:
    def test_random_exhausts(self):
        s = RandomSearcher({"lr": uniform(0, 1)}, max_trials=3)
        cfgs = [s.suggest(f"t{i}") for i in range(4)]
        assert cfgs[3] is None and all(c is not None for c in cfgs[:3])

    def test_grid_searcher(self):
        s = GridSearcher({"lr": grid_search([1, 2, 3])})
        got = [s.suggest(f"t{i}") for i in range(4)]
        assert [g["lr"] for g in got[:3]] == [1, 2, 3] and got[3] is None

    def test_tpe_concentrates_near_optimum(self):
        """TPE on f(x) = (x-0.3)^2 should sample near 0.3 after startup."""
        space = {"x": uniform(0.0, 1.0)}
        tpe = TPESearcher(space, metric="loss", mode="min",
                          n_startup_trials=8, seed=0)
        history = []
        for i in range(60):
            cfg = tpe.suggest(f"t{i}")
            loss = (cfg["x"] - 0.3) ** 2
            tpe.observe(f"t{i}", cfg, loss, final=True)
            history.append(cfg["x"])
        late = np.asarray(history[-20:])
        early = np.asarray(history[:8])
        assert np.abs(late - 0.3).mean() < np.abs(early - 0.3).mean()
        assert np.abs(late - 0.3).mean() < 0.15

    def test_tpe_categorical_and_int(self):
        space = {"c": choice(["good", "bad"]), "n": randint(1, 10)}
        tpe = TPESearcher(space, metric="loss", mode="min",
                          n_startup_trials=5, seed=0)
        for i in range(40):
            cfg = tpe.suggest(f"t{i}")
            loss = (0.0 if cfg["c"] == "good" else 1.0) + abs(cfg["n"] - 5) * 0.1
            tpe.observe(f"t{i}", cfg, loss, final=True)
        late = [tpe.suggest(f"x{i}") for i in range(10)]
        assert sum(1 for c in late if c["c"] == "good") >= 7
