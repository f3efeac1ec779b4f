"""Optimizers, schedules, train step, data pipeline determinism."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.data import DataConfig, SyntheticLMDataset
from repro.models import ModelConfig
from repro.train import (TrainState, adamw, clip_by_global_norm,
                         cosine_schedule, global_norm, linear_warmup_cosine,
                         make_train_state, make_train_step, sgd)


class TestOptimizers:
    def test_adamw_matches_reference_step(self):
        """One AdamW step against the textbook update."""
        p = {"w": jnp.asarray([1.0, -2.0, 3.0])}
        g = {"w": jnp.asarray([0.1, 0.2, -0.3])}
        lr, b1, b2, eps, wd = 0.01, 0.9, 0.999, 1e-8, 0.1
        opt = adamw(lr, b1=b1, b2=b2, eps=eps, weight_decay=wd, grad_clip=None)
        st_ = opt.init(p)
        new_p, st_ = opt.update(g, st_, p)
        m = (1 - b1) * g["w"]
        v = (1 - b2) * g["w"] ** 2
        mhat, vhat = m / (1 - b1), v / (1 - b2)
        expect = p["w"] - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p["w"])
        np.testing.assert_allclose(new_p["w"], expect, rtol=1e-6)

    def test_sgd_momentum_matches_reference(self):
        p = {"w": jnp.asarray([1.0])}
        g = {"w": jnp.asarray([0.5])}
        opt = sgd(0.1, momentum=0.9)
        st_ = opt.init(p)
        p1, st_ = opt.update(g, st_, p)
        np.testing.assert_allclose(p1["w"], 1.0 - 0.1 * 0.5, rtol=1e-6)
        p2, st_ = opt.update(g, st_, p1)
        mom = 0.9 * 0.5 + 0.5
        np.testing.assert_allclose(p2["w"], p1["w"] - 0.1 * mom, rtol=1e-6)

    def test_grad_clip(self):
        tree = {"a": jnp.asarray([3.0, 4.0])}  # norm 5
        clipped, norm = clip_by_global_norm(tree, 1.0)
        np.testing.assert_allclose(norm, 5.0, rtol=1e-6)
        np.testing.assert_allclose(global_norm(clipped), 1.0, rtol=1e-5)

    def test_quadratic_convergence(self):
        """AdamW drives a quadratic to its minimum."""
        opt = adamw(0.1, weight_decay=0.0, grad_clip=None)
        p = {"x": jnp.asarray(5.0)}
        st_ = opt.init(p)
        for _ in range(200):
            g = jax.grad(lambda q: (q["x"] - 2.0) ** 2)(p)
            p, st_ = opt.update(g, st_, p)
        assert abs(float(p["x"]) - 2.0) < 0.05


class TestSchedules:
    def test_warmup_then_decay(self):
        s = linear_warmup_cosine(1.0, warmup=10, total_steps=110)
        assert float(s(jnp.asarray(0))) == 0.0
        assert float(s(jnp.asarray(5))) == pytest.approx(0.5)
        assert float(s(jnp.asarray(10))) == pytest.approx(1.0, abs=0.01)
        assert float(s(jnp.asarray(110))) == pytest.approx(0.1, abs=0.01)

    @given(st.integers(0, 1000))
    @settings(max_examples=30, deadline=None)
    def test_cosine_bounded(self, step):
        s = cosine_schedule(1.0, 500, final_frac=0.1)
        v = float(s(jnp.asarray(step)))
        assert 0.0999 <= v <= 1.0001


class TestTrainStep:
    CFG = ModelConfig(arch_id="t", family="dense", n_layers=2, d_model=64,
                      n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=64).validate()

    def _batch(self, i=0):
        data = SyntheticLMDataset(DataConfig(global_batch=8, seq_len=32,
                                             vocab_size=64, noise=0.05))
        return {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}

    def test_loss_decreases(self):
        opt = adamw(3e-3)
        state = make_train_state(jax.random.key(0), self.CFG, opt)
        step = jax.jit(make_train_step(self.CFG, opt))
        losses = []
        for i in range(30):
            state, m = step(state, self._batch(i))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] * 0.8

    def test_microbatch_equals_full_batch(self):
        opt = adamw(1e-3)
        b = self._batch()
        s0 = make_train_state(jax.random.key(0), self.CFG, opt)
        full = jax.jit(make_train_step(self.CFG, opt))
        micro = jax.jit(make_train_step(self.CFG, opt, microbatch=4))
        s1, m1 = full(s0, b)
        s2, m2 = micro(make_train_state(jax.random.key(0), self.CFG, opt), b)
        np.testing.assert_allclose(float(m1["total_loss"]),
                                   float(m2["total_loss"]), rtol=1e-5)
        # params should closely agree (grad averaging is exact up to fp assoc.)
        d = jax.tree_util.tree_map(lambda a, b_: float(jnp.abs(a - b_).max()),
                                   s1.params, s2.params)
        assert max(jax.tree_util.tree_leaves(d)) < 1e-5

    def test_step_counter_and_remat(self):
        import dataclasses
        cfg = dataclasses.replace(self.CFG, remat=True)
        opt = adamw(1e-3)
        state = make_train_state(jax.random.key(0), cfg, opt)
        step = jax.jit(make_train_step(cfg, opt))
        state, m = step(state, self._batch())
        assert int(state.step) == 1 and jnp.isfinite(m["total_loss"])


class TestDataPipeline:
    def test_deterministic_across_restarts(self):
        cfg = DataConfig(global_batch=4, seq_len=16, vocab_size=100, seed=7)
        a = SyntheticLMDataset(cfg).batch_at(13)
        b = SyntheticLMDataset(cfg).batch_at(13)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_shards_partition_global_batch(self):
        full = SyntheticLMDataset(DataConfig(global_batch=8, seq_len=16,
                                             vocab_size=50, seed=1))
        shard_sizes = []
        for s in range(4):
            sh = SyntheticLMDataset(DataConfig(global_batch=8, seq_len=16,
                                               vocab_size=50, seed=1,
                                               shard_index=s, num_shards=4))
            shard_sizes.append(sh.batch_at(0)["tokens"].shape[0])
        assert shard_sizes == [2, 2, 2, 2]

    def test_labels_are_shifted_tokens(self):
        d = SyntheticLMDataset(DataConfig(global_batch=2, seq_len=16,
                                          vocab_size=50))
        b = d.batch_at(0)
        np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])

    def test_learnable_structure(self):
        """Next token is the permutation of the current one (mostly)."""
        d = SyntheticLMDataset(DataConfig(global_batch=4, seq_len=64,
                                          vocab_size=32, noise=0.0, seed=3))
        b = d.batch_at(0)
        toks = b["tokens"]
        match = (d.perm[toks[:, :-1]] == toks[:, 1:]).mean()
        assert match == 1.0

    def test_invalid_shards_raise(self):
        with pytest.raises(ValueError):
            SyntheticLMDataset(DataConfig(global_batch=5, seq_len=8,
                                          vocab_size=10, num_shards=2))


class TestHardwareProfile:
    """The one-shot ``_profile`` contract (DESIGN.md §9)."""

    CFG = ModelConfig(arch_id="t", family="dense", n_layers=2, d_model=64,
                      n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=64).validate()

    def _trainable(self, **hp):
        from repro.train.trainable import make_model_trainable
        cls = make_model_trainable(self.CFG, batch=4, seq_len=32,
                                   steps_per_iter=3, total_steps=10)
        return cls({"lr": 1e-3, **hp})

    def test_first_step_carries_profile_once(self):
        tr = self._trainable()
        out = tr.step()
        p = out["_profile"]
        assert p["first_step_s"] >= p["steady_step_s"] > 0
        assert p["compile_s"] >= 0
        assert p["param_count"] > 0
        assert p["batch"] == 4 and p["seq_len"] == 32
        # one-shot: the next iteration is clean
        assert "_profile" not in tr.step()

    def test_profile_false_disables(self):
        tr = self._trainable(profile=False)
        assert "_profile" not in tr.step()

    def test_rebuild_rearms_profile(self):
        tr = self._trainable()
        tr.step()
        assert tr.reset_config({"lr": 5e-4})  # PBT mutation path
        assert "_profile" in tr.step()

    def test_roofline_tag(self, monkeypatch):
        from repro.launch import mesh
        # The CPU has no published peaks; lend it the v5e's to check the tag.
        monkeypatch.setitem(mesh.HW, jax.devices()[0].device_kind,
                            mesh.HW[mesh.V5E])
        tr = self._trainable(profile_roofline=True)
        p = tr.step()["_profile"]
        assert p["predicted_step_s"] > 0
        assert p["dominant"] in ("compute", "memory", "collective")
        assert p["achieved_vs_predicted"] > 0
        assert p["arg_bytes"] > 0 and p["temp_bytes"] > 0

    def test_no_roofline_without_peaks(self):
        p = self._trainable(profile_roofline=True).step()["_profile"]
        assert p["arg_bytes"] > 0
        assert not any(k.startswith(("roofline_", "predicted", "achieved"))
                       for k in p)
        assert "dominant" not in p

    def test_state_lives_on_the_slice_device(self):
        from repro.dist.submesh import SlicePool
        dev = jax.devices()[0]
        tr = self._trainable(_slice=SlicePool(devices=[dev]).acquire(1))
        p = tr.step()["_profile"]
        assert p["devices"] == [f"{dev.platform}:{dev.id}"]
        for x in jax.tree_util.tree_leaves(tr.state):
            assert x.committed and x.devices() == {dev}
        tr.restore(tr.save())
        assert all(x.devices() == {dev}
                   for x in jax.tree_util.tree_leaves(tr.state))

    def test_wide_real_slice_is_refused(self):
        from repro.dist.submesh import MeshSlice
        dev = jax.devices()[0]
        with pytest.raises(NotImplementedError, match="ROADMAP 2.1"):
            self._trainable(_slice=MeshSlice(0, 2, (dev, dev)))

    def test_virtual_slice_uses_default_device(self):
        from repro.dist.submesh import SlicePool
        tr = self._trainable(_slice=SlicePool(n_virtual=4).acquire(2))
        assert all(not x.committed for x in jax.tree_util.tree_leaves(tr.state))


class TestTrialTracing:
    """Spans from inside a trial (DESIGN.md §8): ``data`` around each batch,
    ``jit.*`` for the train step's trace, lowering and compile, all children
    of the executor's ``step``, and their ``tune.`` profiler annotations."""

    STEPS_PER_ITER = 2

    @pytest.fixture(scope="class")
    def run(self, tmp_path_factory):
        from repro.core import (CheckpointManager, FIFOScheduler, ObjectStore,
                                Resources, SerialMeshExecutor, Trial,
                                TrialRunner)
        from repro.obs import Observability
        from repro.train.trainable import make_model_trainable

        cls = make_model_trainable(TestHardwareProfile.CFG, batch=2, seq_len=16,
                                   steps_per_iter=self.STEPS_PER_ITER,
                                   total_steps=10)
        obs = Observability(trace=True)
        ex = SerialMeshExecutor(lambda _n: cls, CheckpointManager(ObjectStore()),
                                total_devices=1, obs=obs)
        stop = {"training_iteration": 2}
        runner = TrialRunner(FIFOScheduler(metric="loss", mode="min"), ex,
                             stopping_criteria=stop, obs=obs)
        for lr in (1e-3, 2e-3):
            runner.add_trial(Trial({"lr": lr}, resources=Resources(devices=1),
                                   stopping_criteria=stop))
        log_dir = str(tmp_path_factory.mktemp("profile"))
        jax.profiler.start_trace(log_dir)
        try:
            trials = runner.run()
        finally:
            jax.profiler.stop_trace()
        obs.close(ex)
        assert all(t.status.value == "TERMINATED" for t in trials)
        return [t.trial_id for t in trials], obs.tracer.spans, log_dir

    def test_data_spans_per_step(self, run):
        ids, spans, _ = run
        steps = [s for s in spans if s.name == "step"]
        assert len(steps) == 2 * len(ids)
        for st_ in steps:
            inside = [s for s in spans if s.name == "data" and s.trace == st_.trace
                      and st_.ts <= s.ts <= st_.ts + st_.dur]
            assert len(inside) == self.STEPS_PER_ITER
            assert all(s.args["parent"] == "step" and s.cat == "data"
                       for s in inside)
        for tid in ids:
            data = [s for s in spans if s.name == "data" and s.trace == tid]
            assert [s.args["step"] for s in data] == list(range(4))
        decisions = [s for s in spans if s.name == "schedule.decision"]
        assert sorted(s.trace for s in decisions) == sorted(ids)

    def test_one_trace_lower_compile_of_the_step_in_the_first_step(self, run):
        ids, spans, _ = run
        for tid in ids:
            first = min((s for s in spans if s.name == "step" and s.trace == tid),
                        key=lambda s: s.ts)
            mine = [s for s in spans if s.name.startswith("jit.") and s.trace == tid
                    and s.args.get("fun_name") in ("train_step", "jit(train_step)")]
            assert sorted(s.name for s in mine) == ["jit.compile", "jit.lower",
                                                    "jit.trace"]
            for s in mine:
                assert s.args["parent"] == "step" and s.cat == "compile"
                assert first.ts <= s.ts and s.ts + s.dur <= first.ts + first.dur

    def test_profiler_trace_holds_the_program_spans(self, run):
        import glob
        import os

        from jax.profiler import ProfileData

        path = max(glob.glob(os.path.join(run[2], "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
        names = set()
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                names |= {e.name for ln in plane.lines for e in ln.events}
        assert {"tune.step", "tune.data", "tune.build",
                "tune.schedule.decision"} <= names

    def test_tracing_keeps_the_step_function(self):
        from repro.core.clock import WallClock
        from repro.obs import Observability
        from repro.train.trainable import make_model_trainable

        cls = make_model_trainable(TestHardwareProfile.CFG, batch=2, seq_len=16,
                                   steps_per_iter=2, total_steps=10)
        tr = cls({"lr": 1e-3})
        fn = tr._step_fn
        obs = Observability(trace=True, clock=WallClock())
        try:
            for it in range(2):
                with obs.tracer.span("step", "t-1"):
                    tr.step()
        finally:
            obs.close()
        assert tr._step_fn is fn and fn._cache_size() == 1
        assert len(obs.tracer.spans_named("data")) == 4
