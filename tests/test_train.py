"""Optimizers, schedules, train step, the shared step of a sweep's trials,
data pipeline determinism."""
import contextlib
import dataclasses

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
                         make_hyper_train_step, make_train_state,
                         make_train_step, optimizer_hypers, sgd,
                         shared_train_step, step_cache_clear, step_cache_info)


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


# The train step's name in JAX's trace, lowering and compile events.
STEP_NAMES = ("train_step", "jit(train_step)")
_JIT_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "jit.trace",
               "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
               "/jax/core/compile/backend_compile_duration": "jit.compile"}


@contextlib.contextmanager
def jit_events():
    """(phase, fun_name) of every trace, lowering and compile in the body."""
    events = []

    def listen(event, start, end, **kw):
        if event in _JIT_PHASES:
            events.append((_JIT_PHASES[event], kw.get("fun_name")))

    jax.monitoring.register_event_time_span_listener(listen)
    try:
        yield events
    finally:
        jax.monitoring.unregister_event_time_span_listener(listen)


def step_phases(events):
    return sorted(phase for phase, fun in events if fun in STEP_NAMES)


class TestSharedStep:
    """One jitted step per (model config, optimizer structure, microbatch):
    a trial's scalar hyperparameters are its argument, so a sweep's trials of
    one shape, and a PBT mutation, compile nothing new."""

    # A shape no other test uses, and an empty cache, so what a test sees
    # does not hang on what ran before it in the process.
    CFG = ModelConfig(arch_id="shared", family="dense", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=1, d_ff=64, vocab_size=48).validate()

    @pytest.fixture(autouse=True)
    def _fresh_cache(self):
        step_cache_clear()
        yield
        step_cache_clear()

    def _cls(self, cfg=None):
        from repro.train.trainable import make_model_trainable
        return make_model_trainable(cfg or self.CFG, batch=4, seq_len=16,
                                    steps_per_iter=2, total_steps=10)

    def _batch(self, i):
        data = SyntheticLMDataset(DataConfig(global_batch=4, seq_len=16,
                                             vocab_size=self.CFG.vocab_size))
        return {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}

    def test_trials_of_one_shape_share_one_compiled_step(self):
        cls = self._cls()
        with jit_events() as first:
            a = cls({"lr": 1e-3, "weight_decay": 0.1})
            pa = a.step()["_profile"]
        with jit_events() as second:
            b = cls({"lr": 2e-3, "weight_decay": 0.05})
            pb = b.step()["_profile"]
        assert step_phases(first) == ["jit.compile", "jit.lower", "jit.trace"]
        assert step_phases(second) == []
        info = step_cache_info()
        assert (info.hits, info.misses, info.currsize) == (1, 1, 1)
        assert (pa["step_cache"], pb["step_cache"]) == ("miss", "hit")
        assert a._shared_step is b._shared_step

    @pytest.mark.parametrize("family", ["adamw", "sgd"])
    def test_traced_hypers_match_the_step_with_constants_baked_in(self, family):
        """Three steps with the scalars as arguments against the same steps
        with them written into the program as constants."""
        if family == "adamw":
            config = {"lr": 3e-3, "warmup": 2, "weight_decay": 0.05, "b1": 0.8}
            baked_opt = adamw(linear_warmup_cosine(3e-3, 2, 10), b1=0.8,
                              b2=0.95, weight_decay=0.05, grad_clip=1.0)
        else:
            config = {"lr": 0.05, "warmup": 2, "momentum": 0.8,
                      "weight_decay": 0.01, "grad_clip": 0.5}
            baked_opt = sgd(linear_warmup_cosine(0.05, 2, 10), momentum=0.8,
                            weight_decay=0.01, grad_clip=0.5)
        hypers = {k: jnp.float32(v)
                  for k, v in optimizer_hypers(family, 10, config).items()}
        baked = jax.jit(make_train_step(self.CFG, baked_opt))
        traced = jax.jit(make_hyper_train_step(self.CFG, family))
        s_baked = s_traced = make_train_state(jax.random.key(0), self.CFG, baked_opt)
        for i in range(3):
            s_baked, m_baked = baked(s_baked, self._batch(i))
            s_traced, m_traced = traced(s_traced, self._batch(i), hypers)
            np.testing.assert_allclose(float(m_traced["loss"]),
                                       float(m_baked["loss"]), rtol=1e-6)
        for got, want in zip(jax.tree_util.tree_leaves(s_traced),
                             jax.tree_util.tree_leaves(s_baked)):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
        assert traced._cache_size() == 1

    @pytest.mark.parametrize("change", ["microbatch", "optimizer", "grad_clip",
                                        "model"])
    def test_another_structure_misses(self, change):
        base = {"lr": 1e-3}
        assert self._cls()(base)._step_cache == "miss"
        if change == "model":
            other = self._cls(dataclasses.replace(self.CFG, d_ff=96))(base)
        else:
            value = {"microbatch": 2, "optimizer": "sgd", "grad_clip": None}[change]
            other = self._cls()({**base, change: value})
        assert other._step_cache == "miss"
        info = step_cache_info()
        assert (info.hits, info.misses, info.currsize) == (0, 2, 2)
        assert other.step()["loss"] > 0

    def test_reset_config_compiles_nothing_and_steps_with_the_new_lr(self):
        from repro.dist.submesh import SlicePool
        # On a real slice, so the state is committed to its device.
        tr = self._cls()({"lr": 1e-3,
                          "_slice": SlicePool(devices=jax.devices()[:1]).acquire(1)})
        before = tr.state.params
        tr.step()
        assert not _same(tr.state.params, before)
        with jit_events() as events:
            assert tr.reset_config({"lr": 0.0})
            before = tr.state.params
            out = tr.step()
        assert [phase for phase, _ in events if phase == "jit.compile"] == []
        assert step_phases(events) == []
        assert out["_profile"]["step_cache"] == "hit"
        assert float(tr._hypers["lr"]) == 0.0
        assert _same(tr.state.params, before)  # a zero learning rate moves nothing

    def test_restore_after_reset_config_keeps_the_mutated_hypers(self):
        """PBT's exploit: ``reset_config`` to the mutated config, then
        ``restore`` of the donor's snapshot."""
        cls = self._cls()
        donor = cls({"lr": 1e-3, "weight_decay": 0.1})
        donor.step()
        snapshot = donor.save()
        tr = cls({"lr": 2e-3, "weight_decay": 0.1})
        tr.step()
        assert tr.reset_config({"lr": 0.0, "weight_decay": 0.2})
        tr.restore(snapshot)
        assert _same(tr.state.params, donor.state.params)
        assert float(tr._hypers["lr"]) == 0.0
        assert float(tr._hypers["weight_decay"]) == pytest.approx(0.2)
        tr.step()
        assert _same(tr.state.params, donor.state.params)
        assert step_cache_info().misses == 1

    def test_concurrent_lookups_build_each_step_once(self):
        """Trials of the concurrent executor look the step up from their own
        threads: each key is built once and every count is kept."""
        import sys
        import threading

        n_threads, n_lookups, microbatches = 32, 60, (0, 2, 4)
        hypers = optimizer_hypers("adamw", 10)
        got = {mb: [] for mb in microbatches}
        start = threading.Barrier(n_threads)

        def look():
            start.wait(timeout=30)
            for i in range(n_lookups):
                mb = microbatches[i % len(microbatches)]
                got[mb].append(shared_train_step(self.CFG, "adamw", hypers,
                                                 microbatch=mb)[0])

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=look) for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        info = step_cache_info()
        assert (info.misses, info.hits) == (3, n_threads * n_lookups - 3)
        assert all(len({id(fn) for fn in fns}) == 1 for fns in got.values())


def _same(a, b):
    return all(np.array_equal(x, y) for x, y in
               zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


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

        step_cache_clear()  # the first trial builds the step, whatever ran before

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
        """The first trial traces, lowers and compiles the shared step in its
        first step; the second, of the same shape, does none of them."""
        ids, spans, _ = run
        first_trial, second_trial = ids
        first = min((s for s in spans if s.name == "step" and s.trace == first_trial),
                    key=lambda s: s.ts)
        mine = lambda tid: [s for s in spans if s.name.startswith("jit.")
                            and s.trace == tid and s.args.get("fun_name") in STEP_NAMES]
        assert sorted(s.name for s in mine(first_trial)) == ["jit.compile", "jit.lower",
                                                             "jit.trace"]
        for s in mine(first_trial):
            assert s.args["parent"] == "step" and s.cat == "compile"
            assert first.ts <= s.ts and s.ts + s.dur <= first.ts + first.dur
        assert mine(second_trial) == []

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

        step_cache_clear()
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
        assert tr._step_fn is fn and tr._shared_step._cache_size() == 1
        assert len(obs.tracer.spans_named("data")) == 4
