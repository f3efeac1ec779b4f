"""Runner state machine, executors, resources, slice pool, object store,
checkpoint serialization — the distributed-substrate invariants."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import (CheckpointManager, FIFOScheduler, ObjectStore,
                        ResourceAccountant, Resources, SerialMeshExecutor,
                        Trainable, Trial, TrialRunner, TrialStatus,
                        load_pytree, save_pytree, tree_from_bytes,
                        tree_to_bytes, wrap_function)
from repro.dist.submesh import SlicePool


class Counter(Trainable):
    def setup(self, config):
        self.n = 0
        self.fail_at = config.get("fail_at")

    def step(self):
        self.n += 1
        if self.fail_at and self.n >= self.fail_at:
            raise RuntimeError("boom")
        return {"loss": 1.0 / self.n}

    def save(self):
        return {"n": self.n}

    def restore(self, state):
        self.n = state["n"]


def make_runner(scheduler=None, devices=4, checkpoint_freq=1, stop=10):
    ex = SerialMeshExecutor(lambda name: Counter,
                            CheckpointManager(ObjectStore()),
                            total_devices=devices,
                            checkpoint_freq=checkpoint_freq)
    return TrialRunner(scheduler or FIFOScheduler(metric="loss", mode="min"),
                       ex, stopping_criteria={"training_iteration": stop})


class TestRunner:
    def test_parallel_limited_by_resources(self):
        runner = make_runner(devices=2)
        for i in range(5):
            runner.add_trial(Trial({}, resources=Resources(devices=1),
                                   stopping_criteria={"training_iteration": 3}))
        runner.step()
        running = sum(1 for t in runner.trials if t.status == TrialStatus.RUNNING)
        assert running == 2  # only 2 devices
        trials = runner.run()
        assert all(t.status == TrialStatus.TERMINATED for t in trials)
        assert all(t.training_iteration == 3 for t in trials)

    def test_trial_error_recorded_not_fatal(self):
        runner = make_runner()
        runner.add_trial(Trial({"fail_at": 2}, stopping_criteria={"training_iteration": 5}))
        runner.add_trial(Trial({}, stopping_criteria={"training_iteration": 5}))
        trials = runner.run()
        statuses = sorted(t.status for t in trials)
        assert statuses == [TrialStatus.ERROR, TrialStatus.TERMINATED]
        assert runner.n_errors == 1

    def test_results_recorded_in_order(self):
        runner = make_runner()
        runner.add_trial(Trial({}, stopping_criteria={"training_iteration": 4}))
        (trial,) = runner.run()
        iters = [r.training_iteration for r in trial.results]
        assert iters == [1, 2, 3, 4]

    def test_metric_stop_criterion(self):
        runner = make_runner(stop=100)
        t = Trial({}, stopping_criteria={"training_iteration": 100, "loss_inv": 0})
        runner.add_trial(t)
        # loss decreases; use the iteration bound only
        runner.run(max_steps=500)
        assert t.training_iteration == 100


class TestFunctionAPI:
    def test_function_trainable_reports(self):
        def train(tune):
            x = 0
            for _ in range(5):
                x += tune.params["inc"]
                if tune.should_checkpoint():
                    tune.record_checkpoint({"x": x})
                tune.report(value=x)

        cls = wrap_function(train)
        tr = cls({"inc": 2})
        out = [tr.train()["value"] for _ in range(5)]
        assert out == [2, 4, 6, 8, 10]
        assert tr.train().get("done")
        tr.cleanup()

    def test_function_checkpoint_on_request(self):
        def train(tune):
            for i in range(10):
                if tune.should_checkpoint():
                    tune.record_checkpoint({"i": i})
                tune.report(i=i)

        tr = wrap_function(train)({})
        tr.train()
        state = tr.save()
        assert "i" in state
        tr.cleanup()

    def test_function_stop_mid_run(self):
        stopped = []

        def train(tune):
            try:
                for i in range(1000):
                    tune.report(i=i)
            finally:
                stopped.append(True)

        tr = wrap_function(train)({})
        tr.train()
        tr.cleanup()
        assert stopped


class TestCheckpointSerialization:
    def test_roundtrip_pytree(self, tmp_path):
        tree = {"a": np.arange(12, dtype=np.float32).reshape(3, 4),
                "b": [jnp.ones((2, 2), jnp.bfloat16), 3, "tag"],
                "c": {"d": np.int64(7), "e": None}}
        data = tree_to_bytes(tree)
        back = tree_from_bytes(data)
        np.testing.assert_array_equal(back["a"], tree["a"])
        np.testing.assert_array_equal(np.asarray(back["b"][0], np.float32),
                                      np.ones((2, 2), np.float32))
        assert back["b"][1] == 3 and back["b"][2] == "tag"
        assert back["c"]["d"] == 7 and back["c"]["e"] is None

    def test_crc_detects_corruption(self):
        data = bytearray(tree_to_bytes({"a": np.ones(4)}))
        data[10] ^= 0xFF
        with pytest.raises(IOError):
            tree_from_bytes(bytes(data))

    def test_disk_roundtrip_atomic(self, tmp_path):
        path = str(tmp_path / "ckpt" / "x.ckpt")
        save_pytree({"v": np.arange(5)}, path)
        assert np.array_equal(load_pytree(path)["v"], np.arange(5))

    @given(st.lists(st.floats(-1e6, 1e6, width=32), min_size=1, max_size=20))
    @settings(max_examples=30, deadline=None)
    def test_roundtrip_property(self, values):
        arr = np.asarray(values, np.float32)
        back = tree_from_bytes(tree_to_bytes({"x": arr}))
        np.testing.assert_array_equal(back["x"], arr)


class TestObjectStore:
    def test_put_get_delete(self):
        store = ObjectStore()
        k = store.put({"w": np.ones((4, 4))})
        assert store.contains(k)
        np.testing.assert_array_equal(store.get(k)["w"], np.ones((4, 4)))
        store.delete(k)
        assert not store.contains(k)
        with pytest.raises(KeyError):
            store.get(k)

    def test_lru_spill_to_disk(self, tmp_path):
        store = ObjectStore(capacity_bytes=1000, spill_dir=str(tmp_path))
        keys = [store.put(np.ones(100, np.float32), key=f"k{i}") for i in range(5)]
        assert store.n_spilled > 0
        for k in keys:  # all still retrievable (memory or spilled)
            assert store.get(k) is not None


class TestResources:
    def test_accounting_never_negative(self):
        acct = ResourceAccountant(4.0, 8)
        r = Resources(cpu=2, devices=4)
        acct.acquire(r)
        assert not acct.has_room(Resources(cpu=4, devices=1))
        acct.release(r)
        with pytest.raises(RuntimeError):
            acct.release(r)

    def test_negative_request_rejected(self):
        with pytest.raises(ValueError):
            Resources(cpu=-1)

    def test_overcommit_raises(self):
        acct = ResourceAccountant(1.0, 1)
        with pytest.raises(RuntimeError):
            acct.acquire(Resources(cpu=2))


class TestSlicePool:
    def test_first_fit_and_coalesce(self):
        pool = SlicePool(n_virtual=16)
        a = pool.acquire(6)
        b = pool.acquire(6)
        assert not pool.can_fit(6)
        pool.release(a)
        pool.release(b)
        c = pool.acquire(16)  # coalesced back to one range
        assert c.size == 16

    def test_mesh_from_slice(self):
        import jax
        pool = SlicePool(devices=jax.devices() * 4)  # fake 4 slots on CPU
        sl = pool.acquire(2)
        mesh = sl.make_mesh(("data",))
        assert mesh.shape["data"] == 2

    @given(st.lists(st.integers(1, 5), min_size=1, max_size=20))
    @settings(max_examples=30, deadline=None)
    def test_acquire_release_invariant(self, sizes):
        """Free count is conserved under any acquire/release sequence."""
        pool = SlicePool(n_virtual=32)
        held = []
        for s in sizes:
            if pool.can_fit(s):
                held.append(pool.acquire(s))
        used = sum(h.size for h in held)
        assert pool.n_free == 32 - used
        for h in held:
            pool.release(h)
        assert pool.n_free == 32
        assert pool.can_fit(32)
