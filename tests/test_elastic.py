"""Elastic resource control plane (DESIGN.md §6): SlicePool resize matrix,
ResizePolicy behaviour, per-tier checkpoint-boundary resize with rollback,
and the k=1 credit-equivalence matrix — an elastic run with a sequential pool
must reproduce the serial executor's scheduler decisions exactly on
FIFO/ASHA/HyperBand/PBT."""
import os
from types import SimpleNamespace

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st
from repro.core import (ASHAScheduler, CheckpointManager, EventType,
                        FIFOScheduler, FairShare, GreedyFill,
                        HyperBandScheduler, Logger, MedianStoppingRule,
                        ObjectStore, PopulationBasedTraining,
                        ProcessMeshExecutor, Resources, ResourceBroker,
                        SerialMeshExecutor, TrainableFactory, Trial,
                        TrialRunner, TrialStatus, grid_search,
                        register_worker_factory, run_experiments)
from repro.dist.submesh import SlicePool

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def factory(name: str) -> TrainableFactory:
    return TrainableFactory(target=f"_worker_trainables:{name}",
                            sys_path=(TESTS_DIR,))


class Recorder(Logger):
    def __init__(self):
        self.events = []

    def on_event(self, trial, event):
        self.events.append(event)

    def of(self, kind):
        return [e for e in self.events if e.type == kind]


# ---------------------------------------------------------------------------------
# SlicePool resize matrix
# ---------------------------------------------------------------------------------

class TestSlicePoolResize:
    def test_grow_in_place_into_adjacent_free(self):
        pool = SlicePool(n_virtual=16)
        a = pool.acquire(4)
        grown = pool.resize(a, 8)
        assert (grown.start, grown.size) == (0, 8)
        assert pool.n_free == 8 and pool.n_resized_total == 1

    def test_grow_relocates_when_not_adjacent(self):
        pool = SlicePool(n_virtual=16)
        a = pool.acquire(4)
        b = pool.acquire(4)  # blocks a's in-place growth
        grown = pool.resize(a, 8)
        assert grown.start == 8 and grown.size == 8  # moved past b
        assert pool.n_free == 4
        pool.release(b)
        pool.release(grown)
        assert pool.n_free == 16 and pool.fragments() == 0

    def test_grow_impossible_is_atomic(self):
        pool = SlicePool(n_virtual=8)
        a = pool.acquire(4)
        b = pool.acquire(2)
        with pytest.raises(RuntimeError):
            pool.resize(a, 7)
        # failure left everything exactly as it was
        assert pool.n_free == 2
        pool.release(a)
        pool.release(b)
        assert pool.n_free == 8 and pool.fragments() == 0

    def test_shrink_trims_tail_and_coalesces(self):
        pool = SlicePool(n_virtual=16)
        a = pool.acquire(8)
        b = pool.acquire(8)
        small = pool.resize(a, 2)
        assert (small.start, small.size) == (0, 2)
        assert pool.n_free == 6 and pool.fragments() == 0  # [2, 8) one range
        c = pool.acquire(6)
        assert c.start == 2  # the trimmed tail is immediately reusable
        for s in (small, b, c):
            pool.release(s)
        assert pool.n_free == 16 and pool.fragments() == 0

    def test_try_grow_requires_adjacency(self):
        pool = SlicePool(n_virtual=12)
        a = pool.acquire(4)
        b = pool.acquire(4)
        assert pool.try_grow(a, 8) is None       # b sits in the way
        grown = pool.try_grow(b, 8)              # tail [8, 12) is adjacent
        assert grown is not None and (grown.start, grown.size) == (4, 8)
        with pytest.raises(ValueError):
            pool.try_grow(a, 4)                  # not a growth

    def test_acquire_at_exact_range(self):
        pool = SlicePool(n_virtual=8)
        a = pool.acquire(2)
        s = pool.acquire_at(4, 2)                # mid-range carve
        assert (s.start, s.size) == (4, 2)
        assert pool.fragments() == 1             # holes: [2,4) vs [6,8)
        with pytest.raises(RuntimeError):
            pool.acquire_at(4, 2)                # already held
        pool.release(s)
        pool.release(a)
        assert pool.fragments() == 0

    def test_stats_surface(self):
        pool = SlicePool(n_virtual=16)
        assert pool.utilization() == 0.0
        assert pool.largest_free_block() == 16 and pool.fragments() == 0
        a = pool.acquire(4)
        b = pool.acquire(4)
        pool.acquire(8)
        assert pool.utilization() == 1.0 and pool.largest_free_block() == 0
        pool.release(a)
        assert pool.largest_free_block() == 4 and pool.fragments() == 0
        assert pool.can_resize(b, 2) and pool.can_resize(b, 8)
        assert not pool.can_resize(b, 12)

    # -- acquire/release/resize walk: property-based (hypothesis), plus a
    # seeded walk that pins a fixed set of scripts.

    @staticmethod
    def _run_walk(pool_size, ops):
        """Drive an op script against a pool, asserting the free-list
        invariants after every op: capacity conserved, held/free disjoint,
        largest block bounded, full coalesce on drain.  ``ops`` is a list of
        (kind, index, size): kind 0 releases held[index], 1 resizes
        held[index] to ``size``, 2 acquires ``size``."""
        pool = SlicePool(n_virtual=pool_size)
        held = []
        for kind, index, size in ops:
            if kind == 0 and held:
                held.remove(sl := held[index % len(held)])
                pool.release(sl)
            elif kind == 1 and held:
                sl = held[index % len(held)]
                if size != sl.size and (size < sl.size
                                        or pool.can_resize(sl, size)):
                    held.remove(sl)
                    held.append(pool.resize(sl, size))
            elif kind == 2:
                if pool.can_fit(size):
                    held.append(pool.acquire(size))
            assert pool.n_free == pool_size - sum(h.size for h in held)
            assert pool.largest_free_block() <= pool.n_free
            for h in held:
                for start, fsize in pool._free:
                    assert h.start + h.size <= start or start + fsize <= h.start
        for h in held:
            pool.release(h)
        assert pool.n_free == pool_size and pool.fragments() == 0

    @settings(max_examples=200, deadline=None)
    @given(ops=st.lists(
        st.tuples(st.integers(min_value=0, max_value=2),
                  st.integers(min_value=0, max_value=63),
                  st.integers(min_value=1, max_value=13)),
        max_size=300))
    def test_random_walk_with_resize_conserves_capacity(self, ops):
        """Property form of the old 5-seed walk: hypothesis explores (and
        shrinks) op interleavings instead of five fixed RNG streams."""
        self._run_walk(64, ops)

    @pytest.mark.parametrize("seed", range(3))
    def test_random_walk_seeded_fallback(self, seed):
        """No-hypothesis fallback: the same invariant walk on fixed seeds, so
        the coalescing regression matrix never goes dark."""
        rng = np.random.default_rng(seed)
        ops = [(int(rng.integers(0, 3)), int(rng.integers(0, 64)),
                int(rng.integers(1, 14))) for _ in range(300)]
        self._run_walk(64, ops)


# ---------------------------------------------------------------------------------
# Policies and broker clamping
# ---------------------------------------------------------------------------------

def _fake_runner(scheduler, trials=()):
    return SimpleNamespace(scheduler=scheduler, trials=list(trials))


class TestPolicies:
    def test_greedy_fill_doubles_after_grace(self):
        pool = SlicePool(n_virtual=16)
        sl = pool.acquire(2)
        runner = _fake_runner(ASHAScheduler(max_t=8, grace_period=3))
        young = SimpleNamespace(results=[], trial_id="t",
                                training_iteration=1)
        survivor = SimpleNamespace(results=[], trial_id="t",
                                   training_iteration=3)
        policy = GreedyFill()
        assert policy.propose(runner, young, pool, sl) is None  # pre-grace
        assert policy.propose(runner, survivor, pool, sl) == 4  # one doubling

    def test_greedy_fill_respects_cap_and_feasibility(self):
        pool = SlicePool(n_virtual=8)
        sl = pool.acquire(4)
        other = pool.acquire(4)
        runner = _fake_runner(FIFOScheduler())
        trial = SimpleNamespace(training_iteration=5)
        assert GreedyFill().propose(runner, trial, pool, sl) is None  # full pool
        pool.release(other)
        assert GreedyFill().propose(runner, trial, pool, sl) == 8
        assert GreedyFill(max_devices=4).propose(runner, trial, pool, sl) is None

    def test_fair_share_rebalances(self):
        pool = SlicePool(n_virtual=16)
        big = pool.acquire(12)
        small = pool.acquire(2)
        running = [SimpleNamespace(status=TrialStatus.RUNNING) for _ in range(2)]
        runner = _fake_runner(FIFOScheduler(), running)
        policy = FairShare()
        assert policy.propose(runner, running[0], pool, big) == 8    # shrink
        assert policy.propose(runner, running[1], pool, small) is None  # 2 free
        pool.resize(big, 8)
        assert policy.propose(runner, running[1], pool, small) == 8  # now grow

    def test_fair_share_counts_waiting_trials(self):
        pool = SlicePool(n_virtual=16)
        big = pool.acquire(16)
        trials = [SimpleNamespace(status=TrialStatus.RUNNING),
                  SimpleNamespace(status=TrialStatus.PENDING),
                  SimpleNamespace(status=TrialStatus.PAUSED),
                  SimpleNamespace(status=TrialStatus.TERMINATED)]
        runner = _fake_runner(FIFOScheduler(), trials)
        # 1 running + 2 waiting -> fair share 16 // 3 = 5 -> pow2 4
        assert FairShare().propose(runner, trials[0], pool, big) == 4


class TestDecisionIntervals:
    def test_declared_granularities(self):
        assert FIFOScheduler().decision_interval() == 0
        assert ASHAScheduler(max_t=8).decision_interval() == 1
        assert HyperBandScheduler(max_t=8).decision_interval() == 1
        assert MedianStoppingRule().decision_interval() == 1
        assert PopulationBasedTraining(
            perturbation_interval=5).decision_interval() == 5

    @pytest.mark.parametrize("scheduler,expected", [
        (FIFOScheduler(metric="loss", mode="min"), 4),
        (ASHAScheduler(metric="loss", mode="min", max_t=8), 1),
        (PopulationBasedTraining(metric="loss", mode="min",
                                 perturbation_interval=3), 1),
    ])
    def test_broker_clamps_lookahead(self, scheduler, expected):
        """Exactness rule: full lookahead only for run-to-completion
        schedulers; anything that can stop/perturb is clamped to 1."""
        ex = SerialMeshExecutor(lambda n: None, CheckpointManager(ObjectStore()))
        broker = ResourceBroker(lookahead=4)
        TrialRunner(scheduler, ex, broker=broker)
        assert broker.effective_lookahead == expected
        assert ex.lookahead == expected


# ---------------------------------------------------------------------------------
# Per-tier resize: grow path, state continuity, rollback fallback
# ---------------------------------------------------------------------------------

@pytest.mark.timeout(300)
class TestInHostElastic:
    @pytest.mark.parametrize("executor", ["serial", "concurrent"])
    def test_greedy_grow_preserves_state(self, executor):
        from _worker_trainables import SliceCounter

        an = run_experiments(
            SliceCounter, {"x": 1},
            scheduler=FIFOScheduler(metric="loss", mode="min"),
            stop={"training_iteration": 6},
            total_devices=8,
            slice_pool=SlicePool(n_virtual=8),
            resources_per_trial=Resources(devices=2),
            executor=executor, elastic="greedy", checkpoint_freq=0,
        )
        t = an.trials[0]
        assert t.status == TrialStatus.TERMINATED
        # contiguous results and counter state across every SAVE/RESTORE hop
        assert [r.training_iteration for r in t.results] == [1, 2, 3, 4, 5, 6]
        assert [r.metrics["n"] for r in t.results] == [1, 2, 3, 4, 5, 6]
        devs = [r.metrics["devices"] for r in t.results]
        assert devs[0] == 2 and devs[-1] == 8 and devs == sorted(devs), devs

    @pytest.mark.parametrize("executor", ["serial", "concurrent"])
    def test_failed_rebuild_falls_back_to_old_slice(self, executor):
        from _worker_trainables import GrowAllergic

        rec = Recorder()
        pool = SlicePool(n_virtual=8)
        if executor == "serial":
            ex = SerialMeshExecutor(lambda n: GrowAllergic,
                                    CheckpointManager(ObjectStore()),
                                    total_devices=8, slice_pool=pool)
        else:
            from repro.core import ConcurrentMeshExecutor
            ex = ConcurrentMeshExecutor(lambda n: GrowAllergic,
                                        CheckpointManager(ObjectStore()),
                                        total_devices=8, slice_pool=pool)
        broker = ResourceBroker(policy=GreedyFill())
        runner = TrialRunner(FIFOScheduler(metric="loss", mode="min"), ex,
                             logger=rec,
                             stopping_criteria={"training_iteration": 5},
                             broker=broker)
        trial = Trial({"max_ok": 2}, resources=Resources(devices=2),
                      stopping_criteria={"training_iteration": 5})
        runner.add_trial(trial)
        runner.run()
        assert trial.status == TrialStatus.TERMINATED, trial.error
        assert [r.training_iteration for r in trial.results] == [1, 2, 3, 4, 5]
        # every grow attempt was rolled back; the trial never left 2 devices
        assert all(r.metrics["devices"] == 2 for r in trial.results)
        assert rec.of(EventType.RESIZE_FAILED) and broker.n_resize_failed > 0
        assert broker.n_resized == 0
        assert trial.resources.devices == 2
        assert pool.n_free == 8 and pool.fragments() == 0

    def test_fair_share_shrinks_to_admit_waiting_trial(self):
        """A big runner is trimmed at its boundary so a queued trial can
        launch — rebalance across RUNNING trials, not just greedy growth."""
        from _worker_trainables import SliceCounter

        rec = Recorder()
        pool = SlicePool(n_virtual=8)
        ex = SerialMeshExecutor(lambda n: SliceCounter,
                                CheckpointManager(ObjectStore()),
                                total_devices=8, slice_pool=pool)
        broker = ResourceBroker(policy=FairShare())
        runner = TrialRunner(FIFOScheduler(metric="loss", mode="min"), ex,
                             logger=rec,
                             stopping_criteria={"training_iteration": 6},
                             broker=broker)
        hog = Trial({}, resources=Resources(devices=8),
                    stopping_criteria={"training_iteration": 6})
        waiter = Trial({}, resources=Resources(devices=4),
                       stopping_criteria={"training_iteration": 6})
        runner.add_trial(hog)
        runner.add_trial(waiter)
        runner.run()
        assert hog.status == waiter.status == TrialStatus.TERMINATED
        assert broker.n_resized >= 1 and rec.of(EventType.RESIZED)
        assert waiter.results  # it actually ran
        # the hog was shrunk from 8 down to a fair share at some boundary
        hog_devs = [r.metrics["devices"] for r in hog.results]
        assert hog_devs[0] == 8 and min(hog_devs) <= 4, hog_devs
        assert pool.n_free == 8


@pytest.mark.timeout(600)
class TestProcessElastic:
    def test_in_place_resize_same_process(self):
        """RESIZE over the pipe rebuilds the trainable inside the warm child:
        same pid before/after, counter state carried over the spill surface,
        slice doubled by the broker."""
        pool = SlicePool(n_virtual=8)
        ex = ProcessMeshExecutor(
            factory_resolver=lambda n: factory("SliceCounter"),
            checkpoint_manager=CheckpointManager(ObjectStore()),
            total_devices=8, slice_pool=pool, checkpoint_freq=1)
        broker = ResourceBroker(policy=GreedyFill())
        rec = Recorder()
        runner = TrialRunner(FIFOScheduler(metric="loss", mode="min"), ex,
                             logger=rec,
                             stopping_criteria={"training_iteration": 6},
                             broker=broker)
        trial = Trial({}, resources=Resources(devices=2),
                      stopping_criteria={"training_iteration": 6})
        runner.add_trial(trial)
        pids = set()
        while runner.step():
            pid = ex.worker_pid(trial.trial_id)
            if pid:
                pids.add(pid)
        assert trial.status == TrialStatus.TERMINATED, trial.error
        assert len(pids) == 1, f"resize must not respawn the process: {pids}"
        assert [r.metrics["n"] for r in trial.results] == [1, 2, 3, 4, 5, 6]
        devs = [r.metrics["devices"] for r in trial.results]
        assert devs[0] == 2 and devs[-1] == 8, devs
        assert broker.n_resized >= 2 and len(rec.of(EventType.RESIZED)) >= 2
        assert trial.resources.devices == 8
        assert pool.n_free == 8

    def test_child_rebuild_failure_falls_back(self):
        """A child-side RESIZE failure is non-fatal: the old trainable keeps
        serving in the same process and the pool swap is rolled back."""
        pool = SlicePool(n_virtual=8)
        ex = ProcessMeshExecutor(
            factory_resolver=lambda n: factory("GrowAllergic"),
            checkpoint_manager=CheckpointManager(ObjectStore()),
            total_devices=8, slice_pool=pool, checkpoint_freq=0)
        broker = ResourceBroker(policy=GreedyFill())
        rec = Recorder()
        runner = TrialRunner(FIFOScheduler(metric="loss", mode="min"), ex,
                             logger=rec,
                             stopping_criteria={"training_iteration": 5},
                             broker=broker)
        trial = Trial({"max_ok": 2}, resources=Resources(devices=2),
                      stopping_criteria={"training_iteration": 5})
        runner.add_trial(trial)
        runner.run()
        assert trial.status == TrialStatus.TERMINATED, trial.error
        assert all(r.metrics["devices"] == 2 for r in trial.results)
        assert broker.n_resize_failed > 0 and rec.of(EventType.RESIZE_FAILED)
        assert trial.resources.devices == 2 and pool.n_free == 8

    def test_lookahead_credits_fifo_stream_exact(self):
        """k=4 on FIFO: the worker pipelines STEPs, yet per-trial results are
        exactly the serial stream (extra in-flight results past the stop
        boundary are fenced as stale), and the CREDITS grant is logged."""
        register_worker_factory("SliceCounter", factory("SliceCounter"))
        rec_events = []

        class _Rec(Logger):
            def on_event(self, trial, event):
                rec_events.append(event)

        ex = ProcessMeshExecutor(
            factory_resolver=lambda n: factory("SliceCounter"),
            checkpoint_manager=CheckpointManager(ObjectStore()),
            total_devices=4, checkpoint_freq=0)
        broker = ResourceBroker(lookahead=4)
        runner = TrialRunner(FIFOScheduler(metric="loss", mode="min"), ex,
                             logger=_Rec(),
                             stopping_criteria={"training_iteration": 8},
                             broker=broker)
        trials = [Trial({}, resources=Resources(devices=1),
                        stopping_criteria={"training_iteration": 8})
                  for _ in range(3)]
        for t in trials:
            runner.add_trial(t)
        runner.run()
        assert broker.effective_lookahead == 4
        for t in trials:
            assert t.status == TrialStatus.TERMINATED, t.error
            assert [r.training_iteration for r in t.results] == list(range(1, 9))
        credits = [e for e in rec_events if e.type == EventType.CREDITS]
        assert credits and credits[0].info["granted"] == 4

    @pytest.mark.parametrize("executor", ["concurrent", "process"])
    def test_resize_under_lookahead_backlog_keeps_window(self, executor):
        """Resize while k=4 un-consumed results sit in the bus: the credit
        window must self-maintain (no inflation past k, no collapse) and the
        per-trial stream must stay exact through the resize."""
        register_worker_factory("SliceCounter", factory("SliceCounter"))
        from _worker_trainables import SliceCounter

        an = run_experiments(
            SliceCounter, {"x": 1},
            scheduler=FIFOScheduler(metric="loss", mode="min"),
            stop={"training_iteration": 10},
            total_devices=8,
            slice_pool=SlicePool(n_virtual=8),
            resources_per_trial=Resources(devices=2),
            executor=executor, elastic="greedy", lookahead=4,
            checkpoint_freq=1,
        )
        t = an.trials[0]
        assert t.status == TrialStatus.TERMINATED, t.error
        assert [r.training_iteration for r in t.results] == list(range(1, 11))
        assert [r.metrics["n"] for r in t.results] == list(range(1, 11))
        devs = [r.metrics["devices"] for r in t.results]
        assert devs[0] == 2 and devs[-1] == 8 and devs == sorted(devs), devs


# ---------------------------------------------------------------------------------
# k=1 credit equivalence: elastic process tier == serial tier, whole matrix
# ---------------------------------------------------------------------------------

SCHEDULERS = {
    "fifo": lambda: FIFOScheduler(metric="loss", mode="min"),
    "asha": lambda: ASHAScheduler(metric="loss", mode="min", max_t=6,
                                  grace_period=2, reduction_factor=2),
    "hyperband": lambda: HyperBandScheduler(metric="loss", mode="min",
                                            max_t=4, eta=2),
    "pbt": lambda: PopulationBasedTraining(
        metric="loss", mode="min", perturbation_interval=2,
        hyperparam_mutations={"lr": [0.005, 0.02, 0.08]}, seed=0),
}


@pytest.mark.timeout(600)
class TestCreditEquivalenceMatrix:
    """With a capacity-1 pool every tier executes trials sequentially, so the
    event stream — and therefore every scheduler decision — is deterministic.
    An elastic run (broker on, lookahead requested 4, clamped to 1 for every
    scheduler that can stop/perturb) must reproduce the serial executor's
    trial statuses and result streams exactly."""

    @pytest.mark.parametrize("name", list(SCHEDULERS))
    def test_elastic_k1_matches_serial(self, name):
        from _worker_trainables import LrCounter

        def sweep(executor, elastic):
            register_worker_factory("LrCounter", factory("LrCounter"))
            return run_experiments(
                LrCounter,
                {"lr": grid_search([0.005, 0.02, 0.08])},
                scheduler=SCHEDULERS[name](),
                stop={"training_iteration": 6},
                total_devices=1,
                slice_pool=SlicePool(n_virtual=1),
                resources_per_trial=Resources(devices=1),
                checkpoint_freq=1,
                executor=executor,
                elastic="greedy" if elastic else None,
                lookahead=4 if elastic else 1,
                seed=0,
            )

        serial = sweep("serial", elastic=False)
        elastic = sweep("process", elastic=True)
        assert elastic.best_value() == pytest.approx(serial.best_value())
        # Same grid order both runs; PBT mutates configs, so pair by position.
        assert len(elastic.trials) == len(serial.trials)
        for t, ref in zip(elastic.trials, serial.trials):
            assert t.config["lr"] == pytest.approx(ref.config["lr"]), name
            assert t.status == ref.status, (name, t.trial_id, t.error)
            assert ([r.training_iteration for r in t.results]
                    == [r.training_iteration for r in ref.results]), name
            for mine, theirs in zip(t.results, ref.results):
                assert mine.metrics["loss"] == pytest.approx(
                    theirs.metrics["loss"]), name
