"""Scheduler semantics: each of the six Table-1 algorithms behaves per its
source paper, all through the identical TrialScheduler interface."""
import numpy as np
import pytest

from repro.core import (ASHAScheduler, FIFOScheduler, HyperBandScheduler,
                        MedianStoppingRule, PopulationBasedTraining,
                        Resources, SchedulerDecision, Trial, TrialStatus,
                        TrialRunner, CheckpointManager, ObjectStore,
                        SerialMeshExecutor, Trainable, register_trainable,
                        run_experiments, uniform, loguniform)


class DecayTrainable(Trainable):
    """loss = quality + amplitude * 0.8^iter — separable quality per trial."""

    def setup(self, config):
        self.q = config["quality"]
        self.x = 1.0

    def step(self):
        self.x *= 0.8
        return {"loss": self.q + self.x}

    def save(self):
        return {"x": self.x, "q": self.q}

    def restore(self, state):
        self.x = state["x"]
        self.q = state["q"]

    def reset_config(self, cfg):
        self.q = cfg["quality"]
        return True


def run_qualities(qualities, scheduler, max_iter=20, devices=4, checkpoint_freq=1):
    store = ObjectStore()
    executor = SerialMeshExecutor(
        trainable_cls_resolver=lambda name: DecayTrainable,
        checkpoint_manager=CheckpointManager(store),
        total_devices=devices, checkpoint_freq=checkpoint_freq)
    runner = TrialRunner(scheduler, executor,
                         stopping_criteria={"training_iteration": max_iter})
    for i, q in enumerate(qualities):
        runner.add_trial(Trial({"quality": q}, trial_id=f"t{i:03d}",
                               stopping_criteria={"training_iteration": max_iter}))
    trials = runner.run()
    return {t.trial_id: t for t in trials}


class TestFIFO:
    def test_all_run_to_completion(self):
        trials = run_qualities([0.1, 0.5, 0.9], FIFOScheduler(metric="loss", mode="min"))
        assert all(t.training_iteration == 20 for t in trials.values())
        assert all(t.status == TrialStatus.TERMINATED for t in trials.values())


class TestASHA:
    def test_early_stops_bad_trials(self):
        qualities = list(np.linspace(0.0, 2.0, 16))
        sched = ASHAScheduler(metric="loss", mode="min", max_t=20,
                              grace_period=2, reduction_factor=3)
        trials = run_qualities(qualities, sched)
        total = sum(t.training_iteration for t in trials.values())
        assert total < 16 * 20 * 0.6, "ASHA should spend far less than full budget"
        best = min(trials.values(), key=lambda t: t.config["quality"])
        worst = max(trials.values(), key=lambda t: t.config["quality"])
        assert best.training_iteration > worst.training_iteration

    def test_max_t_terminates(self):
        sched = ASHAScheduler(metric="loss", mode="min", max_t=5, grace_period=1)
        trials = run_qualities([0.1], sched, max_iter=50)
        assert trials["t000"].training_iteration <= 5

    def test_validation(self):
        with pytest.raises(ValueError):
            ASHAScheduler(max_t=1, grace_period=5)

    def test_trial_judged_once_per_rung(self):
        """A later, worse result of a trial is not judged again at a rung it
        passed, where the only score to beat was its own earlier one."""
        from repro.core import Result
        sched = ASHAScheduler(metric="loss", mode="min", max_t=10,
                              grace_period=1, reduction_factor=2)
        t = Trial({"quality": 0.0}, trial_id="lone")
        sched.on_trial_add(None, t)
        for it, loss in enumerate([1.0, 1.1, 1.2, 1.3], start=1):
            verdict = sched.on_result(None, t, Result("lone", it, {"loss": loss}))
            assert verdict == SchedulerDecision.CONTINUE, (it, loss)
        # one score per rung, whatever the number of results
        rungs = sched._brackets[0].rungs
        assert {m: len(r) for m, r in rungs.items()} == {1: 1, 2: 1, 4: 1, 8: 0, 10: 0}

    def test_rung_state_round_trips(self):
        from repro.core import Result
        sched = ASHAScheduler(metric="loss", mode="min", max_t=9,
                              grace_period=1, reduction_factor=3)
        for i, loss in enumerate([2.0, 1.0, 3.0]):
            t = Trial({"quality": loss}, trial_id=f"t{i}")
            sched.on_trial_add(None, t)
            sched.on_result(None, t, Result(t.trial_id, 1, {"loss": loss}))
        clone = ASHAScheduler(metric="loss", mode="min", max_t=9,
                              grace_period=1, reduction_factor=3)
        clone.load_state_dict(sched.state_dict())
        assert clone._brackets[0].rungs == sched._brackets[0].rungs


class TestHyperBand:
    def test_budget_much_less_than_full(self):
        qualities = list(np.linspace(0.0, 2.0, 18))
        sched = HyperBandScheduler(metric="loss", mode="min", max_t=27, eta=3)
        trials = run_qualities(qualities, sched, max_iter=27)
        total = sum(t.training_iteration for t in trials.values())
        assert total < 18 * 27 * 0.5
        # survivors of successive halving are low-quality(=good) trials
        finishers = [t for t in trials.values() if t.training_iteration >= 27]
        assert finishers and all(t.config["quality"] < 1.0 for t in finishers)

    def test_pause_resume_through_checkpoints(self):
        """Synchronous HB pauses early arrivals; they must resume losslessly."""
        sched = HyperBandScheduler(metric="loss", mode="min", max_t=9, eta=3)
        trials = run_qualities([0.1, 0.2, 0.3, 0.4, 0.5, 0.6], sched,
                               max_iter=9, devices=2)
        assert any(t.training_iteration >= 9 for t in trials.values())


class TestMedianStopping:
    def test_stops_below_median(self):
        qualities = [0.0, 0.1, 0.2, 1.5, 1.6, 1.7]
        sched = MedianStoppingRule(metric="loss", mode="min", grace_period=2,
                                   min_samples_required=2)
        trials = run_qualities(qualities, sched, max_iter=15)
        good = [t for t in trials.values() if t.config["quality"] < 0.5]
        bad = [t for t in trials.values() if t.config["quality"] > 1.0]
        assert sched.n_stopped >= 2
        assert (sum(t.training_iteration for t in good) / len(good)
                > sum(t.training_iteration for t in bad) / len(bad))

    def test_grace_period_respected(self):
        sched = MedianStoppingRule(metric="loss", mode="min", grace_period=5,
                                   min_samples_required=1)
        trials = run_qualities([0.0, 5.0], sched, max_iter=8)
        assert trials["t001"].training_iteration >= 5


class TestPBT:
    def test_exploit_copies_good_params(self):
        sched = PopulationBasedTraining(
            metric="loss", mode="min", perturbation_interval=3,
            hyperparam_mutations={"quality": uniform(0.0, 2.0)},
            quantile_fraction=0.34, seed=0)
        trials = run_qualities([0.0, 1.0, 2.0], sched, max_iter=15, devices=3)
        assert sched.n_exploits >= 1
        # the worst trial should have been overwritten with a donor's config
        worst = trials["t002"]
        assert worst.config["quality"] < 2.0

    def test_explore_perturbs_numeric(self):
        sched = PopulationBasedTraining(metric="loss", mode="min",
                                        hyperparam_mutations={"lr": [1, 2, 4, 8]},
                                        resample_probability=0.0, seed=1)
        new = sched._explore({"lr": 2})
        assert new["lr"] in (1, 4)  # neighbour in the list

    def test_quantile_validation(self):
        with pytest.raises(ValueError):
            PopulationBasedTraining(quantile_fraction=0.9)


class TestSchedulerInterfaceUniformity:
    """Paper claim: one narrow interface is sufficient for all algorithms."""

    def test_all_schedulers_same_interface(self):
        from repro.core.schedulers.base import TrialScheduler
        for cls in (FIFOScheduler, ASHAScheduler, HyperBandScheduler,
                    MedianStoppingRule, PopulationBasedTraining):
            assert issubclass(cls, TrialScheduler)
            assert hasattr(cls, "on_result")
            assert hasattr(cls, "choose_trial_to_run")

    def test_decisions_are_narrow(self):
        assert {d.value for d in SchedulerDecision} == {
            "CONTINUE", "PAUSE", "STOP", "RESTART_WITH_CONFIG"}
