"""CPU tests of ``correct``: a whole run of a small cell, with the harness's
look for a chip skipped, comes out correct; with the timed path broken
underneath it comes out not correct, once for each fault a one-chip
training cell can have; and the control, the reference's forward pass in
bfloat16 in the program's step with float32 weights and optimizer, fails
the limits the program meets, in its readings and in a whole run, as does
the program's own bfloat16 path."""
import math
import time

import pytest

from bench_tiny import TINY_LIMITS, add_cell, make_tree
from bench import calibrate, harness


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("bench"))


def _run(tree, cell, seconds=1.0, seed=2**31 + 11):
    return harness.run_cell(harness.load_cell(cell, tree), seed, seconds, False,
                            time.perf_counter(), out_dir=tree / ".bench_out")


def test_long_cell_is_correct(tree):
    res = _run(tree, "tiny.long")
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert res["device"]["platform"] == "cpu" and res["failed"] == 0
    assert all(c["value"] < c["limit"] for c in res["checks"].values())


def test_scheduler_named_in_a_traffic_file_runs(tmp_path):
    tree = make_tree(tmp_path)
    name = add_cell(tree, "asha", scheduler="ASHAScheduler", max_t=2,
                    scheduler_kwargs={"max_t": 2, "grace_period": 1,
                                      "reduction_factor": 4})
    res = _run(tree, name, seconds=3.0)
    assert res["correct"], res["checks"]
    assert {"setup_s", "sweep_tokens_per_s", "trial_start_s"} <= set(res["metrics"])


def test_search_cell_is_correct_and_its_window_holds_whole_trials(tree):
    res = _run(tree, "tiny.search", seconds=4.0)
    assert res["correct"], res["checks"]
    assert {"setup_s", "sweep_tokens_per_s", "trial_start_s"} <= set(res["metrics"])
    budget = harness.load_cell("tiny.search", tree).traffic["max_t"]
    assert res["attempted"] >= budget and res["attempted"] % budget == 0


def _broken(kind):
    from repro.train import trainable as program

    make = program.make_train_step

    def make_train_step(cfg, opt, microbatch=0):
        step = make(cfg, opt, microbatch)

        def unchanged(state, batch):
            return state, step(state, batch)[1]

        def half_batch(state, batch):
            return step(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})

        return {"unchanged": unchanged, "half_batch": half_batch}[kind]

    return make_train_step


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(tree, monkeypatch, fault):
    from repro.train import trainable as program

    monkeypatch.setattr(program, "make_train_step", _broken(fault))
    res = _run(tree, "tiny.long")
    assert res["correct"] is False
    failed = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    assert failed
    if fault == "unchanged":
        assert math.isclose(res["checks"]["change_gap"]["value"], 1.0, rel_tol=1e-4)


def test_control_in_bfloat16_fails_the_limits(tree, capsys):
    import json

    cell = harness.load_cell("tiny.long", tree)
    calibrate.calibrate(cell, [2**31 + 29], controls=1)
    calibrate.control_runs(cell, [2**31 + 31], seconds=1.0)
    rows = {r["kind"]: r for r in map(json.loads, capsys.readouterr().out.splitlines())}
    over = lambda r: [k for k, lim in TINY_LIMITS.items() if r[k] > lim]
    assert over(rows["program"]) == []
    assert over(rows["bf16_activations"])
    assert over(rows["program_bf16"])
    assert over(rows["fault_half_batch"])
    assert rows["control_run"]["correct"] is False
