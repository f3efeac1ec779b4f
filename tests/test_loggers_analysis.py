"""Loggers (CSV/JSONL/console) and ExperimentAnalysis coverage."""
import csv
import json
import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (CSVLogger, ConsoleLogger, ExperimentAnalysis,
                        JSONLLogger, Result, Trial, TrialStatus)


def make_trial_with_results(values, metric="loss"):
    t = Trial({"lr": 0.1})
    for i, v in enumerate(values, start=1):
        t.record_result(Result(trial_id=t.trial_id, training_iteration=i,
                               metrics={metric: float(v)}))
    return t


class TestLoggers:
    def test_csv_logger_writes_rows(self, tmp_path):
        lg = CSVLogger(str(tmp_path))
        t = Trial({"lr": 0.1})
        for i in range(3):
            lg.on_result(t, Result(t.trial_id, i + 1, {"loss": 1.0 / (i + 1)}))
        lg.close()
        path = os.path.join(str(tmp_path), f"{t.trial_id}.csv")
        with open(path) as f:
            rows = list(csv.DictReader(f))
        assert len(rows) == 3
        assert float(rows[2]["loss"]) == pytest.approx(1 / 3)

    def test_jsonl_logger_events(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        lg = JSONLLogger(path)
        t = Trial({"lr": 0.1})
        lg.on_result(t, Result(t.trial_id, 1, {"loss": 0.5}))
        t.set_status(TrialStatus.TERMINATED)
        lg.on_trial_complete(t)
        lg.close()
        events = [json.loads(l) for l in open(path)]
        assert [e["event"] for e in events] == ["run_header", "result", "complete"]
        assert events[0]["schema_version"] == JSONLLogger.SCHEMA_VERSION
        assert events[1]["metrics"]["loss"] == 0.5
        assert events[2]["status"] == "TERMINATED"

    def test_jsonl_skips_non_json_values(self, tmp_path):
        path = str(tmp_path / "e.jsonl")
        lg = JSONLLogger(path)
        t = Trial({"lr": 0.1, "obj": object()})
        lg.on_result(t, Result(t.trial_id, 1, {"loss": 0.5, "arr": np.ones(3)}))
        lg.close()
        ev = [json.loads(l) for l in open(path)
              if json.loads(l)["event"] == "result"][0]
        assert "obj" not in ev["config"] and "arr" not in ev["metrics"]

    def test_console_quiet(self, capsys):
        lg = ConsoleLogger(verbose=False)
        t = Trial({})
        lg.on_result(t, Result(t.trial_id, 1, {"loss": 1.0}))
        lg.on_experiment_end([t])
        assert capsys.readouterr().out == ""


class TestLoggersOnVirtualClock:
    """Clock-seam coverage (DESIGN.md §7): the JSONL fallback-timestamp path
    for bus-less events, and Console flush throttling, both driven
    deterministically on a VirtualClock instead of real 5-second gaps."""

    def test_jsonl_event_timestamps_virtual_and_fallback(self, tmp_path):
        from repro.core import EventType, TrialEvent, VirtualClock

        vc = VirtualClock()
        path = str(tmp_path / "events.jsonl")
        lg = JSONLLogger(path, clock=vc)
        t = Trial({})
        vc.sleep(100.0)
        # Stamped event (came off a bus): its timestamp must be preserved.
        lg.on_event(t, TrialEvent(EventType.HEARTBEAT_MISSED, t.trial_id,
                                  timestamp=vc.time()))
        vc.sleep(50.0)
        # Unstamped event (runner/broker handed it straight to the logger):
        # the logger's own clock supplies the time — the fallback path.
        lg.on_event(t, TrialEvent(EventType.RESTARTED, t.trial_id))
        lg.close()
        header, stamped, fallback = [json.loads(l) for l in open(path)]
        assert header["event"] == "run_header"
        assert header["clock"] == "VirtualClock"
        assert stamped["event"] == "heartbeat_missed"
        assert stamped["t"] == pytest.approx(vc._epoch + 100.0)
        assert fallback["event"] == "restarted"
        assert fallback["t"] == pytest.approx(vc._epoch + 150.0)

    def test_console_flush_throttle_on_virtual_time(self, capsys):
        from repro.core import VirtualClock

        vc = VirtualClock()
        lg = ConsoleLogger(interval_s=5.0, clock=vc)
        t = Trial({})
        vc.sleep(10.0)  # move past _last=0 so the first result prints
        lg.on_result(t, Result(t.trial_id, 1, {"loss": 1.0}))
        for i in range(2, 6):  # 4 results inside the 5s window: throttled
            vc.sleep(1.0)
            lg.on_result(t, Result(t.trial_id, i, {"loss": 1.0 / i}))
        vc.sleep(1.1)  # crosses the 5s boundary: prints again
        lg.on_result(t, Result(t.trial_id, 6, {"loss": 1.0 / 6}))
        out = [l for l in capsys.readouterr().out.splitlines() if l]
        assert len(out) == 2
        assert "iter=1" in out[0] and "iter=6" in out[1]
        assert lg._n_results == 6  # every result counted, two printed


class TestAnalysis:
    def test_best_trial_min_mode(self):
        a = make_trial_with_results([3, 2, 1])
        b = make_trial_with_results([5, 4, 3.5])
        an = ExperimentAnalysis([a, b], metric="loss", mode="min")
        assert an.best_trial() is a
        assert an.best_value() == 1.0

    def test_best_trial_max_mode(self):
        a = make_trial_with_results([0.1, 0.2], metric="accuracy")
        b = make_trial_with_results([0.3, 0.25], metric="accuracy")
        an = ExperimentAnalysis([a, b], metric="accuracy", mode="max")
        assert an.best_trial() is b
        assert an.best_value() == 0.3

    def test_empty_trials(self):
        an = ExperimentAnalysis([], metric="loss", mode="min")
        assert an.best_trial() is None and an.best_config() is None

    def test_trial_without_metric_ignored(self):
        a = make_trial_with_results([1.0])
        b = Trial({})  # no results
        an = ExperimentAnalysis([a, b], metric="loss", mode="min")
        assert an.best_trial() is a

    @given(st.lists(st.lists(st.floats(0.015625, 128.0, width=32), min_size=1,
                             max_size=5), min_size=1, max_size=6))
    @settings(max_examples=25, deadline=None)
    def test_best_value_is_global_min(self, curves):
        trials = [make_trial_with_results(c) for c in curves]
        an = ExperimentAnalysis(trials, metric="loss", mode="min")
        flat = [v for c in curves for v in c]
        assert an.best_value() == pytest.approx(min(flat))


class TestTrialInvariants:
    def test_finished_cannot_restart(self):
        t = Trial({})
        t.set_status(TrialStatus.TERMINATED)
        with pytest.raises(RuntimeError):
            t.set_status(TrialStatus.RUNNING)

    def test_should_stop_on_metric_threshold(self):
        t = Trial({}, stopping_criteria={"accuracy": 0.9})
        r = Result(t.trial_id, 1, {"accuracy": 0.95})
        assert t.should_stop(r)

    def test_best_value_modes(self):
        t = make_trial_with_results([3, 1, 2])
        assert t.best_value("loss", "min") == 1
        assert t.best_value("loss", "max") == 3
        assert t.best_value("nope", "min") is None
