"""Control-plane observability (repro.obs, DESIGN.md §8).

Instrument/tracer unit coverage, the determinism contract (two identical
VirtualClock scenario runs export byte-identical Chrome traces), span
propagation across the process-worker pipe protocol, the metrics JSONL
snapshot stream, and the ConsoleLogger final-flush satellite fix.
"""
import json
import os

import pytest

from repro.core import (CheckpointManager, ConsoleLogger, EventType,
                        FIFOScheduler, JSONLLogger, ObjectStore,
                        ProcessMeshExecutor, Resources, Result,
                        TrainableFactory, Trial, TrialEvent, TrialRunner,
                        TrialStatus, VirtualClock)
from repro.obs import (NULL_OBS, NULL_TRACER, Counter, Gauge, Histogram,
                       MetricsRegistry, Observability, Tracer, span)
from repro.testing import crash_storm, run_scenario

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


# -- instruments ------------------------------------------------------------------------

class TestMetrics:
    def test_counter(self):
        c = Counter("x")
        c.inc()
        c.inc(4)
        assert c.value == 5
        assert c.snapshot() == 5

    def test_gauge_last_write_wins(self):
        g = Gauge("x")
        g.set(3.0)
        g.set(1.5)
        assert g.snapshot() == 1.5

    def test_histogram_aggregates(self):
        h = Histogram("x")
        for v in (1.0, 2.0, 3.0, 10.0):
            h.observe(v)
        s = h.snapshot()
        assert s["count"] == 4
        assert s["sum"] == 16.0
        assert s["min"] == 1.0 and s["max"] == 10.0
        assert s["mean"] == 4.0

    def test_histogram_percentile_conservative(self):
        h = Histogram("x")
        for v in (1.0, 1.0, 1.0, 100.0):
            h.observe(v)
        # Upper-boundary estimate: p50 from the [1,2) bucket, p100 exact max.
        assert 1.0 <= h.percentile(50) <= 2.0
        assert h.percentile(100) == 100.0
        assert Histogram("empty").percentile(99) == 0.0
        assert Histogram("empty").snapshot()["count"] == 0

    def test_registry_create_on_first_use_and_kind_guard(self):
        r = MetricsRegistry()
        c = r.counter("a.b")
        assert r.counter("a.b") is c
        with pytest.raises(TypeError):
            r.gauge("a.b")
        assert r.get("nope") is None
        r.histogram("h")
        assert r.names() == ["a.b", "h"]

    def test_snapshot_line_is_canonical_json(self):
        r = MetricsRegistry()
        r.counter("z").inc()
        r.counter("a").inc(2)
        line = r.snapshot_line(123.0)
        rec = json.loads(line)
        assert rec == {"t": 123.0, "schema_version": 1,
                       "metrics": {"a": 2, "z": 1}}
        # Fixed separators + sorted keys: the byte form is reproducible.
        assert line == r.snapshot_line(123.0)


# -- tracer -----------------------------------------------------------------------------

class TestTracer:
    def test_span_ctx_stamps_from_injected_clock(self):
        vc = VirtualClock()
        tr = Tracer(clock=vc)
        with tr.span("step", "t-1", cat="train", iteration=3) as sp:
            vc.sleep(2.0)
            sp.arg("note", "ok")
        (s,) = tr.spans
        assert (s.name, s.trace, s.cat, s.proc) == ("step", "t-1", "train", "host")
        assert s.ts == vc._epoch and s.dur == 2.0
        assert s.args == {"iteration": 3, "note": "ok"}

    def test_span_records_error_on_exception(self):
        tr = Tracer(clock=VirtualClock())
        with pytest.raises(ValueError):
            with tr.span("build", "t-1"):
                raise ValueError("boom")
        assert tr.spans[0].args["error"] == "ValueError"

    def test_begin_end_and_end_all(self):
        vc = VirtualClock()
        tr = Tracer(clock=vc)
        tr.begin(("trial", "t-1"), "trial", "t-1", cat="lifecycle")
        tr.begin(("trial", "t-2"), "trial", "t-2", cat="lifecycle")
        vc.sleep(5.0)
        tr.end(("trial", "t-1"), status="TERMINATED")
        tr.end(("trial", "t-1"))  # double-end: no-op
        tr.end_all(status="ABANDONED")
        spans = tr.spans
        assert len(spans) == 2
        assert spans[0].args["status"] == "TERMINATED" and spans[0].dur == 5.0
        assert spans[1].args["status"] == "ABANDONED"

    def test_non_scalar_args_dropped(self):
        tr = Tracer(clock=VirtualClock())
        tr.record("x", "t-1", 0.0, 1.0, good=1, bad=object(), arr=[1, 2])
        assert tr.spans[0].args == {"good": 1}

    def test_adopt_wire_tuples(self):
        tr = Tracer(clock=VirtualClock())
        tr.adopt("t-9", [("step", 1.0, 0.5, "train", "worker", {"iteration": 2})])
        (s,) = tr.spans
        assert s.trace == "t-9" and s.proc == "worker" and s.dur == 0.5

    def test_disabled_tracer_is_inert(self):
        tr = NULL_TRACER
        assert not tr.enabled
        ctx = tr.span("x", "t")
        assert ctx is tr.span("y", "t")  # shared no-op ctx, no allocation
        with ctx as sp:
            sp.arg("a", 1)
        tr.record("x", "t", 0.0, 1.0)
        tr.begin("k", "x", "t")
        tr.end("k")
        tr.adopt("t", [("x", 0.0, 1.0, "", "host", {})])
        assert tr.spans == []

    def test_chrome_export_shape(self, tmp_path):
        vc = VirtualClock()
        tr = Tracer(clock=vc)
        tr.record("sched", "", vc.time(), 0.001, cat="sched")
        with tr.span("step", "t-1", cat="train"):
            vc.sleep(1.0)
        path = tr.export_chrome(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
        evs = doc["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M"]
        xs = [e for e in evs if e["ph"] == "X"]
        assert {m["name"] for m in meta} == {"process_name", "thread_name"}
        assert len(xs) == 2
        # Control-plane span rides tid 0; trial span gets its own row.
        by_name = {e["name"]: e for e in xs}
        assert by_name["sched"]["tid"] == 0
        assert by_name["step"]["tid"] == 1
        # µs ints, rebased to the earliest span, dur floored at 1.
        assert by_name["sched"]["ts"] == 0 and by_name["sched"]["dur"] == 1000
        assert by_name["step"]["dur"] == 1_000_000


class TestNestedSpans:
    """``repro.obs.span``: a child of the open context span, or nothing."""

    def test_no_op_with_no_span_open(self):
        ctx = span("data", step=0)
        assert ctx is span("other") is NULL_TRACER.span("x", "t")
        with ctx as sp:
            sp.arg("a", 1)
            sp.set_trace("t")
            sp.discard()

    def test_no_op_under_a_disabled_tracer(self):
        with NULL_TRACER.span("step", "t-1"):
            assert span("data") is NULL_TRACER.span("x", "t")
        tr = Tracer(clock=VirtualClock(), enabled=False)
        with tr.span("step", "t-1"):
            with span("data"):
                pass
        assert tr.spans == []

    def test_child_carries_parent_name_and_trace(self):
        vc = VirtualClock()
        tr = Tracer(clock=vc)
        with tr.span("step", "t-1", cat="train"):
            vc.sleep(1.0)
            with span("data", cat="data", step=4) as sp:
                vc.sleep(0.5)
                with span("inner"):
                    vc.sleep(0.25)
                sp.arg("rows", 8)
        assert span("after") is NULL_TRACER.span("x", "t")  # context restored
        by = {s.name: s for s in tr.spans}
        assert set(by) == {"step", "data", "inner"}
        data, inner, step = by["data"], by["inner"], by["step"]
        assert data.trace == inner.trace == "t-1"
        assert data.args == {"step": 4, "rows": 8, "parent": "step"}
        assert data.cat == "data" and data.dur == 0.75
        assert inner.args == {"parent": "data"} and inner.dur == 0.25
        assert "parent" not in step.args and step.dur == 1.75
        assert step.ts < data.ts < inner.ts

    def test_each_tracer_keeps_its_children(self):
        a, b = Tracer(clock=VirtualClock()), Tracer(clock=VirtualClock())
        with a.span("step", "t-a"):
            with b.span("build", "t-b"):
                with span("data"):
                    pass
            with span("data"):
                pass
        assert [(s.name, s.args.get("parent")) for s in b.spans] == \
            [("data", "build"), ("build", None)]
        assert [(s.name, s.args.get("parent")) for s in a.spans] == \
            [("data", "step"), ("step", None)]

    def test_set_trace_and_discard(self):
        tr = Tracer(clock=VirtualClock())
        with tr.span("schedule.decision", cat="sched") as sp:
            sp.set_trace("t-7")
        with tr.span("schedule.decision", cat="sched") as sp:
            sp.discard()
        (s,) = tr.spans
        assert s.trace == "t-7"

    def test_jit_events_become_children_of_the_open_span(self):
        from repro.core.clock import WallClock
        tr = Tracer(clock=WallClock())
        ev = "/jax/core/compile/jaxpr_trace_duration"
        tr.on_jax_event(ev, 1.0, 2.0, fun_name="f")           # no span open
        with tr.span("step", "t-1"):
            tr.on_jax_event(ev, 1.0, 1.5, fun_name="train_step")
            tr.on_jax_event("/jax/core/compile/backend_compile_duration",
                            2.0, 4.0, fun_name="jit(train_step)")
            tr.on_jax_event("/jax/other/event", 2.0, 3.0)
            Tracer(clock=WallClock()).on_jax_event(ev, 1.0, 2.0)  # not its span
        jit = [s for s in tr.spans if s.name.startswith("jit.")]
        assert [(s.name, s.trace, s.ts, s.dur, s.cat, s.args) for s in jit] == [
            ("jit.trace", "t-1", 1.0, 0.5, "compile",
             {"parent": "step", "fun_name": "train_step"}),
            ("jit.compile", "t-1", 2.0, 2.0, "compile",
             {"parent": "step", "fun_name": "jit(train_step)"})]

    def test_jit_events_are_ignored_on_a_virtual_clock(self):
        tr = Tracer(clock=VirtualClock())
        with tr.span("step", "t-1"):
            tr.on_jax_event("/jax/core/compile/jaxpr_trace_duration", 1.0, 2.0)
        assert [s.name for s in tr.spans] == ["step"]

    def test_observability_listens_until_closed(self):
        import jax
        import jax.numpy as jnp
        from repro.core.clock import WallClock
        obs = Observability(trace=True, clock=WallClock())
        tr = obs.tracer
        with tr.span("step", "t-1"):
            jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(3))
        names = {s.name for s in tr.spans}
        assert {"jit.trace", "jit.lower", "jit.compile"} <= names
        obs.close()
        n = len(tr.spans)
        with tr.span("step", "t-1"):
            jax.jit(lambda x: x * 5.0 - 1.0)(jnp.ones(3))
        assert [s.name for s in tr.spans[n:]] == ["step"]


class TestNullObs:
    def test_null_obs_is_shared_and_inert(self):
        assert NULL_OBS.active is False
        assert NULL_OBS.metrics is None
        assert NULL_OBS.tracer is NULL_TRACER
        NULL_OBS.on_event(TrialEvent(EventType.RESULT, "t-1"))
        assert NULL_OBS.maybe_snapshot(None) is False
        NULL_OBS.close(None)  # idempotent no-op


# -- determinism: byte-identical traces ---------------------------------------------------

def _storm_trace(executor: str, token: str) -> str:
    obs = Observability(trace=True, metrics=True)
    scenario = crash_storm(n_trials=40, seed=3)
    res = run_scenario(scenario,
                       lambda: FIFOScheduler(metric="loss", mode="min"),
                       executor=executor, pool_devices=8,
                       obs=obs, token=token)
    obs.close(res.executor)
    assert any(t.num_failures > 0 for t in res.trials)  # storm engaged
    return obs.tracer.chrome_json()


class TestTraceDeterminism:
    @pytest.mark.parametrize("executor", ["serial", "concurrent"])
    def test_identical_runs_export_identical_bytes(self, executor):
        a = _storm_trace(executor, token="det")
        b = _storm_trace(executor, token="det")
        assert a == b
        doc = json.loads(a)
        names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        # The full lifecycle taxonomy shows up in a crash storm.
        assert {"trial", "schedule.decision", "slice.acquire", "build",
                "step", "ckpt.save", "restart"} <= names

    def test_restarted_trial_spans_share_one_trace(self):
        obs = Observability(trace=True)
        scenario = crash_storm(n_trials=20, seed=3)
        res = run_scenario(scenario,
                           lambda: FIFOScheduler(metric="loss", mode="min"),
                           executor="concurrent", pool_devices=8,
                           obs=obs, token="retr")
        obs.close(res.executor)
        crashed = [t for t in res.trials
                   if t.num_failures > 0 and t.status == TrialStatus.TERMINATED]
        assert crashed
        tid = crashed[0].trial_id
        spans = [s for s in obs.tracer.spans if s.trace == tid]
        lives = [s for s in spans if s.name == "trial"]
        # One lifecycle span per (re)launch, all on the same trace row.
        assert len(lives) == crashed[0].num_failures + 1
        assert lives[0].args["status"] == "REQUEUED"
        assert lives[-1].args["status"] == "TERMINATED"
        assert [s.name for s in spans if s.name == "restart"]
        restores = [s for s in spans if s.name == "ckpt.restore"]
        assert restores and all(s.cat == "ckpt" for s in restores)


# -- process tier: spans cross the pipe ----------------------------------------------------

class TestProcessTierSpans:
    def test_child_spans_nest_inside_parent_trial_span(self):
        obs = Observability(trace=True, metrics=True)
        factory = TrainableFactory(target="_worker_trainables:Counter",
                                   sys_path=(TESTS_DIR,))
        from repro.dist.submesh import SlicePool
        ex = ProcessMeshExecutor(
            factory_resolver=lambda _n: factory,
            checkpoint_manager=CheckpointManager(ObjectStore()),
            total_devices=4, slice_pool=SlicePool(n_virtual=4),
            checkpoint_freq=1, obs=obs)
        runner = TrialRunner(FIFOScheduler(metric="loss", mode="min"), ex,
                             stopping_criteria={"training_iteration": 3},
                             obs=obs)
        t = Trial({}, resources=Resources(devices=1),
                  stopping_criteria={"training_iteration": 3})
        runner.add_trial(t)
        trials = runner.run()
        obs.close(ex)
        assert trials[0].status == TrialStatus.TERMINATED

        spans = [s for s in obs.tracer.spans if s.trace == t.trial_id]
        host = [s for s in spans if s.proc == "host"]
        child = [s for s in spans if s.proc == "worker"]
        assert {"trial", "schedule.decision", "slice.acquire"} <= \
            {s.name for s in host}
        assert {"build", "step", "ckpt.save"} <= {s.name for s in child}
        steps = [s for s in child if s.name == "step"]
        assert len(steps) == 3
        assert all(s.args.get("pid") for s in child if s.name == "build")
        # Child spans join the parent trace and nest inside its lifecycle
        # span (same host, wall time on both sides of the pipe).
        (life,) = [s for s in host if s.name == "trial"]
        eps = 0.05
        for s in child:
            assert s.ts >= life.ts - eps
            assert s.ts + s.dur <= life.ts + life.dur + eps
        # ckpt bytes crossed the pipe into the metrics registry.
        assert obs.metrics.histogram("ckpt.bytes").count >= 1


# -- metrics stream + loggers -------------------------------------------------------------

class TestMetricsStream:
    def test_snapshot_stream_and_final_snapshot(self, tmp_path):
        mpath = str(tmp_path / "metrics.jsonl")
        obs = Observability(metrics=mpath, metrics_interval=30.0)
        scenario = crash_storm(n_trials=40, seed=1)
        res = run_scenario(scenario,
                           lambda: FIFOScheduler(metric="loss", mode="min"),
                           executor="concurrent", pool_devices=8,
                           obs=obs, token="ms")
        obs.close(res.executor)
        recs = [json.loads(l) for l in open(mpath)]
        assert len(recs) >= 2  # periodic snapshots + the close() snapshot
        for rec in recs:
            assert rec["schema_version"] == 1
            assert "metrics" in rec
        final = recs[-1]["metrics"]
        assert final["events.result"] > 0
        assert final["bus.published"] > 0
        assert final["bus.fanin_us"]["count"] > 0
        assert final["sched.choose_us"]["count"] > 0
        assert final["pool.acquire_us"]["count"] > 0
        assert final["ckpt.save_us"]["count"] > 0
        assert final["trials.restarts"] > 0
        # Snapshot timestamps ride the virtual axis, strictly increasing.
        ts = [rec["t"] for rec in recs]
        assert ts == sorted(ts) and ts[0] >= res.clock._epoch

    def test_maybe_snapshot_throttles_on_clock(self, tmp_path):
        vc = VirtualClock()
        obs = Observability(metrics=str(tmp_path / "m.jsonl"),
                            metrics_interval=10.0, clock=vc)
        assert obs.maybe_snapshot(None) is True   # first call always writes
        assert obs.maybe_snapshot(None) is False  # inside the window
        vc.sleep(10.0)
        assert obs.maybe_snapshot(None) is True


class TestConsoleLoggerFlush:
    def test_final_flush_emits_throttled_result(self, capsys):
        vc = VirtualClock()
        lg = ConsoleLogger(interval_s=5.0, clock=vc)
        t = Trial({})
        vc.sleep(10.0)
        lg.on_result(t, Result(t.trial_id, 1, {"loss": 1.0}))   # prints
        vc.sleep(1.0)
        lg.on_result(t, Result(t.trial_id, 2, {"loss": 0.5}))   # throttled
        lg.on_experiment_end([t])  # final flush INSIDE the 5s window
        out = [l for l in capsys.readouterr().out.splitlines() if l]
        assert "iter=1" in out[0]
        assert "iter=2" in out[1]  # the throttled last status still lands
        assert "experiment done" in out[-1]

    def test_flush_is_idempotent_and_quiet_without_pending(self, capsys):
        lg = ConsoleLogger(clock=VirtualClock())
        lg.flush()
        lg.flush()
        assert capsys.readouterr().out == ""

    def test_status_table_with_metrics(self, capsys):
        obs = Observability(metrics=True)
        obs.metrics.counter("events.result").inc(7)
        obs.metrics.histogram("sched.choose_us").observe(12.0)
        lg = ConsoleLogger(clock=VirtualClock(), obs=obs)
        lg.flush()
        out = capsys.readouterr().out
        assert "control-plane status" in out
        assert "results=7" in out
        assert "choose=12.0us" in out
        assert ConsoleLogger(clock=VirtualClock()).status_table() == ""


class TestJSONLHeader:
    def test_run_header_round_trip(self, tmp_path):
        vc = VirtualClock()
        path = str(tmp_path / "e.jsonl")
        lg = JSONLLogger(path, clock=vc, run_id="run-42", executor="serial")
        t = Trial({"lr": 0.1})
        lg.on_result(t, Result(t.trial_id, 1, {"loss": 0.5}))
        lg.close()
        header = json.loads(open(path).readline())
        assert header == {"event": "run_header",
                          "schema_version": JSONLLogger.SCHEMA_VERSION,
                          "run_id": "run-42", "clock": "VirtualClock",
                          "executor": "serial", "decisions": True,
                          "t": vc._epoch}

    def test_old_readers_stay_compatible(self, tmp_path):
        """A v1-era reader that filters on the ``event`` field skips the
        header record and unknown fields without breaking."""
        path = str(tmp_path / "e.jsonl")
        lg = JSONLLogger(path)
        t = Trial({"lr": 0.1})
        lg.on_result(t, Result(t.trial_id, 1, {"loss": 0.5}))
        t.set_status(TrialStatus.TERMINATED)
        lg.on_trial_complete(t)
        lg.close()
        results = [r for r in map(json.loads, open(path))
                   if r["event"] == "result"]
        assert len(results) == 1 and results[0]["metrics"]["loss"] == 0.5
        assert lg.run_id.startswith("run-")
