"""The readers of the program's spans: ``data_ms_per_step`` and
``lower_s_per_trial``, on span lists built by hand."""
import pytest

from bench_tiny import REPO
from bench import harness
from repro.obs import Span


def _reader(name):
    return harness._load_module(REPO / "bench" / "metrics" / f"{name}.py").read


def _span(name, trace, dur, **args):
    return Span(name, trace, 0.0, dur, args=args)


@pytest.mark.parametrize("name", ["data_ms_per_step", "lower_s_per_trial"])
@pytest.mark.parametrize("spans", [None, []])
def test_no_spans_read_nothing(name, spans):
    assert _reader(name)({"spans": spans}) is None


def test_data_ms_per_step_is_the_mean_data_span():
    read = _reader("data_ms_per_step")
    spans = [_span("data", "t-1", 0.030, step=0, parent="step"),
             _span("data", "t-1", 0.040, step=1, parent="step"),
             _span("data", "t-2", 0.020, step=0, parent="step"),
             _span("step", "t-1", 3.0, iteration=1),
             _span("jit.trace", "t-1", 1.0, fun_name="train_step")]
    assert read({"spans": spans}) == pytest.approx(30.0)
    assert read({"spans": spans[3:]}) is None


def test_lower_s_per_trial_sums_the_train_steps_trace_and_lowering():
    read = _reader("lower_s_per_trial")
    spans = [_span("build", "t-1", 0.6), _span("build", "t-2", 0.7),
             _span("jit.trace", "t-1", 0.5, fun_name="train_step", parent="step"),
             _span("jit.lower", "t-1", 0.3, fun_name="jit(train_step)", parent="step"),
             _span("jit.compile", "t-1", 5.0, fun_name="jit(train_step)", parent="step"),
             _span("jit.trace", "t-2", 0.4, fun_name="train_step", parent="step"),
             _span("jit.lower", "t-2", 0.2, fun_name="jit(train_step)", parent="step")]
    assert read({"spans": spans}) == pytest.approx((0.5 + 0.3 + 0.4 + 0.2) / 2)


def test_lower_s_per_trial_ignores_other_functions_and_unbuilt_trials():
    read = _reader("lower_s_per_trial")
    base = [_span("build", "t-1", 0.6),
            _span("jit.trace", "t-1", 0.5, fun_name="train_step", parent="step"),
            _span("jit.lower", "t-1", 0.3, fun_name="jit(train_step)", parent="step")]
    noise = [_span("jit.trace", "t-1", 9.0, fun_name="change_norms", parent="step"),
             _span("jit.lower", "t-1", 9.0, fun_name="jit(change_norms)", parent="step"),
             _span("jit.trace", "t-1", 9.0, fun_name="add", parent="build"),
             _span("jit.trace", "t-1", 9.0, parent="step"),
             # a trial whose build is not in the trace: not counted
             _span("jit.trace", "t-9", 9.0, fun_name="train_step", parent="step")]
    assert read({"spans": base + noise}) == pytest.approx(0.8)
    # builds without any trace of the step: nothing to read
    assert read({"spans": base[:1] + noise}) is None
