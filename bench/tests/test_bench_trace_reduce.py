"""The trace reduction, on a small trace recorded on a TPU v5e by
``record_trace.py``: two executions of ``jit_train_step`` inside the
``bench.window`` span, with host time between them under ``bench.save`` and
outside every span."""
from pathlib import Path

from bench.trace_reduce import CONTROL_PLANE, reduce_trace

TRACE = Path(__file__).resolve().parent / "data" / "v5e_two_steps.xplane.pb"


def test_reduction_of_a_recorded_trace():
    r = reduce_trace(str(TRACE))
    assert r["devices"] == 1
    assert r["module_n"] == 2
    assert 0 < r["busy_s"] < r["window_s"] and 0 < r["module_s"] < r["window_s"]
    # the host slept 30 ms between the steps, 20 of them inside bench.save
    assert r["window_s"] - r["busy_s"] > 0.03
    assert all(" " not in n for n, _ in r["device_ops"])
    names = [n for n, _ in r["idle_gaps"]]
    assert names[0] in ("bench.save", CONTROL_PLANE)
    assert {"bench.save", CONTROL_PLANE} <= set(names)
    assert all(s > 0 for _, s in r["device_ops"])
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_union_of_overlapping_intervals():
    from bench.trace_reduce import _union

    assert _union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
