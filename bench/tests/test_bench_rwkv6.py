"""CPU tests of ``correct`` on an RWKV-6 cell whose limits hold the numbers
the ``rwkv6-1.6b`` configuration holds: a whole run of a small cell comes out
correct; with the program's WKV or embedding broken underneath (the bonus
``u`` dropped, the decay ignored, no ``ln0``, or the WKV's gradient negated
with its forward pass left whole) it comes out not correct; and the control,
the reference's forward pass in bfloat16 in the program's step with float32
weights and optimizer, fails the limits the program meets, in its readings
and in a whole run."""
import json
import time

import pytest

from bench_tiny_rwkv6 import CELL, REPO, TINY_LIMITS, make_rwkv_tree
from bench import calibrate, harness


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_rwkv_tree(tmp_path_factory.mktemp("bench"))


@pytest.fixture()
def fresh_steps():
    """Each test traces its own step: the shared step's cache key names the
    model configuration, not the functions a test patches."""
    from repro.train import step_cache_clear

    step_cache_clear()
    yield
    step_cache_clear()


def _run(tree, seed=2**31 + 11):
    return harness.run_cell(harness.load_cell(CELL, tree), seed, 1.0, False,
                            time.perf_counter(), out_dir=tree / ".bench_out")


def test_rwkv_cell_is_correct(tree, fresh_steps):
    res = _run(tree)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert res["device"]["platform"] == "cpu" and res["failed"] == 0


def _drop_u(mp):
    from repro.models import rwkv6

    wkv = rwkv6._wkv_chunked
    mp.setattr(rwkv6, "_wkv_chunked",
               lambda r, k, v, logw, u, s, chunk: wkv(r, k, v, logw, 0.0 * u, s, chunk))


def _ignore_decay(mp):
    from repro.models import rwkv6

    wkv = rwkv6._wkv_chunked
    mp.setattr(rwkv6, "_wkv_chunked",
               lambda r, k, v, logw, u, s, chunk: wkv(r, k, v, 0.0 * logw, u, s, chunk))


def _no_ln0(mp):
    from repro.models import layers, model

    mp.setattr(model, "_embed_tokens",
               lambda params, tokens, cfg: layers.embed_tokens(params["embed"], tokens, cfg))


def _negate_wkv_backward(mp):
    """The WKV's forward pass as it is, its gradient negated: a fault of the
    backward pass alone."""
    import jax

    from repro.models import rwkv6

    wkv = rwkv6._wkv_chunked

    def negated(r, k, v, logw, u, s, chunk):
        y, state = wkv(r, k, v, logw, u, s, chunk)
        return jax.lax.stop_gradient(2 * y) - y, state

    mp.setattr(rwkv6, "_wkv_chunked", negated)


@pytest.mark.parametrize("fault", [_drop_u, _ignore_decay, _no_ln0],
                         ids=["u_dropped", "decay_ignored", "no_ln0"])
def test_broken_program_is_not_correct(tree, monkeypatch, fresh_steps, fault):
    fault(monkeypatch)
    res = _run(tree)
    assert res["correct"] is False
    assert [k for k, c in res["checks"].items() if c["value"] > c["limit"]]


def test_backward_fault_is_seen_by_the_first_gradient(tree, monkeypatch, fresh_steps):
    """A fault of the backward pass alone is seen by the first gradient's
    error, far above its limit."""
    _negate_wkv_backward(monkeypatch)
    res = _run(tree)
    assert res["correct"] is False
    assert res["checks"]["grad_err"]["value"] > 10 * res["checks"]["grad_err"]["limit"]


def test_tiny_cell_holds_the_configurations_limits():
    """The tiny cell's limits are the chip configuration's, at values for its
    size, so the tests here show what the committed limits catch."""
    conf = json.loads((REPO / "bench" / "configs" / "rwkv6-1.6b.json").read_text())
    assert set(TINY_LIMITS) == set(conf["limits"])


def test_control_in_bfloat16_fails_the_limits(tree, fresh_steps, capsys):
    cell = harness.load_cell(CELL, tree)
    calibrate.calibrate(cell, [2**31 + 29], controls=1)
    calibrate.control_runs(cell, [2**31 + 31], seconds=1.0, out_dir=tree / ".bench_out")
    rows = {r["kind"]: r for r in map(json.loads, capsys.readouterr().out.splitlines())}
    over = lambda r: [k for k, lim in TINY_LIMITS.items() if r[k] > lim]
    assert over(rows["program"]) == []
    assert over(rows["bf16_activations"])
    assert over(rows["fault_half_batch"])
    assert rows["control_run"]["correct"] is False
