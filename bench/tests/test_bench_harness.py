"""CPU tests of the benchmark's harness: discovery by name, the peak table,
the refusal to run without a TPU, the FLOP functions and the comparison's
numbers."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import REPO, make_tree
from bench import harness
from bench.peaks import peaks


def test_cell_config_and_metric_added_as_files_are_found_by_name(tmp_path):
    root = make_tree(tmp_path)
    b = root / "bench"
    (b / "metrics" / "my_metric.py").write_text("def read(ctx):\n    return 42.0\n")
    for ext in (".json", ".py"):
        shutil.copy(b / "configs" / f"tiny{ext}", b / "configs" / f"tiny2{ext}")
    shutil.copy(b / "flops" / "tiny.py", b / "flops" / "tiny2.py")
    traffic = json.loads((b / "traffic" / "long.json").read_text())
    (b / "traffic" / "long2.json").write_text(json.dumps(dict(
        traffic, steps_per_iter=3, scheduler="MedianStoppingRule",
        scheduler_kwargs={"grace_period": 2})))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny2", "source": "test",
                            "file": "bench/configs/tiny2.json", "reduced": [], "why": "t"})
    spec["workloads"].append({"name": "tiny2.long2", "config": "tiny2",
                              "traffic": "long2", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "my_metric", "unit": "s", "better": "lower",
                              "source": "host_clock", "layer": "test", "moves": "setup_s",
                              "workloads": ["tiny2.long2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("tiny2.long2", root)
    assert cell.traffic["steps_per_iter"] == 3
    sched = harness.make_scheduler(cell.traffic, harness.Recorder(1.0))
    assert type(sched).__mro__[1].__name__ == "MedianStoppingRule"
    assert cell.config["name"] == "tiny"          # the copied file's contents
    assert cell.readers["my_metric"]({}) == 42.0
    assert "my_metric" in {m["name"] for m in cell.per_layer}
    assert "my_metric" not in {m["name"] for m in harness.load_cell("tiny.long", root).per_layer}
    changed = [p for p, data in before.items()
               if p.name != "BENCHMARK.json" and p.read_bytes() != data]
    assert changed == []


def test_unknown_device_kind_raises():
    assert peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v99")
    read = harness._load_module(REPO / "bench" / "metrics" / "step_mfu.py").read
    ctx = {"trace": {"module_n": 2, "module_s": 1.0}, "device_kind": "cpu",
           "tokens_per_step": 8, "flops_per_token": 1e9}
    with pytest.raises(KeyError):
        read(ctx)


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    args = ("--workload", "smollm-135m.long", "--seed", str(2**31 + 3),
            "--seconds", "1", "--trace", "0")
    proc = _run(REPO, *args)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip()
    # a directory holding only BENCHMARK.json and the benchmark's files
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, *args)
    assert proc.returncode != 0
    assert not proc.stdout.strip()


def test_flops_against_hand_counts():
    smol = harness._load_module(REPO / "bench" / "flops" / "smollm-135m.py")
    conf = json.loads((REPO / "bench" / "configs" / "smollm-135m.json").read_text())
    # per layer: attention 576*(576+192+192+576), MLP 3*576*1536; head 49152*576
    assert smol.matmul_params(conf["model"]) == 30 * (884_736 + 2_654_208) + 28_311_552
    # 6N = 806.9 MFLOP, causal attention 12*L*S*d / 2 = 212.3 MFLOP
    assert smol.flops_per_token(conf) == 6 * 134_479_872 + 30 * 12 * 2048 * 576 // 2


def test_unknown_scheduler_raises():
    with pytest.raises(ValueError):
        harness.make_scheduler({"scheduler": "NoSuchScheduler"}, harness.Recorder(1.0))
    with pytest.raises(ValueError):
        harness.make_scheduler({"scheduler": "Searcher"}, harness.Recorder(1.0))


def test_grad_err_sees_an_error_that_the_norms_hide():
    import numpy as np

    from bench.reference import compare

    rng = np.random.default_rng(0)
    want = {"losses": [1.0, 1.0, 1.0], "change": {"a": 1.0, "b": 1.0},
            "grad": {"a": rng.normal(size=1000).astype(np.float32),
                     "b": rng.normal(size=100).astype(np.float32)}}
    # the same elements in another order: every norm agrees, the gradient not
    got = dict(want, grad={"a": want["grad"]["a"][::-1].copy(), "b": want["grad"]["b"]})
    numbers = compare(got, want)
    assert numbers["grad_gap"] < 1e-6 and numbers["change_gap"] == 0.0
    assert numbers["grad_err"] > 1.0
    assert compare(want, want)["grad_err"] == 0.0
