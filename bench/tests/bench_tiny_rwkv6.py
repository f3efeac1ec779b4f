"""A benchmark tree with an RWKV-6 cell a CPU test can run: ``bench_tiny``'s
tree plus the configuration ``tinyrwkv`` (the ``rwkv6-1.6b`` reference and
FLOP function at two layers of width 64, two heads of 32) and its cell
``tinyrwkv.long`` under the ``long`` traffic."""
import json
import shutil
from pathlib import Path

from bench_tiny import LONG, REPO, make_tree

TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 2, "d_ff": 128, "vocab_size": 256,
              "rwkv_head_dim": 32, "rwkv_chunk": 16, "remat": False}
TINY_SHAPES = {"batch": 2, "seq_len": 48}
# The numbers the chip configuration holds.  Program and reference agree to
# about 1e-6 here on the CPU; the control and the faults read 1e-4 and more
# (bench/calibrate.py at this size).
TINY_LIMITS = {"loss1_gap": 1e-4, "grad_gap": 1e-3, "grad_err": 1e-3, "change_gap": 1e-3}
CELL = "tinyrwkv.long"


def make_rwkv_tree(dest: Path) -> Path:
    root = make_tree(dest)
    b = root / "bench"
    conf = json.loads((REPO / "bench" / "configs" / "rwkv6-1.6b.json").read_text())
    conf["model"].update(TINY_MODEL)
    conf.update(TINY_SHAPES, name="tinyrwkv", limits=dict(TINY_LIMITS))
    (b / "configs" / "tinyrwkv.json").write_text(json.dumps(conf))
    shutil.copy(REPO / "bench" / "configs" / "rwkv6-1.6b.py", b / "configs" / "tinyrwkv.py")
    shutil.copy(REPO / "bench" / "flops" / "rwkv6-1.6b.py", b / "flops" / "tinyrwkv.py")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tinyrwkv", "source": "test",
                            "file": "bench/configs/tinyrwkv.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tinyrwkv", "traffic": "long",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m.get("workloads") == LONG:
            m["workloads"] = LONG + [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
