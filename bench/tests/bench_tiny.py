"""A benchmark tree at a size a CPU test can run: the repository's metric
readers and traffic files, and one configuration ``tiny`` (SmolLM's
reference and FLOP function at two layers of width 64) with cells
``tiny.long`` and ``tiny.search``."""
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 2, "n_kv_heads": 1,
              "d_ff": 128, "vocab_size": 256, "remat": False}
TINY_SHAPES = {"batch": 4, "seq_len": 32}
# Program and reference agree to about 1e-6 here on the CPU; the control and
# the faults read 1e-4 and more (bench/calibrate.py at this size).
TINY_LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "grad_err": 1e-3, "change_gap": 1e-3}


# Every metric reader in bench/metrics, with the tiny cells it reads something
# in: the sweep cell (the random search) and the long trial.
SWEEP, LONG = ["tiny.search"], ["tiny.long"]
END_TO_END = [("setup_s", "s", None), ("train_tokens_per_s", "tokens/s", LONG),
              ("sweep_tokens_per_s", "tokens/s", SWEEP), ("trial_start_s", "s", SWEEP)]
PER_LAYER = [("decision_us", "us", SWEEP), ("build_s", "s", SWEEP),
             ("compile_s_per_trial", "s", SWEEP), ("step_mfu", "%", LONG),
             ("step_mfu.sweep", "%", SWEEP)]


def _metric(name, unit, cells):
    m = {"name": name, "unit": unit, "better": "lower", "source": "host_clock",
         "layer": "test", "moves": "setup_s"}
    return m if cells is None else dict(m, workloads=cells)


def add_cell(root: Path, traffic: str, **entries) -> str:
    """Add the cell ``tiny.<traffic>`` to the tree at ``root``: the traffic
    file of that name, with ``entries`` over the search cell's, and its entry
    in ``BENCHMARK.json``.  Returns the cell's name."""
    b = root / "bench"
    data = json.loads((b / "traffic" / "search.json").read_text())
    (b / "traffic" / f"{traffic}.json").write_text(json.dumps(dict(data, **entries)))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    name = f"tiny.{traffic}"
    spec["workloads"].append({"name": name, "config": "tiny", "traffic": traffic,
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m.get("workloads") == SWEEP:
            m["workloads"] = SWEEP + [name]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return name


def make_tree(dest: Path) -> Path:
    dest = Path(dest)
    for d in ("metrics", "traffic", "flops", "configs"):
        shutil.copytree(REPO / "bench" / d, dest / "bench" / d)
    conf = json.loads((REPO / "bench" / "configs" / "smollm-135m.json").read_text())
    conf["model"].update(TINY_MODEL)
    conf.update(TINY_SHAPES, name="tiny", limits=dict(TINY_LIMITS))
    (dest / "bench" / "configs" / "tiny.json").write_text(json.dumps(conf))
    shutil.copy(REPO / "bench" / "configs" / "smollm-135m.py",
                dest / "bench" / "configs" / "tiny.py")
    shutil.copy(REPO / "bench" / "flops" / "smollm-135m.py", dest / "bench" / "flops" / "tiny.py")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                        "reduced": [], "why": "test"}]
    spec["workloads"] = [{"name": f"tiny.{t}", "config": "tiny", "traffic": t, "chips": 1,
                          "why": "test"} for t in ("long", "search")]
    spec["end_to_end"] = [_metric(n, u, c) for n, u, c in END_TO_END]
    spec["per_layer"] = [_metric(n, u, c) for n, u, c in PER_LAYER]
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    return dest
