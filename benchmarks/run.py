"""Benchmark harness — one bench per paper table/claim.

    PYTHONPATH=src python -m benchmarks.run [--only NAME]

Prints ``name,us_per_call,derived`` CSV lines; full tables land in
benchmarks/results/*.csv.

  table1_loc   — paper Table 1: LoC per algorithm against the narrow waist
  convergence  — scheduler quality vs budget (ASHA/HB/Median/PBT/TPE vs FIFO)
  overhead     — event-loop + checkpoint-codec throughput
  scaling      — slice-pool occupancy under irregular trials (paper §4.3.1)
  process      — GIL-contention sweep: process vs thread vs serial executors
  elastic      — elastic slice reclaim vs static placement + lookahead credits
  faults       — crash-storm recovery rate + control-plane overhead per event
  cluster      — localhost 3-host socket sweep vs the process tier
  vmap         — beyond-paper: stacked-vmap trial execution vs serial
  kernels      — pure-jnp oracle timings (TPU kernel baselines)
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None,
                    help="run a single bench (loc|convergence|overhead|"
                         "scaling|async|process|elastic|faults|cluster|vmap|"
                         "kernels)")
    args = ap.parse_args()

    from . import (bench_async, bench_cluster, bench_convergence,
                   bench_elastic, bench_faults, bench_kernels, bench_loc,
                   bench_overhead, bench_process, bench_scaling,
                   bench_vmap)
    benches = {
        "loc": bench_loc.run,
        "convergence": bench_convergence.run,
        "overhead": bench_overhead.run,
        "scaling": bench_scaling.run,
        "async": bench_async.run,
        "process": bench_process.run,
        "elastic": bench_elastic.run,
        "faults": lambda: bench_faults.run(2000),
        "cluster": bench_cluster.run,
        "vmap": bench_vmap.run,
        "kernels": bench_kernels.run,
    }
    selected = {args.only: benches[args.only]} if args.only else benches

    print("name,us_per_call,derived")
    failed = []
    for name, fn in selected.items():
        try:
            fn()
        except Exception:  # noqa: BLE001 — report all bench failures at the end
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"FAILED benches: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
