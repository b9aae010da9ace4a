"""Benchmark harness: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows (derived = JSON payload of
the table's reproduced values) and writes experiments/bench_results.json.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.compile_cache import use_compile_cache

BENCHES = {}


def register(name):
    def deco(fn):
        BENCHES[name] = fn
        return fn
    return deco


def _lazy():
    from benchmarks import kernel_bench, paper_tables

    register("table2_accuracy_vs_sparsity")(paper_tables.bench_table2_accuracy)
    register("fig13_sparsity_vs_theta")(paper_tables.bench_fig13_sparsity_vs_theta)
    register("fig12_balance_ratio")(paper_tables.bench_fig12_balance_ratio)
    register("table4_hw_ladder")(paper_tables.bench_table4_hw_ladder)
    register("table5_6_comparison")(paper_tables.bench_table5_comparison)
    register("table7_fig14_dram_energy")(paper_tables.bench_table7_dram_energy)
    register("deltagru_vs_deltalstm")(paper_tables.bench_deltagru_vs_deltalstm)
    register("kernels_micro")(kernel_bench.bench_kernels)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full sweeps (quick subsets by default)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    use_compile_cache()
    _lazy()

    results = {}
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        derived = fn(quick=not args.full)
        us = (time.perf_counter() - t0) * 1e6
        results[name] = {"us_per_call": us, "derived": derived}
        print(f"{name},{us:.0f},{json.dumps(derived, sort_keys=True)}")

    out = os.path.join(os.path.dirname(__file__), "..", "experiments")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "bench_results.json"), "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)


if __name__ == "__main__":
    main()
