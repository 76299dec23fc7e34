"""Benchmark orchestrator: one module per paper figure/table + extensions.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig5,...]

Prints a ``name,us_per_call,derived`` CSV line per benchmark row and writes
full JSON to results/.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (bench_elastic, bench_fig1_dynamic_cuts,
                        bench_fig2_s_sweep, bench_fig5_initial_partitioning,
                        bench_fig6_convergence, bench_fig7_dynamic_adaptation,
                        bench_usecase_comm_volume)
from benchmarks.common import run_main, save

BENCHES = {
    "fig1": bench_fig1_dynamic_cuts,
    "fig2": bench_fig2_s_sweep,
    "fig5": bench_fig5_initial_partitioning,
    "fig6": bench_fig6_convergence,
    "fig7": bench_fig7_dynamic_adaptation,
    "usecase": bench_usecase_comm_volume,
    "elastic": bench_elastic,
}


def _derived(row: dict) -> str:
    for key in ("improvement", "final_cut_mean", "cut_improvement_frac_at_90pct_migrations",
                "peak_time_vs_initial", "modelled_speedup", "recovered_pct",
                "mean_cut_last_half", "cut_after_adapt"):
        if key in row:
            return f"{key}={row[key]}"
    return ""


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    all_rows = {}
    for name, mod in BENCHES.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        rows = mod.run(quick=args.quick)
        dt_us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
        all_rows[name] = rows
        for row in rows:
            label = "/".join(str(row.get(k)) for k in
                             ("bench", "graph", "strategy", "mode", "workload", "s")
                             if row.get(k) is not None)
            print(f"{label},{dt_us:.0f},{_derived(row)}")
        save(f"bench_{name}", rows)
    save("bench_all", all_rows)


if __name__ == "__main__":
    run_main(main)
