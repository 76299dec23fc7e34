"""Strategy arena: every registered partitioner, head to head.

Sweeps every canonical strategy in the ``repro.api`` registry (xDGP's
migrator, the rival partitioners — Spinner-style balanced LPA, SDP-style
real-time refinement, Le Merrer-style restreaming — and the non-adapting
baselines) across the three §5.3 paper scenarios plus the adversarial
rotating-community churn stream, scoring each run on the metrics the
partitioning papers fight over:

  cut        final + mean cut ratio (communication volume proxy)
  balance    final max/mean occupancy
  migrations total vertices moved (the cost of adaptivity)
  wall       end-to-end wall seconds for the run
  exec cost  the §5.3 cost-model total, vs. the shared static baseline

Every (scenario, strategy) cell is one ``DynamicGraphSystem.compare`` dual
run against the ``static`` baseline on the identical event stream — the
candidate and baseline sessions differ by exactly one config field.

  PYTHONPATH=src:. python benchmarks/bench_strategy_arena.py [--scale small]
      [--scenarios twitter adversarial] [--strategies xdgp spinner]

Writes results/bench_strategy_arena.json (validated in CI by
``repro.obs.schema.validate_arena_bench``).
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List

from benchmarks.common import run_main, save
from repro.api import canonical_strategy_names
from repro.scenarios import ARENA_SCENARIOS, CostModel, compare_scenario

METRICS = ("cut_final", "imbalance_final", "migrations_total",
           "wall_seconds", "exec_cost_total")


def _row(scenario: str, strategy: str, res: Dict) -> Dict:
    cand = res["adaptive"]          # compare()'s candidate row, whatever the
    return {                        # strategy actually is
        "scenario": scenario,
        "strategy": strategy,
        "events": res["events"],
        "supersteps": cand["supersteps"],
        "cut_final": cand["cut_final"],
        "cut_mean": cand["cut_mean"],
        "imbalance_final": cand["imbalance_final"],
        "migrations_total": cand["migrations_total"],
        "wall_seconds": round(cand["wall_seconds"], 3),
        "exec_cost_total": cand["exec_cost_total"],
        "exec_cost_reduction_pct": res["exec_cost_reduction_pct"],
        "cut_improvement": res["cut_improvement"],
        "meets_50pct_claim": res["meets_50pct_claim"],
    }


def _winners(rows: List[Dict], scenario: str) -> Dict[str, str]:
    cell = [r for r in rows if r["scenario"] == scenario]
    lowest = lambda key: min(cell, key=lambda r: r[key])["strategy"]
    return {
        "cut": lowest("cut_final"),
        "balance": lowest("imbalance_final"),
        "exec_cost": lowest("exec_cost_total"),
        "wall": lowest("wall_seconds"),
    }


def run(scale: str, scenarios: List[str], strategies: List[str], seed: int,
        backend: str = "auto") -> Dict:
    cost = CostModel()
    rows: List[Dict] = []
    for sname in scenarios:
        scn = ARENA_SCENARIOS[sname](scale, seed=seed)
        print(f"  {sname} [{scn.program}] k={scn.k}, "
              f"{scn.n_events} events, {scn.supersteps} supersteps")
        for strat in strategies:
            t0 = time.perf_counter()
            res = compare_scenario(scn, strategy=strat, cost=cost,
                                   backend=backend)
            row = _row(sname, strat, res)
            row["compare_seconds"] = round(time.perf_counter() - t0, 2)
            rows.append(row)
            print(f"    {strat:9s} cut={row['cut_final']:.3f} "
                  f"imb={row['imbalance_final']:.2f} "
                  f"migr={row['migrations_total']:6d} "
                  f"wall={row['wall_seconds']:6.2f}s "
                  f"cost-{row['exec_cost_reduction_pct']:5.1f}%", flush=True)
    winners = {s: _winners(rows, s) for s in scenarios}
    for s in scenarios:
        print(f"  winners[{s}]: " + ", ".join(
            f"{m}={w}" for m, w in winners[s].items()))
    return {
        "bench": "strategy_arena",
        "scale": scale,
        "seed": seed,
        "backend": backend,
        "baseline": "static",
        "cost_model": {"c_cpu": cost.c_cpu, "c_net": cost.c_net,
                       "c_mig": cost.c_mig},
        "scenarios": list(scenarios),
        "strategies": list(strategies),
        "metrics": list(METRICS),
        "rows": rows,
        "winners": winners,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=("smoke", "small", "full"),
                    default="small")
    ap.add_argument("--scenarios", nargs="*",
                    default=list(ARENA_SCENARIOS),
                    choices=list(ARENA_SCENARIOS))
    ap.add_argument("--strategies", nargs="*",
                    default=list(canonical_strategy_names()),
                    choices=list(canonical_strategy_names()),
                    help="canonical registry names only — aliases would "
                         "run the same strategy twice")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", choices=("auto", "ref", "pallas"),
                    default="auto")
    args = ap.parse_args()

    print(f"strategy arena (scale={args.scale}, backend={args.backend}, "
          f"{len(args.strategies)} strategies x {len(args.scenarios)} "
          f"scenarios)")
    payload = run(args.scale, args.scenarios, args.strategies, args.seed,
                  backend=args.backend)
    path = save("bench_strategy_arena", payload)
    print("saved", path)


if __name__ == "__main__":
    run_main(main)
