"""Superstep microbenchmark: unfused reference vs fused migration kernels.

Measures the xDGP adaptation superstep — ``adapt_iters`` migration
iterations compiled into one ``lax.scan`` program (exactly what the
streaming engine dispatches per batch, see ``core/repartitioner.adapt_jit``)
— under the two scoring backends of DESIGN.md §9:

  ref     the unfused op pipeline: (2E, k) one-hot materialisation +
          segment-sum counts, separate decide/damp passes, stable-sort
          quota ranking (``core/migration.py`` seed path).
  pallas  the fused path (``kernels/migration_kernels.py``): one pass over
          the packed adjacency builds the histogram, selects greedy
          targets and applies damping; quota ranks via the single-key
          sort. Executor resolved by ``repro.compat.pallas_executor()``
          (native Mosaic on TPU; the bit-identical pure-jax oracle on this
          CPU container).

Both backends produce bit-identical assignments (asserted per size), so the
speedup is pure implementation. Plan packing (host-side, once per graph) is
timed separately and also amortised into the reported fused time at one
pack per superstep — the streaming worst case.

  PYTHONPATH=src:. python benchmarks/bench_migration_kernels.py

Writes results/bench_migration_kernels.json and asserts the fused superstep
is ≥2× faster than ref at the largest benchmarked graph size.
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List

import jax
import numpy as np

from benchmarks.common import run_main, save
from repro import compat
from repro.core.initial import initial_partition
from repro.core.partition_state import make_state
from repro.core.repartitioner import adapt_jit
from repro.graph import generators
from repro.kernels.migration_kernels import build_plan


def _bench(fn, *args, repeats: int) -> float:
    jax.block_until_ready(fn(*args))                     # compile + warm
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench_size(graph, name: str, k: int, iters: int, s: float,
               repeats: int) -> Dict:
    lab = initial_partition(graph, k, "hsh")
    state = make_state(graph, lab, k, slack=0.2, seed=0)

    t0 = time.perf_counter()
    plan = build_plan(graph)
    plan_seconds = time.perf_counter() - t0

    step_ref = jax.jit(lambda g, st: adapt_jit(g, st, s=s, iters=iters,
                                               backend="ref"))
    step_fused = jax.jit(lambda g, st, p: adapt_jit(g, st, s=s, iters=iters,
                                                    backend="pallas", plan=p))

    # identical assignments or the comparison is meaningless
    out_ref = step_ref(graph, state)
    out_fused = step_fused(graph, state, plan)
    identical = bool(np.array_equal(np.asarray(out_ref.assignment),
                                    np.asarray(out_fused.assignment)))

    t_ref = _bench(step_ref, graph, state, repeats=repeats)
    t_fused = _bench(step_fused, graph, state, plan, repeats=repeats)
    t_fused_repack = t_fused + plan_seconds              # streaming worst case

    n = int(np.asarray(graph.node_mask).sum())
    e = int(np.asarray(graph.edge_mask).sum())
    row = {
        "graph": name, "nodes": n, "edges": e, "k": k,
        "iters_per_superstep": iters,
        "plan_kind": plan.kind,
        "executor": compat.pallas_executor(),
        "plan_build_seconds": round(plan_seconds, 6),
        "ref_superstep_seconds": round(t_ref, 6),
        "fused_superstep_seconds": round(t_fused, 6),
        "fused_superstep_seconds_with_repack": round(t_fused_repack, 6),
        "speedup": round(t_ref / t_fused, 3),
        "speedup_with_repack": round(t_ref / t_fused_repack, 3),
        "assignments_identical": identical,
    }
    print(f"  {name:12s} n={n:7d} e={e:8d} plan={plan.kind:4s} "
          f"ref={t_ref * 1e3:8.1f}ms fused={t_fused * 1e3:7.1f}ms "
          f"({row['speedup']:.2f}x; {row['speedup_with_repack']:.2f}x with "
          f"per-superstep repack) identical={identical}", flush=True)
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sides", type=int, nargs="*", default=[16, 24, 32, 40, 48],
                    help="fem_cube sides (|V| = side³), ascending")
    ap.add_argument("--plc-nodes", type=int, default=20000,
                    help="power-law graph size (0 = skip)")
    ap.add_argument("--k", type=int, default=9)
    ap.add_argument("--iters", type=int, default=5,
                    help="migration iterations per superstep")
    ap.add_argument("--s", type=float, default=0.5)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()

    print(f"migration-kernel superstep bench (k={args.k}, "
          f"iters={args.iters}, executor={compat.pallas_executor()})")
    rows: List[Dict] = []
    for side in sorted(args.sides):
        g = generators.fem_cube(side)
        rows.append(bench_size(g, f"fem_cube({side})", args.k, args.iters,
                               args.s, args.repeats))
    if args.plc_nodes:
        g = generators.power_law(args.plc_nodes, seed=0)
        rows.append(bench_size(g, f"power_law({args.plc_nodes})", args.k,
                               args.iters, args.s, args.repeats))

    if not rows:
        ap.error("nothing to benchmark: pass --sides and/or --plc-nodes")
    # the ≥2x claim is asserted on the FEM meshes (the paper's core
    # workload); a power-law-only run still reports but asserts on its rows
    fem_rows = [r for r in rows if r["graph"].startswith("fem_cube")] or rows
    largest = max(fem_rows, key=lambda r: r["nodes"])
    payload = {
        "bench": "migration_kernels",
        "k": args.k, "iters_per_superstep": args.iters, "s": args.s,
        "repeats": args.repeats,
        "executor": compat.pallas_executor(),
        "rows": rows,
        "claim": {
            "statement": "fused superstep ≥2× faster than the unfused "
                         "reference at the largest benchmarked graph size, "
                         "with bit-identical assignments",
            "largest_graph": largest["graph"],
            "largest_nodes": largest["nodes"],
            "speedup_at_largest": largest["speedup"],
            "speedup_with_repack_at_largest": largest["speedup_with_repack"],
            "met": bool(largest["speedup"] >= 2.0),
        },
    }
    path = save("bench_migration_kernels", payload)
    print(f"largest graph {largest['graph']}: {largest['speedup']:.2f}x "
          f"(claim ≥2x: {'MET' if payload['claim']['met'] else 'NOT MET'})")
    print("saved", path)
    assert all(r["assignments_identical"] for r in rows), \
        "fused and ref paths diverged — parity violation"
    assert payload["claim"]["met"], (
        f"fused superstep only {largest['speedup']:.2f}x faster than ref at "
        f"{largest['graph']}; expected ≥2x")


if __name__ == "__main__":
    run_main(main)
