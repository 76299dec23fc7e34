"""Scale sweep: events/sec ingest + superstep seconds vs |V| (DESIGN.md §14).

The scale tier's headline artifact: for each (vertex count, backend) cell,
build a power-law graph through the streaming generators (chunked, bounded
host memory), run a live ingest→place→measure stream through a full
``DynamicGraphSystem`` session, run adaptation rounds, and attempt a
budget-gated chunked BSR packing — recording wall times, throughput, cut
movement, the packing outcome, and the process peak-RSS high-water mark.

    PYTHONPATH=src:. python benchmarks/bench_scale_sweep.py --scale smoke
    PYTHONPATH=src:. XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/bench_scale_sweep.py --scale full

Writes results/bench_scale_sweep.json (schema: obs.schema.validate_scale_
bench; re-validated in CI against both a fresh smoke run and the committed
full artifact).
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, List

import numpy as np

from benchmarks.common import run_main, save

SCALES = {
    "smoke": {"sizes": [200_000], "steps": 3, "adapt_iters": 3},
    "full": {"sizes": [100_000, 300_000, 1_000_000], "steps": 3,
             "adapt_iters": 4},
}


def run_cell(n: int, backend: str, *, generator: str, avg_degree: float,
             chunk_edges: int, k: int, steps: int, adapt_iters: int,
             blk: int, bsr_budget_mb: int, seed: int) -> Dict[str, Any]:
    from repro.api import DynamicGraphSystem, SystemConfig
    from repro.api.config import (ClusterSection, GraphSection,
                                  PartitionSection, StreamSection,
                                  TelemetrySection)
    from repro.obs.profiling import peak_rss_bytes
    from repro.scale import (MemoryBudgetError, graph_to_bsr_chunked,
                             make_edge_stream, stream_events)
    from repro.stream.metrics import cut_ratio_of

    a_cap = 1 << 16
    cfg = SystemConfig(
        graph=GraphSection(generator=generator, n=n, avg_degree=avg_degree,
                           chunk_edges=chunk_edges),
        stream=StreamSection(window=1 << 40, a_cap=a_cap, d_cap=1024),
        partition=PartitionSection(strategy="xdgp", k=k,
                                   adapt_iters=adapt_iters),
        cluster=ClusterSection(backend=backend),
        telemetry=TelemetrySection(recompute_every=0),
        seed=seed)

    t0 = time.perf_counter()
    system = DynamicGraphSystem(config=cfg)   # generator builds the graph
    build_seconds = time.perf_counter() - t0
    edges0 = int(system.graph.num_edges)
    cut_before = float(cut_ratio_of(system.tracker))

    # live stream: fresh edges from a disjoint seed, capped per step so the
    # whole batch clears capacity (this measures ingest, not backpressure)
    live = make_edge_stream(generator, n, avg_degree=avg_degree,
                            chunk_edges=min(a_cap // 2, chunk_edges),
                            seed=seed + 1)
    records = []
    for i, batch in enumerate(stream_events(live, t0=1)):
        if i >= steps:
            break
        records.append(system.step(batch))
    events = sum(r.events for r in records)
    ingest_seconds = sum(r.ingest_seconds for r in records)
    step_secs = [r.step_seconds for r in records]
    # first step pays jit compilation; the median of the rest is steady state
    superstep_seconds = float(np.median(step_secs[1:] if len(step_secs) > 1
                                        else step_secs))

    t0 = time.perf_counter()
    hist = system.adapt(adapt_iters)
    adapt_seconds = time.perf_counter() - t0
    cut_after = float(cut_ratio_of(system.tracker))
    migrations = sum(r.migrations for r in records) + hist.total_migrations

    budget = bsr_budget_mb * (1 << 20)
    t0 = time.perf_counter()
    try:
        bsr = graph_to_bsr_chunked(system.graph, blk=blk,
                                   chunk_edges=chunk_edges,
                                   memory_budget=budget)
        nnzb = int(bsr.nnzb)
        bsr_out: Dict[str, Any] = {
            "nnzb": nnzb, "blocks_bytes": int(nnzb * blk * blk * 4),
            "build_seconds": time.perf_counter() - t0}
    except MemoryBudgetError as e:
        # the budget refusing an over-sized packing IS the bounded-memory
        # contract working — record it instead of OOMing the sweep
        bsr_out = {"skipped": str(e)}

    return {"vertices": n, "backend": backend, "edges": edges0,
            "events": int(events), "supersteps": len(records),
            "build_seconds": build_seconds,
            "ingest_events_per_sec": events / max(ingest_seconds, 1e-12),
            "superstep_seconds": superstep_seconds,
            "adapt_seconds": adapt_seconds, "adapt_iters": adapt_iters,
            "migrations": int(migrations),
            "cut_before": cut_before, "cut_after": cut_after,
            "bsr": bsr_out, "peak_rss_bytes": peak_rss_bytes()}


def main(argv: List[str] = None) -> Dict[str, Any]:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", choices=sorted(SCALES), default="smoke")
    ap.add_argument("--sizes", type=int, nargs="*", default=None,
                    help="override the scale preset's vertex counts")
    ap.add_argument("--backends", nargs="*", default=["local", "sharded"])
    ap.add_argument("--generator", default="rmat")
    ap.add_argument("--avg-degree", type=float, default=8.0)
    ap.add_argument("--chunk-edges", type=int, default=1 << 18)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--blk", type=int, default=8,
                    help="BSR tile size; power-law graphs scatter edges so "
                         "nearly every edge lands in its own tile — small "
                         "blocks keep the pack inside the memory budget")
    ap.add_argument("--bsr-budget-mb", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    preset = SCALES[args.scale]
    sizes = args.sizes if args.sizes else preset["sizes"]
    backends = list(args.backends)
    if "sharded" in backends and jax.device_count() < args.k:
        print(f"[scale] sharded needs {args.k} devices, have "
              f"{jax.device_count()} — dropping it from the sweep "
              f"(set XLA_FLAGS=--xla_force_host_platform_device_count="
              f"{args.k})")
        backends = [b for b in backends if b != "sharded"]
    if not backends:
        raise SystemExit("no runnable backends")

    rows = []
    for n in sizes:
        for backend in backends:
            t0 = time.perf_counter()
            row = run_cell(n, backend, generator=args.generator,
                           avg_degree=args.avg_degree,
                           chunk_edges=args.chunk_edges, k=args.k,
                           steps=preset["steps"],
                           adapt_iters=preset["adapt_iters"], blk=args.blk,
                           bsr_budget_mb=args.bsr_budget_mb, seed=args.seed)
            rows.append(row)
            print(f"[scale] |V|={n:>9,} {backend:>7}: "
                  f"build {row['build_seconds']:6.1f}s  "
                  f"ingest {row['ingest_events_per_sec']:>11,.0f} ev/s  "
                  f"superstep {row['superstep_seconds']*1e3:8.1f} ms  "
                  f"cut {row['cut_before']:.3f}->{row['cut_after']:.3f}  "
                  f"rss {row['peak_rss_bytes']/2**30:.2f} GiB  "
                  f"({time.perf_counter()-t0:.0f}s)")

    from repro.obs.manifest import run_manifest
    from repro.obs.profiling import memory_probe
    payload = {"bench": "scale_sweep", "generator": args.generator,
               "k": args.k, "chunk_edges": args.chunk_edges,
               "blk": args.blk,
               "avg_degree": args.avg_degree, "scale": args.scale,
               "sizes": sizes, "backends": backends, "rows": rows,
               "manifest": run_manifest(None, memory=memory_probe())}
    from repro.obs.schema import validate_scale_bench
    validate_scale_bench(payload)
    path = save("bench_scale_sweep", payload)
    print(f"[scale] wrote {path}")
    return payload


if __name__ == "__main__":
    run_main(main)
