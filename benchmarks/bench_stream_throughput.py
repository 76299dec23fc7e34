"""Streaming ingestion throughput: vectorized engine vs. the seed path.

Replays a synthetic CDR stream (default 100k events) through

  (a) the seed ingestion path — per-event Python loop over deques + dict
      window tracking (the pre-streaming-layer ``SlidingWindowGraph.advance``
      implementation, reproduced here verbatim as the baseline), and
  (b) the streaming layer — ``WindowIngestor`` (vectorized batch build +
      scatter-max expiry) driven by ``repro.api.DynamicGraphSystem``.

Reported per path:
  * ingest events/sec — the events → GraphDelta stage (the part the seed did
    with Python loops; graph application is identical jit code in both).
  * end-to-end events/sec — including ``apply_delta``.
The engine run also reports the cut trajectory (online placement + adaptive
migration active) and asserts the incremental cut tracker shows zero drift
at every check.

  PYTHONPATH=src python benchmarks/bench_stream_throughput.py [--events N]
"""
from __future__ import annotations

import argparse
import time
from collections import deque

import numpy as np
import jax.numpy as jnp

from benchmarks.common import run_main, save
from repro.api import (DynamicGraphSystem, PartitionSection, StreamSection,
                       SystemConfig, TelemetrySection, XdgpAdaptive,
                       empty_graph)
from repro.graph import generators
from repro.graph.structure import GraphDelta, apply_delta
from repro.stream import stream_batches


def seed_path(times, src, dst, n_cap, e_cap, window, a_cap, d_cap, span):
    """The seed per-event ingestion loop, instrumented at the same boundary
    as the engine (delta construction vs. graph application)."""
    graph = empty_graph(n_cap, e_cap)
    last_seen: dict = {}
    ingest_s = total_s = 0.0
    events_total = 0
    for now, events in stream_batches(times, src, dst, span):
        t0 = time.perf_counter()
        adds: deque = deque()
        dels: deque = deque()
        for t, u, v in events:                      # the seed's hot loop
            adds.append((int(u), int(v)))
            last_seen[int(u)] = int(t)
            last_seen[int(v)] = int(t)
        horizon = now - window
        for n in [n for n, t in last_seen.items() if t < horizon]:
            dels.append(n)
            del last_seen[n]
        a = min(len(adds), a_cap)
        d = min(len(dels), d_cap)
        add_src = np.full((a_cap,), -1, np.int32)
        add_dst = np.full((a_cap,), -1, np.int32)
        add_mask = np.zeros((a_cap,), bool)
        for i in range(a):                          # the seed's drain loop
            u, v = adds.popleft()
            add_src[i], add_dst[i] = u, v
            add_mask[i] = True
        del_nodes = np.full((d_cap,), -1, np.int32)
        del_mask = np.zeros((d_cap,), bool)
        for i in range(d):
            del_nodes[i] = dels.popleft()
            del_mask[i] = True
        delta = GraphDelta(add_src=jnp.asarray(add_src), add_dst=jnp.asarray(add_dst),
                           add_mask=jnp.asarray(add_mask),
                           del_nodes=jnp.asarray(del_nodes),
                           del_mask=jnp.asarray(del_mask))
        t1 = time.perf_counter()
        graph = apply_delta(graph, delta)
        graph.src.block_until_ready()
        t2 = time.perf_counter()
        ingest_s += t1 - t0
        total_s += t2 - t0
        events_total += len(events)
    return {"ingest_seconds": ingest_s, "total_seconds": total_s,
            "events": events_total,
            "ingest_eps": events_total / max(ingest_s, 1e-12),
            "total_eps": events_total / max(total_s, 1e-12)}


def engine_path(times, src, dst, n_cap, e_cap, window, a_cap, d_cap, span,
                placement: str, adapt_iters: int):
    cfg = SystemConfig(
        stream=StreamSection(window=window, batch_span=span,
                             a_cap=a_cap, d_cap=d_cap),
        partition=PartitionSection(strategy="xdgp", k=8,
                                   adapt_iters=adapt_iters),
        telemetry=TelemetrySection(recompute_every=5))
    system = DynamicGraphSystem(empty_graph(n_cap, e_cap), cfg,
                                strategy=XdgpAdaptive(placement=placement))
    recs = system.run((times, src, dst))
    drift = [r.drift for r in recs if r.drift is not None]
    assert drift and all(d == 0.0 for d in drift), f"tracker drift: {drift}"
    events = sum(r.events for r in recs)
    ingest_s = sum(r.ingest_seconds for r in recs)
    total_s = sum(r.step_seconds for r in recs)
    return {"ingest_seconds": ingest_s, "total_seconds": total_s,
            "events": events,
            "ingest_eps": events / max(ingest_s, 1e-12),
            "total_eps": events / max(total_s, 1e-12),
            "drift_checks": len(drift), "max_drift": max(drift),
            "cut_trajectory": [r.cut_ratio for r in recs],
            "imbalance_final": recs[-1].imbalance,
            "migrations_total": sum(r.migrations for r in recs),
            "placed_total": sum(r.new_placed for r in recs)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--events", type=int, default=100_000)
    ap.add_argument("--users", type=int, default=20_000)
    ap.add_argument("--window", type=int, default=600)
    args = ap.parse_args()

    times, callers, callees = generators.sliding_window_stream(
        args.users, args.events, args.window, seed=7)
    n_cap, e_cap = args.users, 4 * args.events // 10
    a_cap = d_cap = 16384
    span = args.window // 3

    # warm up apply_delta compilation outside the timed region (both paths
    # share the jit cache, so neither pays compile time in the comparison)
    warm = empty_graph(n_cap, e_cap)
    apply_delta(warm, GraphDelta.empty(a_cap, d_cap)).src.block_until_ready()

    print(f"stream: {len(times)} events, {args.users} users, window {args.window}")
    seed = seed_path(times, callers, callees, n_cap, e_cap, args.window,
                     a_cap, d_cap, span)
    print(f"seed  path: ingest {seed['ingest_eps']:12.0f} ev/s   "
          f"end-to-end {seed['total_eps']:12.0f} ev/s")
    eng = engine_path(times, callers, callees, n_cap, e_cap, args.window,
                      a_cap, d_cap, span, placement="online", adapt_iters=3)
    print(f"engine    : ingest {eng['ingest_eps']:12.0f} ev/s   "
          f"end-to-end {eng['total_eps']:12.0f} ev/s   "
          f"(+ placement/adaptation/metrics active)")
    speedup = eng["ingest_eps"] / seed["ingest_eps"]
    print(f"ingestion speedup: {speedup:.1f}x   "
          f"drift checks: {eng['drift_checks']} (max drift {eng['max_drift']})")
    print(f"cut trajectory: {eng['cut_trajectory'][0]:.3f} → "
          f"{eng['cut_trajectory'][-1]:.3f} over {len(eng['cut_trajectory'])} supersteps; "
          f"placed {eng['placed_total']}, migrated {eng['migrations_total']}")
    # acceptance target is defined at the 100k-event scale; smaller streams
    # amortise the fixed per-batch cost worse, so only warn there
    if args.events >= 100_000:
        assert speedup >= 10.0, f"ingestion speedup {speedup:.1f}x below 10x target"
    elif speedup < 10.0:
        print(f"note: {speedup:.1f}x below the 10x target "
              f"(measured off-scale: {args.events} < 100000 events)")

    path = save("bench_stream_throughput", {
        "events": len(times), "users": args.users, "window": args.window,
        "seed_path": seed, "engine": eng, "ingest_speedup": speedup})
    print("saved", path)


if __name__ == "__main__":
    run_main(main)
