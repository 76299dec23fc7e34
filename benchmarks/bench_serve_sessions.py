"""Serving-layer benchmark (DESIGN.md §12): N concurrent tenant sessions
under sustained bursty open-loop load, plus the kill-and-recover drill.

Two measurements, one committed artifact (results/bench_serve_sessions.json,
schema-checked by ``repro.obs.schema.validate_serve_bench`` in CI):

* **Sustained throughput + tail latency** — every tenant gets its own
  open-loop arrival process (Poisson base + periodic bursts; arrivals do
  NOT wait for the server, so a slow server accumulates real backlog).
  Headline: aggregate events/sec and the pooled p50/p99 submit→commit
  ingest latency across all tenants.

* **Kill-and-recover drill** — a checkpointed serving process is started
  and SIGKILLed mid-run (real subprocess, no cleanup), a fresh process
  recovers from the last committed checkpoint and replays; the bench
  asserts every tenant's telemetry digest equals the uninterrupted
  reference bit for bit and reports the recovery wall time.

    PYTHONPATH=src python -m benchmarks.bench_serve_sessions [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List

import numpy as np

from benchmarks.common import run_main, save
from repro.api import SystemConfig
from repro.serve import (AdmissionPolicy, GraphServer, OpenLoopLoad,
                         TrafficShape, synthetic_stream)
from repro.serve import drill

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tenant_config(i: int, *, n_cap: int, e_cap: int) -> SystemConfig:
    return SystemConfig.from_dict({
        "graph": {"n_cap": n_cap, "e_cap": e_cap},
        "stream": {"window": 600, "a_cap": 2048, "d_cap": 1024},
        "partition": {"k": 4},
        "seed": 11 + i,
    })


def serve_open_loop(n_tenants: int, n_events: int, *, quick: bool,
                    ) -> Dict[str, Any]:
    """Drive N tenants with independent bursty open-loop arrivals until
    every load is delivered and drained; measure sustained ingest."""
    # offered aggregate ≈ tenants · (0.8·rate + 0.2·burst) — sized so bursts
    # overrun service capacity (queues form, p99 ≫ p50) but the server
    # catches up between bursts instead of saturating for the whole run
    shape = TrafficShape(rate=1000.0, burst_rate=8000.0,
                         burst_every=1.0, burst_len=0.2)
    server = GraphServer(admission=AdmissionPolicy(queue_cap=200_000,
                                                   max_batch_events=4096))
    loads: Dict[str, OpenLoopLoad] = {}
    for i in range(n_tenants):
        name = f"tenant{i}"
        server.add_tenant(name, config=_tenant_config(
            i, n_cap=128 if quick else 256, e_cap=4096 if quick else 8192))
        t, u, v = synthetic_stream(96 if quick else 192, n_events,
                                   seed=11 + i, span=3000)
        loads[name] = OpenLoopLoad(t, u, v, shape, seed=31 + i)

    # warm the jit caches off the clock (the first superstep compiles, which
    # would otherwise dominate the recorded ingest latencies)
    for name in loads:
        server.submit(name, loads[name].take_due(0.002))
    server.drain()
    for t in server.tenants.values():
        t.latencies.clear()

    t0 = time.perf_counter()
    ticks = 0
    while True:
        elapsed = time.perf_counter() - t0
        for name, load in loads.items():
            batch = load.take_due(elapsed)
            if batch.size:
                server.submit(name, batch)
        busy = any(t.chunks or t.stream_backlog
                   for t in server.tenants.values())
        if not busy and all(l.remaining == 0 for l in loads.values()):
            break
        server.tick()
        ticks += 1
    wall = time.perf_counter() - t0

    stats = server.stats()
    pooled = np.concatenate([np.asarray(t.latencies, np.float64)
                             for t in server.tenants.values()])
    events_total = int(sum(t.admitted for t in server.tenants.values()))
    return {
        "tenants": n_tenants,
        "ticks": ticks,
        "events_total": events_total,
        "supersteps_total": int(sum(t["supersteps"] for t in
                                    stats["tenants"].values())),
        "wall_seconds": wall,
        "events_per_sec": events_total / wall,
        "ingest_p50_s": float(np.percentile(pooled, 50)),
        "ingest_p99_s": float(np.percentile(pooled, 99)),
        "per_tenant": {
            name: {"events": server.tenants[name].admitted,
                   "supersteps": int(t["supersteps"]),
                   "rejected": server.tenants[name].rejected,
                   "shed": server.tenants[name].shed,
                   "p50_s": t["ingest_p50_s"], "p99_s": t["ingest_p99_s"]}
            for name, t in stats["tenants"].items()},
    }


def kill_recover_drill(n_tenants: int, *, quick: bool) -> Dict[str, Any]:
    """Real-process SIGKILL drill via ``repro.serve.drill``; returns recovery
    seconds + bit-exactness against the uninterrupted reference."""
    workdir = tempfile.mkdtemp(prefix="serve_drill_")
    cfg = dict(drill.DEFAULT_CONFIG)
    cfg.update(tenants=n_tenants, workdir=workdir,
               ticks=16 if quick else 24, kill_tick=11 if quick else 14,
               n_events=300 if quick else 600)
    cfg_path = os.path.join(workdir, "cfg.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")

    def run(command: str) -> subprocess.CompletedProcess:
        return subprocess.run(
            [sys.executable, "-m", "repro.serve.drill", command,
             "--config", cfg_path],
            capture_output=True, text=True, env=env, cwd=REPO, timeout=900)

    # every drill process (the reference too) is a child of its own: the
    # accelerator belongs to one process at a time
    victim = run("run")
    if victim.returncode != -signal.SIGKILL:
        raise RuntimeError(f"drill run did not die by SIGKILL "
                           f"(rc={victim.returncode}): {victim.stderr}")
    rec = run("recover")
    if rec.returncode != 0:
        raise RuntimeError(f"drill recover failed: {rec.stderr}")
    ref = run("reference")
    if ref.returncode != 0:
        raise RuntimeError(f"drill reference failed: {ref.stderr}")
    with open(os.path.join(workdir, "recovered.json")) as f:
        recovered = json.load(f)
    with open(os.path.join(workdir, "reference.json")) as f:
        reference = json.load(f)
    bit_exact = recovered["digests"] == reference["digests"]
    if not bit_exact:
        raise RuntimeError("kill-recover drill diverged from the reference")
    return {
        "seconds": recovered["recovery"]["seconds"],
        "replay_total_seconds": recovered["total_seconds"],
        "manifest_tick": recovered["recovery"]["tick"],
        "kill_tick": cfg["kill_tick"],
        "tenants": n_tenants,
        "bit_exact": bit_exact,
    }


def run(quick: bool = False) -> Dict[str, Any]:
    n_tenants = 8
    n_events = 1500 if quick else 4000
    # the drill's processes need the accelerator, which this process holds
    # from its first JAX computation on: run them before it has one
    recovery = kill_recover_drill(n_tenants, quick=quick)
    payload = serve_open_loop(n_tenants, n_events, quick=quick)
    payload["recovery"] = recovery
    return payload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    payload = run(quick=args.quick)

    from repro.obs.schema import validate_serve_bench
    validate_serve_bench(payload)
    path = save("bench_serve_sessions", payload)
    print(f"tenants={payload['tenants']} "
          f"events/sec={payload['events_per_sec']:.0f} "
          f"p50={payload['ingest_p50_s'] * 1e3:.1f}ms "
          f"p99={payload['ingest_p99_s'] * 1e3:.1f}ms "
          f"recovery={payload['recovery']['seconds']:.2f}s "
          f"bit_exact={payload['recovery']['bit_exact']}")
    print(path)


if __name__ == "__main__":
    run_main(main)
