"""Distributed end-to-end: one paper scenario, local vs sharded execution.

The cluster engine's selling points, measured from the session itself:

  * parity     — the sharded (partition-per-device shard_map) run produces
                 bit-identical assignments and cut trajectories to the
                 local run (DESIGN.md §10), so distribution is free of
                 modelling error;
  * comm bill  — per-superstep halo/collective byte telemetry. The halo
                 volume is the boundary the adaptive heuristic shrinks, so
                 the adaptive run's comm bill falls as the cut falls —
                 "cut == comm volume" made measurable end to end;
  * gap trace  — both runs execute with span tracing on (plus the sharded
                 comm probe, DESIGN.md §11) and emit
                 ``results/trace_distributed_e2e_{local,sharded}.jsonl``, a
                 Chrome/Perfetto export, and a per-phase local-vs-sharded
                 gap summary — the measurement baseline attributing the
                 sharded slowdown to named phases (bucketing, dispatch,
                 halo exchange, quota collective, kernel, host sync).

Where JAX may run on the CPU the script first asks it for 8 fake host
devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8``, appended
unless a count is set) and runs the scenario's k. On an accelerator host it
runs one partition per device, up to the scenario's k (k=4 on a four-chip
host), and refuses to run on fewer than two.

  PYTHONPATH=src:. python benchmarks/bench_distributed_e2e.py --scale smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import numpy as np

from benchmarks.common import RESULTS_DIR, run_main, save
from repro.api import DynamicGraphSystem
from repro.scenarios import SCENARIOS

SCALES = {"smoke": 12, "small": 40, "full": None}   # max supersteps


def run_one(scn, *, k: int, cluster: str, max_supersteps):
    cfg = scn.system_config(strategy="xdgp", cluster=cluster)
    cfg = dataclasses.replace(
        cfg, partition=dataclasses.replace(cfg.partition, k=k),
        telemetry=dataclasses.replace(cfg.telemetry, trace=True,
                                      trace_comm_probe=True))
    if cluster == "sharded":
        # the scenario streams through its growth phase, so give the
        # padded buckets doubling head-room: shapes jump O(log) times
        # instead of creeping every superstep, and each jump is the only
        # recompile in its bucket
        cfg = dataclasses.replace(cfg, cluster=dataclasses.replace(
            cfg.cluster, halo_pad=1.0, block_pad=1.0, edge_pad=1.0))
    system = DynamicGraphSystem(scn.graph, cfg)
    t0 = time.perf_counter()
    recs = system.run(scn, max_supersteps=max_supersteps)
    wall = time.perf_counter() - t0
    score = system.score()
    row = {
        "cluster": cluster,
        "wall_seconds": wall,
        "supersteps": len(recs),
        "cut_final": score["cut_final"],
        "cut_trajectory": score["cut_trajectory"],
        "migrations_total": score["migrations_total"],
        "halo_bytes_total": score["halo_bytes"],
        "halo_live_bytes_total": score["halo_live_bytes"],
        "collective_bytes_total": score["collective_bytes"],
        "halo_bytes_per_superstep": [r.halo_bytes for r in recs],
        "halo_live_bytes_per_superstep": [r.halo_live_bytes for r in recs],
        "live_edges_per_superstep": [r.live_edges for r in recs],
        "cut_ratio_per_superstep": [r.cut_ratio for r in recs],
        "cluster_stats": system.snapshot()["cluster"],
    }
    return row, np.asarray(system.labels), system.tracer


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="cellular",
                    choices=sorted(SCENARIOS))
    ap.add_argument("--scale", default="smoke", choices=sorted(SCALES))
    args = ap.parse_args()

    scn = SCENARIOS[args.scenario](
        "smoke" if args.scale == "smoke" else "small", seed=0)
    max_ss = SCALES[args.scale]
    # partition-per-device: as many partitions as devices, up to the
    # scenario's own k (the fake CPU devices always cover it)
    import jax
    devices, platform = jax.device_count(), jax.default_backend()
    k = scn.k if platform == "cpu" else min(scn.k, devices)
    if k < 2 or devices < k:
        raise SystemExit(f"sharded-vs-local needs {max(k, 2)} devices; JAX "
                         f"has {devices} {platform} device(s)")

    local_row, local_labels, local_tr = run_one(scn, k=k, cluster="local",
                                                max_supersteps=max_ss)
    shard_row, shard_labels, shard_tr = run_one(scn, k=k, cluster="sharded",
                                                max_supersteps=max_ss)

    bit_identical = bool(np.array_equal(local_labels, shard_labels))
    cuts_identical = (local_row["cut_trajectory"]
                      == shard_row["cut_trajectory"])
    # the padded halo is shape-stable by design, so the "cut == comm
    # volume" trajectory lives in the *live* (unpadded) halo bytes
    halo = shard_row["halo_live_bytes_per_superstep"]
    edges = [max(1, e) for e in shard_row["live_edges_per_superstep"]]
    # the headline: comm volume *per live edge* tracks the cut the
    # heuristic is shrinking (the raw bill also grows with the graph)
    per_edge = [h / e for h, e in zip(halo, edges)]
    head = max(1, len(halo) // 3)
    halo_head = float(np.mean(per_edge[:head])) if halo else 0.0
    halo_tail = float(np.mean(per_edge[-head:])) if halo else 0.0

    # compile accounting straight off the trace: every dispatch is tagged
    # compiled=True/False, and cluster/recompile fires once per shape bucket
    dispatches = [ev for ev in shard_tr.events
                  if ev["name"] == "cluster/dispatch"]
    compiles = sum(1 for ev in dispatches
                   if ev.get("attrs", {}).get("compiled"))
    recompile_spans = sum(1 for ev in shard_tr.events
                          if ev["name"] == "cluster/recompile")
    compiled_steps = shard_row["cluster_stats"]["compiled_steps"]

    payload = {
        "scenario": scn.name,
        "k": k,
        "scale": args.scale,
        "events": scn.n_events,
        "assignments_bit_identical": bit_identical,
        "cut_trajectories_identical": cuts_identical,
        "halo_live_bytes_per_edge_early": halo_head,
        "halo_live_bytes_per_edge_late": halo_tail,
        "dispatches": len(dispatches),
        "compiled_dispatches": compiles,
        "compiled_steps": compiled_steps,
        "local": local_row,
        "sharded": shard_row,
    }
    path = save("bench_distributed_e2e", payload)

    # -- the gap trace (DESIGN.md §11): where does local-vs-sharded go? ----
    os.makedirs(RESULTS_DIR, exist_ok=True)
    local_trace = local_tr.write_jsonl(
        os.path.join(RESULTS_DIR, "trace_distributed_e2e_local.jsonl"))
    shard_trace = shard_tr.write_jsonl(
        os.path.join(RESULTS_DIR, "trace_distributed_e2e_sharded.jsonl"))
    shard_tr.write_chrome(
        os.path.join(RESULTS_DIR, "trace_distributed_e2e.trace.json"))
    sum_l, sum_s = local_tr.phase_totals(), shard_tr.phase_totals()
    gap = {
        "scenario": scn.name, "k": k, "scale": args.scale,
        "wall_local_s": local_row["wall_seconds"],
        "wall_sharded_s": shard_row["wall_seconds"],
        "slowdown": shard_row["wall_seconds"] / local_row["wall_seconds"],
        "dispatches": len(dispatches),
        "compiled_dispatches": compiles,
        "compiled_steps": compiled_steps,
        "phases_local": sum_l,
        "phases_sharded": sum_s,
        # phases only the sharded path has, ranked: the slowdown, named
        "sharded_only_total_s": {n: sum_s[n]["total_s"]
                                 for n in sorted(set(sum_s) - set(sum_l),
                                                 key=lambda n:
                                                 -sum_s[n]["total_s"])},
    }
    save("trace_distributed_e2e", gap)
    print(f"{'phase':<24} {'local':>10} {'sharded':>10}")
    for name in sorted(set(sum_l) | set(sum_s),
                       key=lambda n: -sum_s.get(n, {"total_s": 0})["total_s"]):
        tl = sum_l.get(name, {}).get("total_s", 0.0)
        ts = sum_s.get(name, {}).get("total_s", 0.0)
        print(f"{name:<24} {tl * 1e3:9.1f}ms {ts * 1e3:9.1f}ms")
    print(f"traces -> {local_trace}, {shard_trace}")

    print(f"scenario={scn.name} k={k} scale={args.scale}")
    print(f"  parity: assignments bit-identical={bit_identical} "
          f"cut trajectories identical={cuts_identical}")
    print(f"  compile cache: {compiles}/{len(dispatches)} dispatches "
          f"compiled ({compiled_steps} shape buckets, "
          f"{recompile_spans} recompile spans)")
    print(f"  sharded comm: halo={shard_row['halo_bytes_total']}B "
          f"(live {shard_row['halo_live_bytes_total']}B) "
          f"collective={shard_row['collective_bytes_total']}B "
          f"over {shard_row['supersteps']} supersteps")
    print(f"  live halo bytes per live edge early->late: "
          f"{halo_head:.2f}B -> {halo_tail:.2f}B "
          f"(cut {shard_row['cut_ratio_per_superstep'][0]:.3f} -> "
          f"{shard_row['cut_ratio_per_superstep'][-1]:.3f})")
    print(f"  wall: local={local_row['wall_seconds']:.2f}s "
          f"sharded={shard_row['wall_seconds']:.2f}s")
    print(f"saved -> {path}")
    assert bit_identical and cuts_identical, "sharded parity violated"
    # the bugfix's contract: at most one compile per shape bucket
    assert compiles == recompile_spans == compiled_steps, \
        (compiles, recompile_spans, compiled_steps)
    assert compiles < max(2, len(dispatches)), \
        f"every dispatch recompiled ({compiles}/{len(dispatches)})"


if __name__ == "__main__":
    from repro.compat import request_host_devices
    request_host_devices(8)         # before JAX initialises its backends
    run_main(main)
