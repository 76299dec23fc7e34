#!/usr/bin/env python3
"""Drive the session's main path once on a TPU and check what comes out.

    python chip_smoke.py              # phases "stream" and "kernel", one chip
    python chip_smoke.py --chips 4    # the sharded backend against local

Phases (one process; every check must hold or the script exits non-zero):

* ``stream`` — a 1M-vertex R-MAT session (k=8, PageRank interleaved) takes
  a live edge stream from a disjoint seed for ``--steps`` supersteps, then
  ``adapt(4)``; run with the fused scorer (``compute.backend="pallas"``) and
  with the unfused reference (``"ref"``). Assignments and every superstep's
  ``cut_edges`` must agree bit for bit, and the tracker's drift check at the
  last superstep must read 0.
* ``kernel`` — a 262,144-vertex FEM cube (k=9) takes a fixed number of
  batch rounds on the native Mosaic kernel over a BSR plan, and on the
  reference; assignments must agree bit for bit.
* ``sharded`` (only with ``--chips 4``) — the stream session at k=4 on the
  ``sharded`` backend over four chips and on ``local``; assignments must
  agree bit for bit.

Earlier lines report the device, the executor and plan, first-call
(compile included) and steady wall seconds of this one run, and the parity
outcomes; they are a smoke check, not a benchmark. The last line is the
JSON verdict. Without a TPU the script fails before running any phase.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "src"))


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def tpu_devices(count: int):
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    check(platform == "tpu",
          f"no TPU found: JAX sees {len(devices)} {platform} device(s)")
    check(len(devices) >= count,
          f"need {count} TPU chips, JAX sees {len(devices)}")
    return devices


def stream_session(*, n: int, k: int, steps: int, adapt_rounds: int,
                   compute: str, cluster: str, seed: int):
    """One streaming session: ``steps`` supersteps of a live edge stream,
    then ``adapt(adapt_rounds)``; returns what the parity checks compare."""
    import numpy as np
    from repro.api import DynamicGraphSystem, SystemConfig
    from repro.api.config import (ClusterSection, ComputeSection,
                                  GraphSection, PartitionSection,
                                  StreamSection, TelemetrySection)
    from repro.scale import make_edge_stream, stream_events

    a_cap = 1 << 16
    cfg = SystemConfig(
        graph=GraphSection(generator="rmat", n=n, avg_degree=8.0),
        stream=StreamSection(window=1 << 40, a_cap=a_cap, d_cap=1024),
        partition=PartitionSection(strategy="xdgp", k=k, adapt_iters=4),
        compute=ComputeSection(program="pagerank", backend=compute),
        cluster=ClusterSection(backend=cluster),
        telemetry=TelemetrySection(recompute_every=steps),
        seed=seed)
    t0 = time.perf_counter()
    system = DynamicGraphSystem(config=cfg)
    build_s = time.perf_counter() - t0
    live = make_edge_stream("rmat", n, avg_degree=8.0,
                            chunk_edges=a_cap // 2, seed=seed + 1)
    records = []
    for i, batch in enumerate(stream_events(live, t0=1)):
        if i >= steps:
            break
        records.append(system.step(batch))
    check(len(records) == steps,
          f"the live stream ran dry after {len(records)} supersteps")
    t0 = time.perf_counter()
    system.adapt(adapt_rounds)
    adapt_s = time.perf_counter() - t0
    labels = np.asarray(system.labels)
    live_mask = np.asarray(system.graph.node_mask)
    rank = np.asarray(system.program_state)
    check(labels.shape == (system.graph.n_cap,), "assignment shape")
    check(bool(((labels[live_mask] >= 0) & (labels[live_mask] < k)).all()),
          "assignment outside [0, k)")
    check(bool(np.isfinite(rank).all()), "non-finite PageRank state")
    step_s = [r.step_seconds for r in records]
    return {
        "labels": labels, "rank": rank,
        "cut_edges": [r.cut_edges for r in records],
        "drift": records[-1].drift, "cut_after": system.cut_ratio,
        "events": sum(r.events for r in records),
        "edges": int(system.tracker.edges), "build_s": build_s,
        "first_step_s": step_s[0],
        "steady_step_s": statistics.median(step_s[1:]),
        "adapt_s": adapt_s, "plan": system.scoring_plan,
    }


def report_stream(phase: str, name: str, run) -> None:
    log(phase, f"{name}: build {run['build_s']:.3f}s, first superstep "
               f"{run['first_step_s']:.3f}s (compile included), steady "
               f"superstep {run['steady_step_s']:.3f}s, adapt "
               f"{run['adapt_s']:.3f}s, plan {run['plan']}, "
               f"{run['events']} events, cut_edges {run['cut_edges']}, "
               f"drift {run['drift']}, cut after adapt "
               f"{run['cut_after']:.6f}")


def compare_runs(phase: str, a, b, names) -> None:
    import numpy as np
    check(a["drift"] == 0.0 and b["drift"] == 0.0,
          f"tracker drift at the last superstep: {a['drift']}, {b['drift']}")
    check(a["cut_edges"] == b["cut_edges"],
          f"per-superstep cut_edges differ: {a['cut_edges']} vs "
          f"{b['cut_edges']}")
    differ = int(np.sum(a["labels"] != b["labels"]))
    check(differ == 0, f"{names[0]} and {names[1]} assignments differ in "
                       f"{differ} slots")
    check(np.array_equal(a["rank"], b["rank"]),
          "PageRank states differ on the same graph")
    log(phase, f"parity: {names[0]} == {names[1]} bit for bit "
               f"({a['labels'].shape[0]} slots, {len(a['cut_edges'])} "
               f"supersteps + adapt), drift 0")


def phase_stream(args) -> None:
    runs = {}
    for compute in ("pallas", "ref"):
        runs[compute] = stream_session(
            n=args.n, k=8, steps=args.steps, adapt_rounds=4,
            compute=compute, cluster="local", seed=args.seed)
        report_stream("stream", compute, runs[compute])
    check(runs["pallas"]["plan"] is not None
          and runs["pallas"]["plan"]["kind"] in ("bsr", "flat"),
          f"no batch plan recorded: {runs['pallas']['plan']}")
    compare_runs("stream", runs["pallas"], runs["ref"], ("pallas", "ref"))


def phase_kernel(args) -> None:
    import numpy as np
    from repro import compat
    from repro.api import DynamicGraphSystem, SystemConfig
    from repro.api.config import ComputeSection, PartitionSection
    from repro.graph.generators import fem_cube

    executor = compat.pallas_executor()
    check(executor == "native",
          f"fused kernel executor is {executor!r}, not 'native'")
    graph = fem_cube(args.fem_side)
    labels = {}
    for compute in ("pallas", "ref"):
        cfg = SystemConfig(partition=PartitionSection(strategy="xdgp", k=9),
                           compute=ComputeSection(backend=compute),
                           seed=args.seed)
        system = DynamicGraphSystem(graph, cfg)
        times = []
        for _ in range(2):           # first call compiles; second is steady
            t0 = time.perf_counter()
            system.adapt(args.rounds)
            times.append(time.perf_counter() - t0)
        plan = system.scoring_plan
        if compute == "pallas":
            check(plan is not None and plan["kind"] == "bsr",
                  f"kernel phase did not score over BSR tiles: {plan}")
        labels[compute] = np.asarray(system.labels)
        log("kernel", f"{compute}: executor "
                      f"{executor if compute == 'pallas' else 'unfused'}, "
                      f"plan {plan}, adapt({args.rounds}) first "
                      f"{times[0]:.3f}s (compile included), steady "
                      f"{times[1]:.3f}s, cut {system.cut_ratio:.6f}")
        del system
    differ = int(np.sum(labels["pallas"] != labels["ref"]))
    check(differ == 0, f"native and ref assignments differ in {differ} slots")
    log("kernel", f"parity: native == ref bit for bit "
                  f"({labels['ref'].shape[0]} slots, "
                  f"{2 * args.rounds} rounds)")


def phase_sharded(args) -> None:
    runs = {}
    for cluster in ("sharded", "local"):
        runs[cluster] = stream_session(
            n=args.n, k=4, steps=args.steps, adapt_rounds=4,
            compute="pallas", cluster=cluster, seed=args.seed)
        report_stream("sharded", cluster, runs[cluster])
    compare_runs("sharded", runs["sharded"], runs["local"],
                 ("sharded", "local"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phase and its local twin")
    ap.add_argument("--n", type=int, default=1_000_000,
                    help="R-MAT vertices of the stream sessions")
    ap.add_argument("--steps", type=int, default=4,
                    help="supersteps of the live stream")
    ap.add_argument("--fem-side", type=int, default=64,
                    help="side of the FEM cube of the kernel phase")
    ap.add_argument("--rounds", type=int, default=20,
                    help="batch rounds per adapt() call of the kernel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    check(args.steps >= 2, "--steps must be at least 2")

    import jax
    from repro import compat
    compat.enable_compile_cache()
    devices = tpu_devices(args.chips)
    log("device", f"platform {devices[0].platform}, kind "
                  f"{devices[0].device_kind}, count {len(devices)}, "
                  f"jax {jax.__version__}")
    phases = ([phase_sharded] if args.chips == 4
              else [phase_stream, phase_kernel])
    for phase in phases:
        t0 = time.perf_counter()
        phase(args)
        log(phase.__name__[len("phase_"):],
            f"phase wall {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
