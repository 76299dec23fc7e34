#!/usr/bin/env python3
"""Profile the ``adapt`` cell with the program's tracer on.

    python3 benchmarks/chip/profile_adapt.py --seed <n> [--pairs 5] \\
        [--workload fem64-adapt] [--side S --rounds R] [--out FILE] \\
        [--keep-trace DIR]

After a traced set-up (the graph, then one ``adapt(R)`` on a traced
session, as the cell's set-up does untraced), it times ``--pairs`` pairs of
``adapt(R)`` from fresh hash starts, one untraced and one traced in
alternating order (the cost of tracing), then profiles one traced
repetition (session reset and ``adapt(R)``, as the cell's ``--trace 1``
run does) under ``jax.profiler``. The program's spans are read from the
capture (``spans.py``), so the idle gaps are named by them.

The last line of standard output is one JSON object: the set-up's phases
and its ``compile_s`` by stage (the set-up session's and the whole
process's), the pairs and the median over them of the traced one's excess
in %, the per-layer metrics of the cell and of the program's spans and
counters (``metrics/``), the breakdown and the reads counted per site;
``--out`` writes it to a file as well, and ``--keep-trace`` copies the
capture to a directory for reading by hand. ``--side``/``--rounds`` shrink
the cell for a rehearsal on the CPU. This is a measurement tool: it prints
no result line of the benchmark and checks nothing against the reference.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(_HERE))

PROGRAM_METRICS = ("plan_build_ms.adapt", "history_idle_ms.adapt",
                   "host_syncs.adapt", "compile_s.adapt")


def _session(cell, graph, seed: int, trace: bool):
    # the cell's session (adapt_cell._session) with telemetry.trace set
    from repro.api import DynamicGraphSystem, SystemConfig
    from repro.api.config import (ComputeSection, PartitionSection,
                                  TelemetrySection)
    s = cell.config["session"]
    cfg = SystemConfig(
        partition=PartitionSection(strategy="xdgp", k=s["k"], s=s["s"],
                                   slack=s["slack"]),
        compute=ComputeSection(backend=s["compute_backend"]),
        telemetry=TelemetrySection(trace=trace), seed=seed)
    return DynamicGraphSystem(graph, cfg)


def _compile_s(events: List[Dict]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for e in events:
        if e["type"] == "counter" and e["name"] == "compile_s":
            stage = e["attrs"]["stage"]
            out[stage] = out.get(stage, 0.0) + e["value"]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="fem64-adapt")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--pairs", type=int, default=5)
    ap.add_argument("--side", type=int, default=None)
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args()

    import jax
    import numpy as np
    from repro.obs.trace import Tracer
    process = Tracer(meta={"label": "process"})     # every compile, set-up on
    from chip import adapt_cell, cost, harness, spans, xplane

    over: Dict[str, Any] = {"config": {}, "traffic": {}}
    if args.side:
        over["config"]["graph"] = {"side": args.side}
    if args.rounds:
        over["traffic"]["rounds"] = args.rounds
    cell = harness.load_cell(args.workload, over)
    rounds = cell.traffic["rounds"]
    device = jax.devices()[0]
    harness.log(f"device: {device.platform} {device.device_kind}; "
                f"{cell.name}, seed {args.seed}, rounds {rounds}")

    phases: Dict[str, float] = {}
    t = time.perf_counter()
    graph = adapt_cell._graph(cell)
    jax.block_until_ready(graph.edge_mask)
    phases["graph"] = time.perf_counter() - t
    t = time.perf_counter()
    system = _session(cell, graph, args.seed, trace=True)
    jax.block_until_ready((system.labels, system.tracker.cut))
    phases["session"] = time.perf_counter() - t
    t = time.perf_counter()
    system.adapt(rounds)
    jax.block_until_ready((system.labels, system.tracker.cut))
    phases["warmup_adapt"] = time.perf_counter() - t
    setup_spans = list(system.tracer.events)
    del system
    phases["setup_s"] = time.perf_counter() - STARTED
    process_compile = _compile_s(process.events)
    process.events.clear()

    pairs: List[Dict[str, float]] = []
    for i in range(args.pairs):
        pair: Dict[str, float] = {}
        for trace in ((False, True) if i % 2 == 0 else (True, False)):
            s = _session(cell, graph, args.seed + 1 + i, trace)
            jax.block_until_ready((s.labels, s.tracker.cut))
            t = time.perf_counter()
            s.adapt(rounds)
            jax.block_until_ready((s.labels, s.tracker.cut))
            pair["traced" if trace else "plain"] = time.perf_counter() - t
            del s
        pairs.append(pair)
    cost_pct = statistics.median(
        100.0 * (p["traced"] / p["plain"] - 1.0) for p in pairs
    ) if pairs else None

    logdir = tempfile.mkdtemp(prefix="chip-trace-")
    lines: Dict[str, int] = {}
    try:
        jax.profiler.start_trace(logdir)
        with jax.profiler.TraceAnnotation("bench/adapt"):
            system = _session(cell, graph, args.seed + 1000, trace=True)
            jax.block_until_ready((system.labels, system.tracker.cut))
            system.adapt(rounds)
            jax.block_until_ready((system.labels, system.tracker.cut))
        jax.profiler.stop_trace()
        events = xplane.load(logdir, lines)
        program = spans.load(logdir)
        marks = [e for e in events if e["kind"] == "host"]
        lo = min(e["start_ns"] for e in marks)
        hi = max(e["start_ns"] + e["dur_ns"] for e in marks)
        profile = xplane.reduce(events, (lo, hi),
                                kernel=cell.config["kernel_op"],
                                spans=program)
        if args.keep_trace:
            shutil.copytree(logdir, args.keep_trace)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)

    edges = int(np.asarray(graph.edge_mask).sum())
    run = {"trace": profile, "device_kind": device.device_kind,
           "work": cost.scoring_pass(2 * edges, graph.n_cap,
                                     cell.config["session"]["k"]),
           "counters": {"rounds_profiled": rounds},
           "spans": list(system.tracer.events), "setup_spans": setup_spans}
    metrics = {}
    for name in [m["name"] for m in cell.per_layer] + list(PROGRAM_METRICS):
        metrics[name] = harness.reader(name)(run)
    gaps = dict(profile["idle_gaps"])
    inside = {k: v for k, v in gaps.items() if k.startswith("xdgp/")}
    syncs = [e for e in run["spans"] if e["type"] == "counter"
             and e["name"] == "host_syncs"]
    counts: Dict[str, int] = {}
    for name in ("adapt", "plan.build", "adapt.history"):
        counts[name] = sum(1 for s in program if s["name"] == "xdgp/" + name)
    out = {
        "workload": cell.name, "seed": args.seed, "rounds": rounds,
        "device": device.device_kind, "setup": phases,
        "compile_s": {"setup_session": _compile_s(setup_spans),
                      "process_setup": process_compile,
                      "after_setup": _compile_s(process.events)},
        "pairs": pairs, "tracing_cost_pct": cost_pct,
        "metrics": metrics,
        "breakdown": {"window_s": profile["window_s"],
                      "busy_s": profile["busy_s"],
                      "device_ops": profile["device_ops"],
                      "idle_gaps": profile["idle_gaps"]},
        "idle_inside_adapt_s": sum(inside.values()),
        "adapt_self_idle_share": (inside.get("xdgp/adapt", 0.0)
                                  / sum(inside.values())
                                  if inside else None),
        "host_syncs": [dict(s.get("attrs", {}), total=s["value"])
                       for s in syncs],
        "program_spans_in_capture": counts,
        "device_lines": lines,
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _HERE]
    sys.path[:0] = [os.path.join(_ROOT, "src"), os.path.dirname(_HERE)]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_ROOT,
                                                           ".jax_cache")
    import jax
    from repro import compat
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compat.enable_compile_cache()
    sys.exit(main())
