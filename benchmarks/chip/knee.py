#!/usr/bin/env python3
"""Find a stream cell's knee on the chip: the highest offered rate at which
the ingest backlog does not grow over the window.

    python3 benchmarks/chip/knee.py --workload <cell> --seed <n> \\
        --seconds <s> --rates <events/s> [<events/s> ...]

One session (the cell's configuration, built and warmed as a run does)
takes each rate in turn for ``--seconds`` of uniform open-loop arrivals;
the backlog left by one rate is drained before the next. Prints one JSON
line per rate: committed events per second, the mean superstep, and the
backlog a third of the way in and at the end.
"""
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(_HERE))


def main() -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(_ROOT, "src"), os.path.dirname(_HERE)]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_ROOT,
                                                           ".jax_cache")
    import jax
    import numpy as np
    from chip import deployment, gen, harness
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    devices = harness.chips(cell.chips)[:cell.chips]
    sess = cell.config["session"]
    g, a_cap = deployment.graph_spec(cell, "kronecker"), sess["a_cap"]
    graph = deployment.graph(cell, args.seed, sess["stream_edge_slots"])
    system = deployment.session(cell, graph, args.seed, trace=False,
                                devices=devices, stream=True)
    total = 2 * a_cap + int(sum(args.rates) * args.seconds) + a_cap
    events = gen.stream_events(args.seed, total, scale=g["scale"], a=g["A"],
                               b=g["B"], c=g["C"])
    for i in range(2):
        system.step(events[i * a_cap:(i + 1) * a_cap])
    at = 2 * a_cap
    empty = events[:0]
    for rate in args.rates:
        while system.backlog[0]:
            system.step(empty)
        n = int(rate * args.seconds)
        due = gen.due_offsets("uniform", rate, n, args.seed)
        stream = events[at:at + n]
        at += n
        sent, rows = 0, []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < args.seconds:
            hi = int(np.searchsorted(due, time.perf_counter() - t0, "right"))
            rec = system.step(stream[sent:hi])
            sent = hi
            rows.append((time.perf_counter() - t0, rec.adds,
                         rec.backlog_adds))
        third = next(b for t, _, b in rows if t >= args.seconds / 3)
        print(json.dumps({
            "rate": rate, "supersteps": len(rows),
            "committed_per_s": sum(a for _, a, _ in rows) / rows[-1][0],
            "mean_superstep_s": rows[-1][0] / len(rows),
            "backlog_third": third, "backlog_end": rows[-1][2]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
