#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...]

For each seed, in one process: one run of the cell (the session's
readings of every compared number) and the control, the reference in
bfloat16 put in the session's place, on that run's own inputs. Prints one
JSON line per seed: ``{"seed", "program": {...}, "control": {...}}``.
The benchmark's own runs never run the control.
"""
import os
import sys
import time

STARTED = time.perf_counter()
_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(_HERE))


def main() -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(_ROOT, "src"), os.path.dirname(_HERE)]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_ROOT,
                                                           ".jax_cache")
    import jax
    from chip import harness
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    devices = harness.chips(cell.chips)[:cell.chips]
    started = STARTED
    for seed in args.seeds:
        out = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                               trace=False, started=started,
                               devices=devices)
        t = time.perf_counter()
        control = harness.runner(cell.traffic["mode"]).control(
            cell, seed, out.run["replay"])
        print(json.dumps({
            "seed": seed, "correct": out.correct,
            "program": {k: c["value"] for k, c in out.checks.items()},
            "control": {k: c["value"] for k, c in control.items()},
            "control_s": time.perf_counter() - t,
            "metrics": out.metrics, "notes": out.notes}), flush=True)
        started = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
