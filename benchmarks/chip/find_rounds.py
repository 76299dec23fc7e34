#!/usr/bin/env python3
"""Find the round count of an ``adapt`` traffic mix on the CPU.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/find_rounds.py \\
        --config fem-cube-64-k9 --horizon 200 --within 0.05

Runs the plain reference from the hash start with session seed 0 for
``--horizon`` rounds and prints, as JSON, the cut after every round and the
first round whose cut lies within ``--within`` (a fraction) of the cut
after the horizon. The traffic file records that round and both cuts.
"""
import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--horizon", type=int, default=200)
    ap.add_argument("--within", type=float, default=0.05)
    args = ap.parse_args()
    sys.path[:0] = [os.path.dirname(_HERE)]
    import jax
    import numpy as np
    from chip import gen
    from chip.reference import partition
    with open(os.path.join(_HERE, "configs", args.config + ".json")) as f:
        config = json.load(f)
    side, s = config["graph"]["side"], config["session"]
    src, dst = gen.fem_cube_edges(side)
    n, k = side ** 3, s["k"]
    src, dst = src.astype(np.int32), dst.astype(np.int32)
    mask = np.ones(src.shape, bool)
    live = np.ones((n,), bool)
    labels = partition.hash_start(n, k)
    pending = np.full((n,), -1, np.int32)
    cap = partition.capacity(n, k, s["slack"])
    key = jax.random.PRNGKey(0)
    cuts = []
    for _ in range(args.horizon):
        labels, pending, key = partition.migrate(
            src, dst, mask, live, labels, pending, cap, key, rounds=1,
            s=s["s"], k=k, flush=False)
        cuts.append(int(partition.cut_edges(src, dst, mask, labels)))
    final = cuts[-1]
    rounds = next(r + 1 for r, c in enumerate(cuts)
                  if c <= (1 + args.within) * final)
    print(json.dumps({"rounds": rounds, "cut_at_rounds": cuts[rounds - 1],
                      "cut_at_horizon": final, "horizon": args.horizon,
                      "within": args.within, "edges": int(src.shape[0]),
                      "cuts": cuts}))


if __name__ == "__main__":
    main()
