"""Run a cell at a tiny size on the CPU, past the harness's look for a chip
(tests only: the entry point never does this)."""
import os
import time

import jax

from chip import harness

TINY = {
    "g500-s18-sat": {"config": {"graph": {"scale": 9},
                                "session": {"a_cap": 256,
                                            "stream_edge_slots": 16384}},
                     "traffic": {"knee_events_per_s": 2000,
                                 "tail_seconds": 2}},
    "g500-s18-paced": {"config": {"graph": {"scale": 9},
                                  "session": {"a_cap": 256,
                                              "stream_edge_slots": 16384}},
                       "traffic": {"knee_events_per_s": 2000,
                                   "tail_seconds": 2}},
    "fem64-adapt": {"config": {"graph": {"side": 8}},
                    "traffic": {"rounds": 6}},
}


FILES = {"g500-s18-sat": "graph500-s18-ef16",
         "g500-s18-paced": "graph500-s18-ef16",
         "fem64-adapt": "fem-cube-64-k9"}


def tiny_cell(workload: str) -> harness.Cell:
    """The cell's configuration and traffic files, cut to ``TINY`` (read
    by name, so cells not yet listed in BENCHMARK.json run too)."""
    over = TINY[workload]
    config = harness._json(os.path.join(harness.HERE, "configs",
                                        FILES[workload] + ".json"))
    traffic = harness._json(os.path.join(harness.HERE, "traffic",
                                         workload + ".json"))
    return harness.Cell(name=workload, chips=1,
                        config=harness._merge(config, over["config"]),
                        traffic=harness._merge(traffic, over["traffic"]),
                        end_to_end=[], per_layer=[])


def run_tiny(workload: str, *, seed: int = 7, seconds: float = 1.0,
             trace: bool = False) -> harness.Outcome:
    return harness.run_cell(tiny_cell(workload), seed=seed, seconds=seconds,
                            trace=trace, started=time.perf_counter(),
                            devices=jax.devices())
