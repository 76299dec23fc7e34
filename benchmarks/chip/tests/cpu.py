"""Run a cell at a tiny size on the CPU, past the harness's look for a chip
(tests only: the entry point never does this)."""
import json
import os
import time

import jax

from chip import harness

# the cut to a tiny size, by what a cell is made of: its graph's generator
# and its traffic's mode, so a cell added as data files runs here too
TINY_GRAPH = {"kronecker": {"scale": 9}, "fem_cube": {"side": 8}}
TINY_MODE = {
    "stream": {"config": {"session": {"a_cap": 256,
                                      "stream_edge_slots": 16384}},
               "traffic": {"knee_events_per_s": 2000, "tail_seconds": 2}},
    "adapt": {"config": {}, "traffic": {"rounds": 6}},
}


def benchmark() -> dict:
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells() -> list:
    """The names of the cells ``BENCHMARK.json`` lists."""
    return [w["name"] for w in benchmark()["workloads"]]


def tiny(cell: harness.Cell) -> harness.Cell:
    """``cell`` cut to its tiny size."""
    graph = TINY_GRAPH[cell.config["graph"]["generator"]]
    mode = TINY_MODE[cell.traffic["mode"]]
    config = harness._merge(cell.config, {"graph": graph})
    return harness.Cell(name=cell.name, chips=1,
                        config=harness._merge(config, mode["config"]),
                        traffic=harness._merge(cell.traffic, mode["traffic"]),
                        end_to_end=cell.end_to_end,
                        per_layer=cell.per_layer)


def tiny_cell(workload: str) -> harness.Cell:
    """The cell ``workload`` of ``BENCHMARK.json``, cut to its tiny size."""
    return tiny(harness.load_cell(workload))


def run_tiny(workload, *, seed: int = 7, seconds: float = 1.0,
             trace: bool = False) -> harness.Outcome:
    """Run ``workload`` (a cell's name, or a ``Cell`` already cut) once."""
    cell = tiny_cell(workload) if isinstance(workload, str) else workload
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            started=time.perf_counter(),
                            devices=jax.devices())
