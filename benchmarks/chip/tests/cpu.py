"""Run a cell at a tiny size on the CPU, past the harness's look for a chip
(tests only: the entry point never does this).

A cell's tiny cases run on as many host devices as the cell has chips. JAX
fixes the device count when it starts, so ``on_chips`` hands a case that
asks for another count than this process has to a child process started
with that many (``--xla_force_host_platform_device_count``) and holds the
case to the child's verdict: one-chip cells run here, on the one CPU
device, a four-chip cell on four."""
import functools
import inspect
import json
import os
import subprocess
import sys
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
DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


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
    return harness.Cell(name=cell.name, chips=cell.chips,
                        config=harness._merge(config, mode["config"]),
                        traffic=harness._merge(cell.traffic, mode["traffic"]),
                        end_to_end=cell.end_to_end,
                        per_layer=cell.per_layer)


def tiny_cell(workload: str) -> harness.Cell:
    """The cell ``workload`` of ``BENCHMARK.json``, cut to its tiny size."""
    return tiny(harness.load_cell(workload))


def run_tiny(workload, *, seed: int = 7, seconds: float = 1.0,
             trace: bool = False) -> harness.Outcome:
    """Run ``workload`` (a cell's name, or a ``Cell`` already cut) once, on
    its cell's chips' worth of this process's devices."""
    cell = tiny_cell(workload) if isinstance(workload, str) else workload
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            started=time.perf_counter(),
                            devices=jax.devices()[:cell.chips])


def cell_chips(workload: str, **_) -> int:
    """The chips of the cell ``workload`` of ``BENCHMARK.json``."""
    return harness.load_cell(workload).chips


def four_chip_stream() -> harness.Cell:
    """The first Kronecker stream cell, cut to its tiny size and deployed
    on four chips: the sharded backend, one partition a chip. A stand-in
    while ``BENCHMARK.json`` lists no four-chip cell: once one is listed,
    this and the cases that use it go, and the parametrisation over the
    listed cells covers the multi-chip path."""
    name = next(w for w in cells()
                if (c := harness.load_cell(w)).traffic["mode"] == "stream"
                and c.config["graph"]["generator"] == "kronecker")
    cell = tiny_cell(name)
    config = harness._merge(cell.config, {"session": {
        "k": 4, "cluster_backend": "sharded"}})
    return harness.Cell(name=f"{name}-sharded-k4", chips=4, config=config,
                        traffic=cell.traffic, end_to_end=cell.end_to_end,
                        per_layer=cell.per_layer)


def on_chips(chips_of=cell_chips):
    """Decorator of a test case that runs a cell: ``chips_of`` gives the
    cell's chips from the case's arguments (by default its ``workload``).
    The case runs here where this process has that many devices, and
    otherwise, alone, in a child process that has that many; it passes
    where the child's run of it passed."""
    def wrap(test):
        @functools.wraps(test)
        def case(**kwargs):
            chips = chips_of(**kwargs)
            if len(jax.devices()) == chips:
                return test(**kwargs)
            _in_child(inspect.getfile(test), chips)
        return case
    return wrap


def _in_child(path: str, chips: int) -> None:
    """Run the current test case again in a child process with ``chips``
    host devices, and fail where it fails there."""
    name = os.environ["PYTEST_CURRENT_TEST"].rsplit(" ", 1)[0]
    node = f"{path}::{name.split('::', 1)[1]}"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(DEVICE_COUNT_FLAG)]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=" ".join(flags + [f"{DEVICE_COUNT_FLAG}={chips}"]))
    env.pop("PYTEST_CURRENT_TEST", None)
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", node],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, (
        f"{node} on {chips} host devices failed:\n{out.stdout[-6000:]}"
        f"\n{out.stderr[-2000:]}")
