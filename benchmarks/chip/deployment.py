"""The deployment a configuration file states: its graph and its session.

A runner builds both from the file alone. Every key of ``graph`` and of
``session`` is honoured by the runner that reads the file, or the run stops
with ``BenchError``: a key that nothing reads would leave the deployment
running on the program's defaults without a word.

* ``graph(cell, seed, spare_slots)`` dispatches on ``graph.generator``:
  ``kronecker`` (the Graph500 generator, drawn from the seed, with
  ``spare_slots`` free edge slots for a stream) or ``fem_cube`` (the 3-D
  lattice, the same for every seed).
* ``session(cell, graph, seed, ...)`` builds the ``DynamicGraphSystem``: the
  partitioning, the scorer, the execution backend (``cluster_backend``) and
  the tie rule, plus the stream's keys where the runner streams.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

from . import gen
from .harness import BenchError, Cell

INFINITE_WINDOW = 1 << 40

GRAPH_KEYS = {
    "kronecker": {"generator", "scale", "edgefactor", "A", "B", "C", "D",
                  "permute_vertices"},
    "fem_cube": {"generator", "side"},
}
BATCH_KEYS = {"k", "s", "slack", "tie_break", "compute_backend",
              "cluster_backend"}
STREAM_KEYS = BATCH_KEYS | {"adapt_iters", "a_cap", "d_cap", "window",
                            "program", "recompute_every",
                            "stream_edge_slots"}


def _only(section: str, given: Dict[str, Any], known: set) -> None:
    extra = sorted(set(given) - known)
    if extra:
        raise BenchError(f"{section} keys {extra} are not honoured by this "
                         f"runner (it reads {sorted(known)})")


def graph_spec(cell: Cell, *generators: str) -> Dict[str, Any]:
    """``cell``'s ``graph`` section, checked: a generator among
    ``generators`` and no key the generator does not read."""
    g = cell.config["graph"]
    name = g.get("generator")
    if name not in generators:
        raise BenchError(f"graph generator {name!r} is not one this runner "
                         f"builds ({', '.join(generators)})")
    _only("graph", g, GRAPH_KEYS[name])
    if name == "kronecker":
        if abs(g["A"] + g["B"] + g["C"] + g["D"] - 1.0) > 1e-9:
            raise BenchError(f"Kronecker initiator A+B+C+D must be 1, got "
                             f"{g['A'] + g['B'] + g['C'] + g['D']}")
        if g["permute_vertices"] is not True:
            raise BenchError("the Kronecker generator always permutes the "
                             "vertices (permute_vertices must be true)")
    return g


def kronecker_arrays(cell: Cell, seed: int, spare_slots: int):
    """(src, dst, edge_mask, node_mask, edges) of the seed's Kronecker base
    graph with ``spare_slots`` free edge slots."""
    g = graph_spec(cell, "kronecker")
    n = 1 << g["scale"]
    m = g["edgefactor"] * n
    u, v = gen.kronecker_edges(seed, gen.BASE_EDGES, scale=g["scale"], m=m,
                               a=g["A"], b=g["B"], c=g["C"])
    return gen.dedupe_graph(u, v, n=n, e_cap=m + spare_slots)


def graph(cell: Cell, seed: int, spare_slots: int = 0):
    """The configuration's base graph, as the program's ``Graph``."""
    from repro.graph.structure import Graph, from_edges
    g = graph_spec(cell, *GRAPH_KEYS)
    if g["generator"] == "fem_cube":
        src, dst = gen.fem_cube_edges(g["side"])
        return from_edges(src, dst, g["side"] ** 3)
    src, dst, edge_mask, node_mask, _ = kronecker_arrays(cell, seed,
                                                         spare_slots)
    return Graph(src=src, dst=dst, node_mask=node_mask, edge_mask=edge_mask)


def session(cell: Cell, graph, seed: int, *, trace: bool, devices: Sequence,
            stream: bool):
    """The session the configuration states. ``stream``: the runner feeds
    it events, so the stream's keys are read as well. An execution
    backend that needs more chips than the cell has is refused here, not at
    the first superstep."""
    from repro.api import DynamicGraphSystem, SystemConfig
    from repro.api.config import (ClusterSection, ComputeSection,
                                  PartitionSection, StreamSection,
                                  TelemetrySection)
    s = cell.config["session"]
    _only("session", s, STREAM_KEYS if stream else BATCH_KEYS)
    if s["tie_break"] != "random":
        raise BenchError(f"tie_break {s['tie_break']!r}: the reference "
                         f"breaks ties at random only (Spinner's rule)")
    partition = dict(strategy="xdgp", k=s["k"], s=s["s"], slack=s["slack"],
                     tie_break=s["tie_break"])
    compute = dict(backend=s["compute_backend"])
    telemetry = dict(trace=trace)
    sections: Dict[str, Any] = {}
    if stream:
        if s["window"] != "infinite":
            raise BenchError(f"session window {s['window']!r}: the stream "
                             f"runner and its reference expire nothing, so "
                             f"the window must be \"infinite\"")
        sections["stream"] = StreamSection(window=INFINITE_WINDOW,
                                           a_cap=s["a_cap"], d_cap=s["d_cap"])
        partition["adapt_iters"] = s["adapt_iters"]
        compute["program"] = s["program"]
        telemetry["recompute_every"] = s["recompute_every"]
    cfg = SystemConfig(partition=PartitionSection(**partition),
                       compute=ComputeSection(**compute),
                       cluster=ClusterSection(backend=s["cluster_backend"]),
                       telemetry=TelemetrySection(**telemetry), seed=seed,
                       **sections)
    try:
        system = DynamicGraphSystem(graph, cfg)
    except ValueError as e:
        raise BenchError(f"the session cannot be built: {e}") from e
    need = getattr(system.backend, "required_devices", None)
    if need is not None:
        try:
            wanted = need(s["k"])
        except (RuntimeError, ValueError) as e:
            raise BenchError(f"{s['cluster_backend']} backend: {e}") from e
        if wanted > len(devices):
            raise BenchError(f"{s['cluster_backend']} backend needs {wanted} "
                             f"chips, the cell has {len(devices)}")
    return system
