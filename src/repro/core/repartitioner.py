"""Run-to-convergence drivers for the adaptive heuristic (paper §3, Fig. 2/6).

The paper's convergence criterion: zero migrations for 30 consecutive
iterations. ``run_to_convergence`` is a host loop around the jit'd
``migrate_step`` so we can record per-iteration history (cut ratio,
migrations) exactly like the paper's figures; ``adapt_rounds`` runs a fixed
number of iterations (continuous mode); ``converge_jit`` is a pure
``lax.while_loop`` variant for embedding the adaptation inside larger jit
programs (the distributed engine uses it).

These module-level functions are the implementation behind the
``XdgpAdaptive`` strategy in ``repro.api``. ``AdaptivePartitioner`` remains
as a deprecated shim over them for seed-era callers.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.structure import Graph, cut_ratio
from repro.core.partition_state import PartitionState, make_state, imbalance
from repro.core.migration import migrate_step, flush_pending
from repro.obs.trace import NULL_TRACER


@dataclasses.dataclass
class AdaptiveConfig:
    k: int = 9                    # paper's microbenchmarks use 9 partitions
    s: float = 0.5                # paper's recommended damping (§3.4)
    slack: float = 0.1            # capacity head-room over perfect balance
    patience: int = 30            # paper: converged after 30 quiet iterations
    max_iters: int = 500
    seed: int = 0
    chunked_counts: bool = False  # memory-light scoring for very large graphs
    tie_break: str = "random"     # "stay" = paper's literal rule; "random" = Spinner-style
    rel_tol: float = 1e-3         # cut-ratio plateau tolerance (random tie-break mode)


@dataclasses.dataclass
class History:
    cut_ratio: List[float]
    migrations: List[int]
    willing: List[int]
    imbalance: List[float]

    def as_dict(self) -> Dict[str, list]:
        return dataclasses.asdict(self)

    @property
    def total_migrations(self) -> int:
        return int(np.sum(self.migrations))

    @property
    def iterations(self) -> int:
        return len(self.migrations)

    @staticmethod
    def empty() -> "History":
        return History([], [], [], [])


def run_to_convergence(graph: Graph, state: PartitionState, *, s: float = 0.5,
                       patience: int = 30, max_iters: int = 500,
                       tie_break: str = "random", rel_tol: float = 1e-3,
                       chunked_counts: bool = False,
                       record_history: bool = True,
                       backend: str = "ref", plan=None,
                       step_fn=None, tracer: Any = NULL_TRACER,
                       ) -> Tuple[PartitionState, History]:
    """Iterate until converged.

    Convergence: tie_break="stay" → zero migrations for ``patience``
    consecutive iterations (the paper's criterion). tie_break="random" →
    tied boundaries keep fluctuating forever, so we additionally stop when
    the cut ratio has not improved by ``rel_tol`` over a ``patience``
    iteration window.

    ``backend``/``plan`` select the scoring implementation per iteration
    (see ``migrate_step``); the graph is fixed for the whole loop, so one
    pre-packed ``plan`` amortises over every iteration. ``step_fn``
    overrides the whole iteration — ``state -> (state, MigrationStats)`` —
    which is how the sharded execution backend reuses this control flow
    (same stopping rule, same history) over the cluster engine.

    Each iteration's reads of its results back to the host (three, five
    with ``record_history``) run inside an ``adapt.history`` span of
    ``tracer`` and are counted by it (site ``history``).
    """
    if step_fn is None:
        step_fn = lambda st: migrate_step(st, graph, plan, s=s,
                                          use_chunked_counts=chunked_counts,
                                          tie_break=tie_break, backend=backend)
    hist = History.empty()
    quiet = 0
    best_cut = float("inf")
    stale = 0
    read = tracer.host_read
    for _ in range(max_iters):
        state, stats = step_fn(state)
        with tracer.span("adapt.history"):
            moved = read(int, stats.committed, "history")
            pending = read(int, stats.admitted, "history")
            cut = read(float, cut_ratio(graph, state.assignment), "history")
            if record_history:
                hist.cut_ratio.append(cut)
                hist.migrations.append(moved)
                hist.willing.append(read(int, stats.willing, "history"))
                hist.imbalance.append(read(
                    float, imbalance(state, graph.node_mask), "history"))
        quiet = quiet + 1 if (moved == 0 and pending == 0) else 0
        if cut < best_cut * (1.0 - rel_tol):
            best_cut = cut
            stale = 0
        else:
            stale += 1
        if quiet >= patience:
            break
        if tie_break == "random" and stale >= patience:
            break
    state = flush_pending(state, graph)
    return state, hist


def adapt_rounds(graph: Graph, state: PartitionState, iters: int, *,
                 s: float = 0.5, tie_break: str = "random",
                 chunked_counts: bool = False,
                 record_history: bool = True,
                 backend: str = "ref", plan=None,
                 step_fn=None, tracer: Any = NULL_TRACER,
                 ) -> Tuple[PartitionState, History]:
    """Run a fixed number of adaptation iterations (continuous mode).

    Pending moves stay deferred at return (paper §4.2) — the next call's
    first iteration commits them, exactly like the interleaved stream mode.
    ``step_fn`` overrides the iteration like in ``run_to_convergence``.
    With ``record_history`` each iteration reads four results back to the
    host, inside an ``adapt.history`` span of ``tracer`` and counted by it
    (site ``history``); without it the loop reads nothing.
    """
    if step_fn is None:
        step_fn = lambda st: migrate_step(st, graph, plan, s=s,
                                          use_chunked_counts=chunked_counts,
                                          tie_break=tie_break, backend=backend)
    hist = History.empty()
    read = tracer.host_read
    for _ in range(iters):
        state, stats = step_fn(state)
        if record_history:
            with tracer.span("adapt.history"):
                hist.cut_ratio.append(read(
                    float, cut_ratio(graph, state.assignment), "history"))
                hist.migrations.append(read(int, stats.committed, "history"))
                hist.willing.append(read(int, stats.willing, "history"))
                hist.imbalance.append(read(
                    float, imbalance(state, graph.node_mask), "history"))
    return state, hist


class AdaptivePartitioner:
    """Deprecated seed-era driver; use ``repro.api.DynamicGraphSystem`` (or
    the ``XdgpAdaptive`` strategy / the module-level driver functions)."""

    def __init__(self, config: AdaptiveConfig):
        warnings.warn(
            "AdaptivePartitioner is deprecated; use "
            "repro.api.DynamicGraphSystem (converge()/adapt()) with the "
            "'xdgp' PartitionStrategy, or the module-level "
            "run_to_convergence/adapt_rounds drivers",
            DeprecationWarning, stacklevel=2)
        self.config = config

    def init_state(self, graph: Graph, assignment: jax.Array,
                   capacity: Optional[jax.Array] = None) -> PartitionState:
        return make_state(graph, assignment, self.config.k,
                          slack=self.config.slack, seed=self.config.seed,
                          capacity=capacity)

    def step(self, state: PartitionState, graph: Graph) -> Tuple[PartitionState, dict]:
        state, stats = migrate_step(state, graph, s=self.config.s,
                                    use_chunked_counts=self.config.chunked_counts,
                                    tie_break=self.config.tie_break)
        return state, {k: int(v) for k, v in stats._asdict().items()}

    def run_to_convergence(self, graph: Graph, state: PartitionState,
                           record_history: bool = True,
                           ) -> Tuple[PartitionState, History]:
        cfg = self.config
        return run_to_convergence(
            graph, state, s=cfg.s, patience=cfg.patience,
            max_iters=cfg.max_iters, tie_break=cfg.tie_break,
            rel_tol=cfg.rel_tol, chunked_counts=cfg.chunked_counts,
            record_history=record_history)

    def adapt(self, graph: Graph, state: PartitionState, iters: int,
              ) -> Tuple[PartitionState, History]:
        cfg = self.config
        return adapt_rounds(graph, state, iters, s=cfg.s,
                            tie_break=cfg.tie_break,
                            chunked_counts=cfg.chunked_counts)


def converge_jit(graph: Graph, state: PartitionState, *, s: float = 0.5,
                 patience: int = 30, max_iters: int = 500,
                 tie_break: str = "stay", backend: str = "ref",
                 plan=None) -> PartitionState:
    """Pure lax.while_loop convergence (no history) — embeddable inside jit.

    Used by the distributed engine and the dry-run lowering of the
    partitioner program. Uses the paper's zero-migration criterion, so the
    default tie_break here is the paper's "stay" rule.
    """

    def cond(carry):
        st, quiet, it = carry
        return (quiet < patience) & (it < max_iters)

    def body(carry):
        st, quiet, it = carry
        st, stats = migrate_step(st, graph, plan, s=s, tie_break=tie_break,
                                 backend=backend)
        moved = stats.committed + stats.admitted
        quiet = jnp.where(moved == 0, quiet + 1, 0)
        return st, quiet, it + 1

    state, _, _ = jax.lax.while_loop(
        cond, body, (state, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)))
    return flush_pending(state, graph)


def adapt_jit(graph: Graph, state: PartitionState, *, s: float = 0.5,
              iters: int = 30, tie_break: str = "random",
              backend: str = "ref", plan=None) -> PartitionState:
    """Fixed-iteration adaptation as a single jit program (lax.scan) — the
    fused superstep the streaming engine dispatches per batch."""

    def body(st, _):
        st, stats = migrate_step(st, graph, plan, s=s, tie_break=tie_break,
                                 backend=backend)
        return st, stats.committed

    state, _ = jax.lax.scan(body, state, None, length=iters)
    return flush_pending(state, graph)
