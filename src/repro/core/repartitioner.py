"""Run-to-convergence drivers for the adaptive heuristic (paper §3, Fig. 2/6).

The paper's convergence criterion: zero migrations for 30 consecutive
iterations. ``run_to_convergence`` is a host loop around the jit'd
``migrate_step`` so we can record per-iteration history (cut ratio,
migrations) exactly like the paper's figures; ``adapt_rounds`` runs a fixed
number of iterations (continuous mode) and reads its history back once, at
the end; ``converge_jit`` is a pure
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

from repro.graph.structure import Graph, cut_edges
from repro.core.partition_state import PartitionState, make_state, occupancy
from repro.core.migration import MigrationStats, migrate_step, flush_pending
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

    def add_rounds(self, rows: np.ndarray, edges: int, k: int) -> None:
        """Append rounds read by ``read_rounds``. The ratios are formed here
        alone, from the integers, in float32 as ``cut_ratio`` and
        ``imbalance`` form them on the device."""
        f32 = np.float32
        cut = rows[:, _CUT].astype(f32) / f32(max(edges, 1))
        mean = np.maximum(rows[:, _OCC_SUM].astype(f32) / f32(k), f32(1))
        self.cut_ratio.extend(cut.tolist())
        self.migrations.extend(rows[:, _COMMITTED].tolist())
        self.willing.extend(rows[:, _WILLING].tolist())
        self.imbalance.extend(
            (rows[:, _OCC_MAX].astype(f32) / mean).tolist())


# the columns of a ``round_row``, in ``MigrationStats``'s field order
_COMMITTED, _WILLING, _ADMITTED, _CUT, _OCC_MAX, _OCC_SUM = range(6)

# rounds ``adapt_rounds`` dispatches ahead of the device: enough to keep it
# fed, few enough that the queued rounds' states stay a bounded set
_AHEAD = 8


@jax.jit
def round_row(graph: Graph, state: PartitionState,
              stats: MigrationStats) -> jax.Array:
    """One round's integers as a (6,) int32 row, on the device and without
    a read: committed, willing, admitted, cut edges, occupancy max and sum.
    A step that does not report its quality (its fields are None) gets it
    from ``state`` here."""
    if stats.cut_edges is None:
        occ = occupancy(state, graph.node_mask)
        stats = stats._replace(cut_edges=cut_edges(graph, state.assignment),
                               occupancy_max=jnp.max(occ),
                               occupancy_sum=jnp.sum(occ))
    return jnp.stack([jnp.asarray(v, jnp.int32) for v in stats])


def read_rounds(graph: Graph, rows: List[jax.Array],
                tracer: Any = NULL_TRACER) -> Tuple[np.ndarray, int]:
    """The rounds' rows (``round_row``) and the graph's live edges in one
    blocking read, inside an ``adapt.history`` span of ``tracer`` and
    counted by it (site ``history``): an (R, 6) int array, and E."""
    with tracer.span("adapt.history"):
        rows, edges = tracer.host_read(
            jax.device_get, (rows, graph.num_edges), "history")
    return np.stack(rows), int(edges)


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

    Each iteration reads its round back to the host once
    (``read_rounds``): the stopping rule needs its numbers.
    """
    if step_fn is None:
        step_fn = lambda st: migrate_step(st, graph, plan, s=s,
                                          use_chunked_counts=chunked_counts,
                                          tie_break=tie_break, backend=backend)
    hist = History.empty()
    quiet = 0
    best_cut = float("inf")
    stale = 0
    for _ in range(max_iters):
        state, stats = step_fn(state)
        rows, edges = read_rounds(graph, [round_row(graph, state, stats)],
                                  tracer)
        hist.add_rounds(rows, edges, state.k)
        moved, pending = hist.migrations[-1], int(rows[0, _ADMITTED])
        cut = hist.cut_ratio[-1]
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
    return state, (hist if record_history else History.empty())


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
    No round reads: with ``record_history`` each keeps its ``round_row``
    on the device and the loop reads them all back once at the end
    (``read_rounds``); without it the loop reads nothing. Once it has
    dispatched round t the host waits for round t − ``_AHEAD`` to finish,
    so the rounds queued on the device, and the states they hold, stay a
    bounded set whatever ``iters`` is.
    """
    if step_fn is None:
        step_fn = lambda st: migrate_step(st, graph, plan, s=s,
                                          use_chunked_counts=chunked_counts,
                                          tie_break=tie_break, backend=backend)
    hist = History.empty()
    rows, queued = [], []
    for _ in range(iters):
        state, stats = step_fn(state)
        queued.append(stats.committed)
        if len(queued) > _AHEAD:
            jax.block_until_ready(queued.pop(0))
        if record_history:
            rows.append(round_row(graph, state, stats))
    if rows:
        hist.add_rounds(*read_rounds(graph, rows, tracer), state.k)
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
        return state, {k: int(v) for k, v in stats._asdict().items()
                       if v is not None}

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
