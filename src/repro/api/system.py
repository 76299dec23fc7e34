"""DynamicGraphSystem: the one front door to the xDGP runtime.

One session object owns the paper's full loop — ingest → place → adapt →
compute → measure — with the partitioning policy abstracted behind a
``PartitionStrategy`` (paper §4: one system; §3: the policy inside it):

    events ──► WindowIngestor (vectorized batch + expiry, backpressure)
                   │ GraphDelta
                   ▼
               apply_delta (static-shape scatter, jit)
                   │
                   ▼
               strategy.place (where do arrivals go?)
                   │
                   ▼
               strategy.adapt (interleaved migration rounds)
                   │
                   ▼
               VertexProgram superstep (optional, message traffic charged)
                   │
                   ▼
               QualityTracker (incremental cut / occupancy, drift-checked)

The session replaces the former ``StreamEngine`` (streaming),
``AdaptivePartitioner`` drivers (batch convergence) and the scenario
harness's hand-wired dual run (comparison):

  step(events, now)   one superstep → SuperstepRecord telemetry
  run(stream)         windowed replay of a whole (t, u, v) stream
  converge()          batch mode: adapt the current graph to quiescence
  adapt(iters)        batch mode: a fixed number of adaptation rounds
  inject(delta)       apply a pre-built GraphDelta (bursts, benchmarks)
  snapshot()          partition-quality + BSR-tiling view of *now*
  score()             cost-model scoring of the telemetry (paper §5.3)
  compare(stream)     dual run vs. a baseline strategy on the same stream

plus the cluster lifecycle (DESIGN.md §10):

  distribute()        execute on the "sharded" backend (partition-per-device)
  gather()            return to on-host execution
  rescale(new_k)      elastic k-change: re-home orphans, re-adapt, report
  save(path)          checkpoint the whole session (atomic, resumable)
  restore(path)       class method: resume a saved session mid-run

Swapping ``config.partition.strategy`` between ``"xdgp"`` and ``"static"``
reproduces the paper's adaptive-vs-static-hash comparison with no other
code changes; ``config.compute.backend`` independently selects the
migration-scoring implementation (fused kernels vs the unfused reference —
bit-identical results, DESIGN.md §9); ``config.cluster.backend`` selects
the execution layer (on-host vs shard_map SPMD — bit-identical again,
DESIGN.md §10).

Example — batch-adapt a static mesh to quiescence (doctested in CI):

    >>> from repro.api import DynamicGraphSystem, PartitionSection, SystemConfig
    >>> from repro.graph.generators import fem_grid2d
    >>> g = fem_grid2d(8)                                  # 64-vertex mesh
    >>> cfg = SystemConfig(partition=PartitionSection(strategy="xdgp", k=4))
    >>> system = DynamicGraphSystem(g, cfg)
    >>> before = system.cut_ratio                          # hash partitioning
    >>> hist = system.converge(record_history=False)
    >>> system.cut_ratio < before                          # paper §3: improved
    True
    >>> snap = system.snapshot()
    >>> snap["nodes"], snap["k"]
    (64, 4)

    Sessions checkpoint and resume as one operation (DESIGN.md §10):

    >>> import tempfile
    >>> with tempfile.TemporaryDirectory() as ckpt:
    ...     _ = system.save(ckpt)
    ...     resumed = DynamicGraphSystem.restore(ckpt)
    >>> resumed.cut_ratio == system.cut_ratio
    True
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.backend import resolve_execution_backend
from repro.api.config import SystemConfig
from repro.api.strategy import StrategyContext, resolve_strategy
from repro.checkpoint import Checkpointer
from repro.core.partition_state import PartitionState, default_capacity, make_state
from repro.core.repartitioner import History
from repro.core.vertex_program import (CostModel, VertexProgram, make_program,
                                       message_volume)
from repro.core.vertex_program import superstep as program_superstep
from repro.api.telemetry import SuperstepRecord
from repro.obs.metrics import MetricsRegistry, record_superstep
from repro.obs.trace import NULL_TRACER, Tracer
from repro.graph.bsr import bsr_density_stats, graph_to_bsr
from repro.graph.structure import Graph, GraphDelta, apply_delta, from_edges
from repro.graph.structure import cut_ratio as graph_cut_ratio
from repro.stream.ingest import (EdgeStreamBuffer, WindowIngestor,
                                 stream_batches)
from repro.stream.metrics import (QualityTracker, cut_ratio_of, delta_update,
                                  drift_check, imbalance_of, init_tracker,
                                  move_update)

StreamLike = Union[Tuple[np.ndarray, np.ndarray, np.ndarray], Any]


def empty_graph(n_cap: int, e_cap: int) -> Graph:
    """All-padding graph: a stream grows it from nothing."""
    return Graph(src=jnp.full((e_cap,), -1, jnp.int32),
                 dst=jnp.full((e_cap,), -1, jnp.int32),
                 node_mask=jnp.zeros((n_cap,), bool),
                 edge_mask=jnp.zeros((e_cap,), bool))


# ---------------------------------------------------------------------------
# Partition-quality snapshots (BSR tiling view)
# ---------------------------------------------------------------------------

def partition_relabelled(graph: Graph, assignment) -> Optional[Graph]:
    """Relabel live vertices grouped by partition (the relocation step that
    turns partition quality into BSR tile locality)."""
    nm = np.asarray(graph.node_mask)
    em = np.asarray(graph.edge_mask)
    lab = np.asarray(assignment)
    live = np.flatnonzero(nm)
    if live.size == 0 or not em.any():
        return None
    order = live[np.argsort(lab[live], kind="stable")]
    new_id = np.full(graph.n_cap, -1, np.int64)
    new_id[order] = np.arange(live.size)
    s = new_id[np.asarray(graph.src)[em]]
    d = new_id[np.asarray(graph.dst)[em]]
    return from_edges(s, d, live.size)


def bsr_snapshot(graph: Graph, assignment, blk: int = 32) -> Dict:
    """Tile stats of the partition-relabelled adjacency (kernel-cost proxy)."""
    relab = partition_relabelled(graph, assignment)
    if relab is None:      # no live vertices/edges: same shape as the
        return {"nnzb": 0, "diag_frac": 1.0, "mean_band": 0.0,  # empty branch
                "tiles_per_row": 0.0}                 # of bsr_density_stats
    return bsr_density_stats(graph_to_bsr(relab, blk=blk))


def _stream_arrays(stream: StreamLike) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Accept a (times, src, dst) tuple or any object with those attributes
    (a ``Scenario`` drops straight in)."""
    if isinstance(stream, (tuple, list)) and len(stream) == 3:
        t, u, v = stream
    else:
        t, u, v = stream.times, stream.src, stream.dst
    return np.asarray(t), np.asarray(u), np.asarray(v)


class DynamicGraphSystem:
    """One dynamic-graph processing session (graph + strategy + telemetry)."""

    def __init__(self, graph: Optional[Graph] = None,
                 config: Optional[SystemConfig] = None, *,
                 assignment: Optional[jax.Array] = None,
                 strategy: Any = None,
                 program: Optional[VertexProgram] = None):
        """Args:
          graph:      initial padded graph; None builds an empty one from
                      ``config.graph`` (n_cap/e_cap must be set).
          config:     the layered ``SystemConfig`` (defaults throughout).
          assignment: explicit initial labels; None asks the strategy.
          strategy:   overrides ``config.partition.strategy`` with a name,
                      class or instance (for variants a string can't express,
                      e.g. ``XdgpAdaptive(placement="inherit")``).
          program:    overrides ``config.compute.program`` with a constructed
                      ``VertexProgram``.
        """
        self.config = cfg = config if config is not None else SystemConfig()
        if graph is None:
            if cfg.graph.generator is not None:
                # scale tier (DESIGN.md §14): build the starting graph from
                # a streaming generator, chunked, seeded from the session
                from repro.scale import session_graph
                graph = session_graph(cfg.graph, seed=cfg.seed)
            elif cfg.graph.n_cap <= 0 or cfg.graph.e_cap <= 0:
                raise ValueError("pass an initial graph, set config.graph "
                                 "n_cap/e_cap so the session can build an "
                                 "empty one, or name a config.graph "
                                 "generator to synthesise one")
            else:
                graph = empty_graph(cfg.graph.n_cap, cfg.graph.e_cap)
        p = cfg.partition
        self.strategy = resolve_strategy(strategy if strategy is not None
                                         else p.strategy)
        self.backend = resolve_execution_backend(cfg.cluster.backend,
                                                 cluster=cfg.cluster)
        # observability (DESIGN.md §11): disabled sessions hold the shared
        # NULL_TRACER, whose hooks are constant-time no-ops — the superstep
        # pays no clock reads, fences or allocation unless telemetry.trace
        # turned tracing on
        if cfg.telemetry.trace:
            self.tracer: Any = Tracer(meta={
                "label": f"{self.strategy.name}/{cfg.cluster.backend}",
                "strategy": self.strategy.name,
                "backend": cfg.cluster.backend, "k": cfg.partition.k})
        else:
            self.tracer = NULL_TRACER
        self.backend.tracer = self.tracer
        self.backend.comm_probe = cfg.telemetry.trace_comm_probe
        self.metrics: Optional[MetricsRegistry] = (
            MetricsRegistry() if cfg.telemetry.metrics else None)
        # remembered so compare() can replay identical fresh sessions
        self._initial_graph = graph
        self._initial_assignment = assignment
        self._program_arg = program
        # a constructor-override strategy/program cannot be rebuilt from the
        # config alone — save() records the fact so restore() can insist on
        # being handed the same objects back
        self._strategy_override = strategy is not None
        self._program_override = program is not None

        self.graph = graph
        if assignment is None:
            assignment = self.strategy.init(graph, p.k)
        # capacity is provisioned for the slot space, not the current live
        # set: a stream can legally grow the graph to n_cap vertices.
        capacity = default_capacity(graph.n_cap, p.k, p.slack)
        self.state: PartitionState = make_state(
            graph, assignment, p.k, slack=p.slack, seed=cfg.seed,
            capacity=capacity)
        self.ingestor = WindowIngestor(
            n_cap=graph.n_cap, window=cfg.stream.window,
            a_cap=cfg.stream.a_cap, d_cap=cfg.stream.d_cap,
            dedupe=cfg.stream.dedupe,
            carry_backlog=cfg.stream.carry_backlog)
        if cfg.stream.dedupe:
            em = np.asarray(graph.edge_mask)
            if em.any():
                self.ingestor.seed_live_edges(np.asarray(graph.src)[em],
                                              np.asarray(graph.dst)[em])
        self.tracker: QualityTracker = init_tracker(graph, self.state.assignment,
                                                    p.k)
        self.telemetry: List[SuperstepRecord] = []
        self._superstep = 0
        self._now = 0
        self._run_seconds = 0.0
        self._place_key = jax.random.PRNGKey(cfg.seed ^ 0x5EED)

        # optional interleaved vertex program (think-like-a-vertex compute)
        if program is None and cfg.compute.program is not None:
            program = make_program(cfg.compute.program)
        self.program = program
        self.program_state: Optional[jax.Array] = None
        if program is not None:
            self.program_state = program.init(graph)

            def _prog_step(before_mask, g, st, step):
                # vertices born this superstep enter with their init state
                born = g.node_mask & ~before_mask
                st = jnp.where(born[:, None], program.init(g), st)
                return program_superstep(program, g, st, step)

            self._prog_step = jax.jit(_prog_step)
            self._msg_volume = jax.jit(
                lambda g, lab: message_volume(g, lab, program.state_dim))

    # -- context assembly ---------------------------------------------------
    @property
    def labels(self) -> jax.Array:
        """Current per-slot partition assignment."""
        return self.state.assignment

    @property
    def cut_ratio(self) -> float:
        """Current cut ratio (incrementally tracked — O(1) read)."""
        return float(cut_ratio_of(self.tracker))

    @property
    def imbalance(self) -> float:
        """Current max/mean occupancy (incrementally tracked — O(1) read)."""
        return float(imbalance_of(self.tracker))

    @property
    def scoring_plan(self) -> Optional[Dict]:
        """Kind and packed shape of the plan the last batch ``adapt()`` /
        ``converge()`` scored over (``"bsr"`` tiles when they fit the
        device-memory budget, else ``"flat"``); None for the unfused
        reference path, before the first batch call, or for a strategy
        that only meets the ``PartitionStrategy`` protocol (which does not
        declare ``last_plan``; cf. ``cluster_native`` in ``api/backend``)."""
        return getattr(self.strategy, "last_plan", None)

    @property
    def backlog(self) -> Tuple[int, int]:
        """Deferred ingest work: (queued adds, queued dels) still sitting in
        the stream buffer past a_cap/d_cap — the capacity-backpressure signal
        the serving layer folds into per-tenant pressure (DESIGN.md §12)."""
        return self.ingestor.buffer.backlog

    @property
    def pressure(self) -> float:
        """Stream-buffer backlog relative to one superstep's drain capacity
        (≥ 1.0 means ingest is deferring work)."""
        return self.ingestor.buffer.pressure

    def _ctx(self, **runtime: Any) -> StrategyContext:
        p = self.config.partition
        return StrategyContext(
            k=p.k, s=p.s, adapt_iters=p.adapt_iters, tie_break=p.tie_break,
            placement_passes=p.placement_passes, patience=p.patience,
            max_iters=p.max_iters, rel_tol=p.rel_tol,
            backend=self.config.compute.backend, **runtime)

    def _place(self, delta: GraphDelta, before: Graph, after: Graph,
               ) -> Tuple[jax.Array, int]:
        """Route a delta's arrivals through the strategy's place hook."""
        labels_before = self.state.assignment
        self._place_key, sub = jax.random.split(self._place_key)
        ctx = self._ctx(node_mask=before.node_mask, assignment=labels_before,
                        occupancy=self.tracker.occupancy,
                        capacity=self.state.capacity, rng=sub)
        labels = self.strategy.place(delta, ctx)
        if ctx.placed is not None:
            placed = ctx.placed
        else:
            placed = int(jnp.sum(~before.node_mask & after.node_mask))
        return labels, placed

    # -- one superstep ------------------------------------------------------
    def step(self, events: np.ndarray, now: Optional[int] = None) -> SuperstepRecord:
        """Ingest one event batch, place arrivals, adapt, compute, measure."""
        cfg = self.config
        if now is None:
            ev = np.asarray(events)
            now = int(ev[:, 0].max()) if ev.size else self._now
        t_start = time.perf_counter()
        tr = self.tracer
        sp_step = tr.span("superstep", superstep=self._superstep + 1)
        sp_step.__enter__()

        # 1. INGEST: vectorized batch → one padded GraphDelta
        with tr.span("ingest"):
            delta, istats = self.ingestor.ingest(events, now)
        t_ingest = time.perf_counter() - t_start

        # 2. APPLY + PLACE: grow/shrink the graph, route arrivals through the
        # strategy. A provably empty delta skips the device pipeline entirely
        # (quiet stream gaps would otherwise pay full-graph scatters for
        # no-ops).
        before = self.graph
        labels_before = self.state.assignment
        if istats.adds_out == 0 and istats.dels_out == 0:
            after = before
            labels_placed = labels_before
            new_placed = 0
        else:
            with tr.span("place", adds=istats.adds_out,
                         dels=istats.dels_out) as sp:
                after = apply_delta(before, delta)
                labels_placed, new_placed = self._place(delta, before, after)

                # 3. MEASURE the ingest: incremental cut/occupancy from
                # diffs only
                self.tracker, _ = delta_update(self.tracker, before, after,
                                               labels_before, labels_placed)
                sp.fence(labels_placed, self.tracker.cut)

        # 4. ADAPT: the strategy's interleaved rounds on the new graph,
        # executed wherever the session's backend runs (local / sharded)
        state = dataclasses.replace(self.state, assignment=labels_placed)
        with tr.span("migrate") as sp:
            state = self.backend.adapt(self.strategy, after, state,
                                       self._ctx())
            self.tracker, moved = move_update(self.tracker, after,
                                              labels_placed, state.assignment)
            sp.fence(state.assignment, self.tracker.cut)
        comm = self.backend.pop_superstep_comm()

        self.graph = after
        self.state = state
        self._superstep += 1
        self._now = int(now)

        # dedupe mode models the live edge set exactly, which makes e_cap
        # exhaustion detectable: apply_delta drops additions silently once
        # free slots run out, and the mirror would drift forever after
        if cfg.stream.dedupe and \
                self.ingestor.live_edge_count != int(self.tracker.edges):
            raise RuntimeError(
                f"edge capacity exhausted at superstep {self._superstep}: "
                f"graph holds {int(self.tracker.edges)} live edges but "
                f"{self.ingestor.live_edge_count} were released "
                f"(e_cap={after.e_cap}); increase e_cap or lower a_cap")

        # 5. COMPUTE: one BSP superstep of the vertex program on the adapted
        # graph; its message traffic under the current assignment is the
        # paper's execution-time driver (§5.3: remote messages dominate).
        local_bytes = remote_bytes = 0
        compute_seconds = 0.0
        if self.program is not None:
            with tr.span("compute"):
                t_c = time.perf_counter()
                self.program_state = self._prog_step(
                    before.node_mask, after, self.program_state,
                    jnp.asarray(self._superstep, jnp.int32))
                self.program_state.block_until_ready()
                compute_seconds = time.perf_counter() - t_c
                lb, rb = self._msg_volume(after, state.assignment)
                local_bytes, remote_bytes = int(lb), int(rb)

        # 6. DRIFT CHECK: periodic full recompute validates the tracker
        drift = None
        every = cfg.telemetry.recompute_every
        with tr.span("commit"):
            if every and self._superstep % every == 0:
                self.tracker, drift = drift_check(self.tracker, after,
                                                  state.assignment)

        record = SuperstepRecord(
            superstep=self._superstep, now=int(now),
            events=int(np.asarray(events).shape[0]) if np.asarray(events).size else 0,
            adds=istats.adds_out, dels=istats.dels_out,
            backlog_adds=istats.adds_backlog, backlog_dels=istats.dels_backlog,
            invalid_events=istats.invalid, stale_dropped=istats.stale_dropped,
            new_placed=new_placed, migrations=int(moved),
            cut_edges=int(self.tracker.cut), live_edges=int(self.tracker.edges),
            cut_ratio=float(cut_ratio_of(self.tracker)),
            imbalance=float(imbalance_of(self.tracker)),
            ingest_seconds=t_ingest,
            step_seconds=time.perf_counter() - t_start,
            drift=drift,
            dup_dropped=istats.dup_dropped,
            local_bytes=local_bytes, remote_bytes=remote_bytes,
            compute_seconds=compute_seconds,
            halo_bytes=comm["halo_bytes"],
            halo_live_bytes=comm.get("halo_live_bytes", 0),
            collective_bytes=comm["collective_bytes"],
        )
        self.telemetry.append(record)
        sp_step.set(migrations=int(moved), cut_ratio=record.cut_ratio)
        sp_step.__exit__(None, None, None)
        tr.counter("migrations", record.migrations)
        if self.metrics is not None:
            record_superstep(self.metrics, record,
                             backend=self.backend.name)
        return record

    # -- windowed replay of a whole stream ----------------------------------
    def run(self, stream: StreamLike, *, batch_span: Optional[int] = None,
            max_supersteps: Optional[int] = None) -> List[SuperstepRecord]:
        """Replay a (t, u, v) stream window-by-window through the session.

        ``stream`` is a 3-tuple of arrays or any object with ``times`` /
        ``src`` / ``dst`` attributes (a ``Scenario`` drops straight in, its
        ``batch_span`` honoured unless overridden).
        """
        times, src, dst = _stream_arrays(stream)
        if batch_span is None:
            batch_span = getattr(stream, "batch_span", None)
        if batch_span is None:
            batch_span = self.config.stream.batch_span
        t0 = time.perf_counter()
        out: List[SuperstepRecord] = []
        for now, events in stream_batches(times, src, dst, batch_span):
            out.append(self.step(events, now))
            if max_supersteps is not None and len(out) >= max_supersteps:
                break
        self._run_seconds += time.perf_counter() - t0
        return out

    def drain(self, now: Optional[int] = None, max_supersteps: int = 64,
              ) -> List[SuperstepRecord]:
        """Flush capacity-deferred changes with empty-input supersteps."""
        now = self._now if now is None else now
        out: List[SuperstepRecord] = []
        empty = np.empty((0, 3), np.int64)
        while len(self.ingestor.buffer) and len(out) < max_supersteps:
            out.append(self.step(empty, now))
        return out

    # -- batch adaptation (the former AdaptivePartitioner drivers) -----------
    def converge(self, *, record_history: bool = True) -> History:
        """Adapt the current graph to quiescence (paper's convergence rule)."""
        old = self.state.assignment
        state, hist = self.backend.converge(
            self.strategy, self.graph, self.state,
            self._ctx(record_history=record_history))
        self.tracker, _ = move_update(self.tracker, self.graph, old,
                                      state.assignment)
        self.state = state
        self.backend.pop_superstep_comm()   # batch comm lands in the totals
        return hist

    def adapt(self, iters: int, *, record_history: bool = True) -> History:
        """A fixed number of adaptation rounds on the current graph."""
        old = self.state.assignment
        state, hist = self.backend.adapt_rounds(
            self.strategy, self.graph, self.state, iters,
            self._ctx(record_history=record_history))
        self.tracker, _ = move_update(self.tracker, self.graph, old,
                                      state.assignment)
        self.state = state
        self.backend.pop_superstep_comm()   # batch comm lands in the totals
        return hist

    def inject(self, delta: GraphDelta) -> int:
        """Apply a pre-built ``GraphDelta`` (growth burst, benchmark event)
        through the place/measure path, bypassing the event-stream ingestor.
        Returns the number of vertices placed. Not compatible with
        ``stream.dedupe`` sessions (the live-edge mirror only sees the
        ingest path)."""
        if self.config.stream.dedupe:
            raise RuntimeError("inject() bypasses the ingest path and would "
                               "desync the dedupe live-edge mirror; ingest "
                               "events via step() instead")
        before = self.graph
        after = apply_delta(before, delta)
        labels_before = self.state.assignment
        labels, placed = self._place(delta, before, after)
        self.tracker, _ = delta_update(self.tracker, before, after,
                                       labels_before, labels)
        self.graph = after
        self.state = dataclasses.replace(self.state, assignment=labels)
        return placed

    # -- cluster lifecycle (DESIGN.md §10) -----------------------------------
    def _swap_backend(self, backend_name: str, **cluster_changes: Any) -> None:
        """Atomically move to another backend: resolve and validate the
        candidate first, commit config + backend only if that succeeds."""
        cfg = self.config.with_cluster(backend=backend_name,
                                       **cluster_changes)
        if self.backend.name == backend_name:
            # same backend class: keep the instance (and its cumulative
            # comm totals), just refresh its knobs and drop stale caches
            self.backend.cluster = cfg.cluster
            self.backend.invalidate()
        else:
            self.backend = resolve_execution_backend(backend_name,
                                                     cluster=cfg.cluster)
            self.backend.tracer = self.tracer
            self.backend.comm_probe = cfg.telemetry.trace_comm_probe
        self.config = cfg

    def distribute(self, *, devices: Optional[int] = None,
                   ) -> "DynamicGraphSystem":
        """Move the session onto the sharded backend (partition-per-device
        SPMD via the cluster engine). Validates device availability eagerly
        so a missing ``XLA_FLAGS`` fails here — with the session left
        untouched on its current backend — not at the next superstep.
        The adaptation trajectory is unchanged — the sharded engine is
        decision-identical to the local one (DESIGN.md §10)."""
        changes = {} if devices is None else {"devices": int(devices)}
        cfg = self.config.with_cluster(backend="sharded", **changes)
        candidate = resolve_execution_backend("sharded", cluster=cfg.cluster)
        candidate.required_devices(self.config.partition.k)   # may raise
        if self.backend.name == "sharded":
            # already sharded: keep the instance (cumulative comm totals),
            # refresh its knobs and drop caches built for the old config
            self.backend.cluster = cfg.cluster
            self.backend.invalidate()
        else:
            self.backend = candidate          # the validated instance
            self.backend.tracer = self.tracer
            self.backend.comm_probe = cfg.telemetry.trace_comm_probe
        self.config = cfg
        return self

    def gather(self) -> "DynamicGraphSystem":
        """Return the session to on-host execution. The session's canonical
        arrays never left slot order, so this is a pure backend swap."""
        self._swap_backend("local")
        return self

    def rescale(self, new_k: int, *, lost: Optional[Tuple[int, ...]] = None,
                adapt_iters: int = 60) -> Dict:
        """Elastic k-change: workers joined (``new_k > k``) or died.

        Orphaned vertices are re-homed by hash (``runtime.elastic``), the
        session re-provisions capacity for the new partition count, and the
        strategy re-adapts on the session's own backend — the paper's §4.3
        recovery story promoted to one session operation. Returns the
        ``elastic_rescale`` report (cut before/after, migrations)."""
        from repro.runtime.elastic import rescale_assignment

        old_k = self.config.partition.k
        # validate the post-rescale cluster BEFORE mutating anything: a
        # sharded session needs one device per new partition, and failing
        # mid-rescale would leave the session half-rewritten and unusable
        cl = self.config.cluster
        if cl.devices not in (0, int(new_k)):
            cl = dataclasses.replace(cl, devices=0)
        probe = resolve_execution_backend(cl.backend, cluster=cl)
        if hasattr(probe, "required_devices"):
            probe.required_devices(int(new_k))                # may raise
        a0 = rescale_assignment(self.labels, old_k, int(new_k), lost)
        cut_rehash = float(graph_cut_ratio(self.graph, a0))
        p = dataclasses.replace(self.config.partition, k=int(new_k))
        self.config = dataclasses.replace(self.config, partition=p)
        if self.config.cluster.devices not in (0, int(new_k)):
            # a pinned device count cannot survive a k-change (k == P)
            self.config = self.config.with_cluster(devices=0)
        capacity = default_capacity(self.graph.n_cap, int(new_k), p.slack)
        self.state = make_state(self.graph, a0, int(new_k), slack=p.slack,
                                seed=self.config.seed, capacity=capacity)
        self.tracker = init_tracker(self.graph, self.state.assignment,
                                    int(new_k))
        # a k-change is a mesh change: drop the backend's bucketing/mesh
        # caches but keep the instance (cumulative comm totals survive)
        self.backend.cluster = self.config.cluster
        self.backend.invalidate()
        hist = self.adapt(adapt_iters)
        return {"old_k": old_k, "new_k": int(new_k),
                "cut_after_rehash": cut_rehash,
                "cut_after_adapt": self.cut_ratio,
                "migrations": hist.total_migrations}

    # -- checkpoint / restore -------------------------------------------------
    _CKPT_VERSION = 1

    def _session_arrays(self) -> Dict[str, Any]:
        """The array pytree the checkpointer persists (fixed key structure —
        the treedef must match between save and the restore template)."""
        ing = self.ingestor
        add_src, add_dst, add_t, dels = ing.buffer.peek_all()
        prog = (self.program_state if self.program_state is not None
                else jnp.zeros((0,), jnp.float32))
        return {
            "graph": {"src": self.graph.src, "dst": self.graph.dst,
                      "node_mask": self.graph.node_mask,
                      "edge_mask": self.graph.edge_mask},
            "state": {"assignment": self.state.assignment,
                      "pending": self.state.pending,
                      "capacity": self.state.capacity,
                      "rng": self.state.rng,
                      "iteration": self.state.iteration,
                      "last_moves": self.state.last_moves},
            "tracker": {"cut": self.tracker.cut, "edges": self.tracker.edges,
                        "occupancy": self.tracker.occupancy},
            "window": {"last_seen": ing.tracker.last_seen,
                       "live_lo": ing._live_lo, "live_hi": ing._live_hi,
                       "backlog_add_src": add_src, "backlog_add_dst": add_dst,
                       "backlog_add_t": add_t, "backlog_dels": dels},
            "place_key": self._place_key,
            "program_state": prog,
        }

    def save(self, path: str, *, step: Optional[int] = None) -> int:
        """Checkpoint the whole session — graph, partition state, tracker,
        window/backlog state, telemetry and config — atomically under
        ``path``. Returns the step id (defaults to the superstep counter).
        A sharded session checkpoints its canonical slot-order state, so it
        can be restored on any host and re-``distribute()``-d there."""
        step = self._superstep if step is None else int(step)
        extra = {
            "version": self._CKPT_VERSION,
            "config": self.config.to_dict(),
            "strategy": self.strategy.name,
            "strategy_override": self._strategy_override,
            "program_override": self._program_override,
            "has_program": self.program is not None,
            "superstep": self._superstep,
            "now": self._now,
            "run_seconds": self._run_seconds,
            "telemetry": [dataclasses.asdict(r) for r in self.telemetry],
        }
        ckpt = Checkpointer(path, use_async=False)
        ckpt.save(step, self._session_arrays(), extra=extra)
        return step

    @classmethod
    def restore(cls, path: str, *, step: Optional[int] = None,
                strategy: Any = None,
                program: Optional[VertexProgram] = None,
                ) -> "DynamicGraphSystem":
        """Resume a session saved with :meth:`save` — mid-run: partition
        state (including deferred moves and the RNG), incremental tracker,
        window liveness, ingest backlog and telemetry all pick up exactly
        where the checkpoint left them.

        A session built with constructor overrides (``strategy=`` /
        ``program=`` instances the config cannot express) must be handed
        the same overrides here — a checkpoint records only their names,
        and resuming with a different policy would silently diverge from
        the saved trajectory, so restore refuses instead."""
        ckpt = Checkpointer(path, use_async=False)
        extra = ckpt.read_extra(step)
        if extra is None or extra.get("version") != cls._CKPT_VERSION:
            raise ValueError(f"{path} is not a session checkpoint "
                             f"(missing/incompatible extra.json)")
        cfg = SystemConfig.from_dict(extra["config"])
        dummy = jnp.zeros((0,), jnp.float32)
        template = {
            "graph": {k: dummy for k in ("src", "dst", "node_mask",
                                         "edge_mask")},
            "state": {k: dummy for k in ("assignment", "pending", "capacity",
                                         "rng", "iteration", "last_moves")},
            "tracker": {k: dummy for k in ("cut", "edges", "occupancy")},
            "window": {k: dummy for k in ("last_seen", "live_lo", "live_hi",
                                          "backlog_add_src",
                                          "backlog_add_dst", "backlog_add_t",
                                          "backlog_dels")},
            "place_key": dummy,
            "program_state": dummy,
        }
        payload, _ = ckpt.restore(template, step)
        g = payload["graph"]
        graph = Graph(src=jnp.asarray(g["src"]), dst=jnp.asarray(g["dst"]),
                      node_mask=jnp.asarray(g["node_mask"]),
                      edge_mask=jnp.asarray(g["edge_mask"]))
        if extra.get("strategy_override") and strategy is None:
            raise ValueError(
                f"checkpoint was saved from a session built with an "
                f"explicit strategy override ({extra['strategy']!r}); the "
                f"config alone cannot rebuild it — pass the same strategy "
                f"via restore(..., strategy=...)")
        if extra.get("program_override") and program is None:
            raise ValueError(
                "checkpoint was saved from a session built with an explicit "
                "program override; the config alone cannot rebuild it — "
                "pass the same program via restore(..., program=...)")
        st = payload["state"]
        system = cls(graph, cfg, assignment=jnp.asarray(st["assignment"]),
                     strategy=strategy, program=program)
        if system.strategy.name != extra["strategy"]:
            raise ValueError(
                f"checkpoint was saved with strategy "
                f"{extra['strategy']!r} but the restored session resolves "
                f"to {system.strategy.name!r}; pass the original strategy "
                f"instance via restore(..., strategy=...)")
        if extra.get("has_program") and system.program is None:
            raise ValueError(
                "checkpoint carries a vertex-program state but the restored "
                "session has no program (it was passed as a constructor "
                "override); pass it via restore(..., program=...)")
        system.state = PartitionState(
            assignment=jnp.asarray(st["assignment"], jnp.int32),
            pending=jnp.asarray(st["pending"], jnp.int32),
            capacity=jnp.asarray(st["capacity"], jnp.int32),
            rng=jnp.asarray(st["rng"]),
            iteration=jnp.asarray(st["iteration"], jnp.int32),
            last_moves=jnp.asarray(st["last_moves"], jnp.int32))
        tr = payload["tracker"]
        system.tracker = QualityTracker(
            cut=jnp.asarray(tr["cut"], jnp.int32),
            edges=jnp.asarray(tr["edges"], jnp.int32),
            occupancy=jnp.asarray(tr["occupancy"], jnp.int32))
        w = payload["window"]
        ing = system.ingestor
        # host-side window state must be writable numpy, not device views
        ing.tracker.last_seen = np.array(w["last_seen"], np.int64)
        ing._live_lo = np.array(w["live_lo"], np.int64)
        ing._live_hi = np.array(w["live_hi"], np.int64)
        ing.buffer = EdgeStreamBuffer(ing.a_cap, ing.d_cap)
        if np.asarray(w["backlog_add_src"]).size:
            ing.buffer.push_edges(np.asarray(w["backlog_add_src"]),
                                  np.asarray(w["backlog_add_dst"]),
                                  np.asarray(w["backlog_add_t"]))
        if np.asarray(w["backlog_dels"]).size:
            ing.buffer.push_node_removals(np.asarray(w["backlog_dels"]))
        system._place_key = jnp.asarray(payload["place_key"])
        prog = np.asarray(payload["program_state"])
        if system.program is not None and prog.size:
            system.program_state = jnp.asarray(prog)
        system._superstep = int(extra["superstep"])
        system._now = int(extra["now"])
        system._run_seconds = float(extra["run_seconds"])
        system.telemetry = [SuperstepRecord(**r) for r in extra["telemetry"]]
        return system

    # -- measurement --------------------------------------------------------
    def snapshot(self, *, bsr_blk: Optional[int] = None) -> Dict:
        """Partition-quality + BSR-tiling view of the session right now."""
        blk = bsr_blk if bsr_blk is not None else self.config.telemetry.bsr_blk
        return {
            "strategy": self.strategy.name,
            "backend": self.backend.name,
            "cluster": self.backend.device_stats(),
            "scoring_plan": self.scoring_plan,
            "k": self.config.partition.k,
            "supersteps": self._superstep,
            "now": self._now,
            "nodes": int(jnp.sum(self.graph.node_mask)),
            "edges": int(self.tracker.edges),
            "cut_edges": int(self.tracker.cut),
            "cut_ratio": float(cut_ratio_of(self.tracker)),
            "imbalance": float(imbalance_of(self.tracker)),
            "occupancy": np.asarray(self.tracker.occupancy).tolist(),
            "capacity": np.asarray(self.state.capacity).tolist(),
            "bsr": bsr_snapshot(self.graph, self.state.assignment, blk=blk),
        }

    def cost_model(self) -> CostModel:
        c = self.config.compute
        return CostModel(c_cpu=c.c_cpu, c_net=c.c_net, c_mig=c.c_mig)

    def score(self, *, cost: Optional[CostModel] = None,
              bsr_blk: Optional[int] = None) -> Dict:
        """Cost-model scoring of the session's telemetry (paper §5.3):

          cost(step) = c_cpu · local_bytes + c_net · remote_bytes
                       + c_mig · migrations · unit_bytes

        so the strategy is charged for its own migration overhead, like the
        paper's end-to-end ">50% execution time reduction" claim."""
        recs = self.telemetry
        if not recs:
            raise RuntimeError("score() needs telemetry; run() or step() first")
        drifts = [r.drift for r in recs if r.drift is not None]
        if any(d != 0.0 for d in drifts):     # survives python -O, unlike assert
            raise RuntimeError(f"quality tracker drifted: {drifts}")
        cost = cost if cost is not None else self.cost_model()
        scale = self.config.compute.payload_scale
        state_dim = self.program.state_dim if self.program is not None else 0
        unit = state_dim * 4 * scale
        local = sum(r.local_bytes for r in recs) * scale
        remote = sum(r.remote_bytes for r in recs) * scale
        migrations = sum(r.migrations for r in recs)
        per_step = [cost.superstep_cost(r.local_bytes * scale,
                                        r.remote_bytes * scale,
                                        r.migrations, unit) for r in recs]
        total = float(np.sum(per_step))
        blk = bsr_blk if bsr_blk is not None else self.config.telemetry.bsr_blk
        return {
            "mode": self.strategy.name,
            "backend": self.backend.name,
            "supersteps": len(recs),
            "events": int(sum(r.events for r in recs)),
            "halo_bytes": int(sum(r.halo_bytes for r in recs)),
            "halo_live_bytes": int(sum(r.halo_live_bytes for r in recs)),
            "collective_bytes": int(sum(r.collective_bytes for r in recs)),
            "cut_final": float(recs[-1].cut_ratio),
            "cut_mean": float(np.mean([r.cut_ratio for r in recs])),
            "imbalance_final": float(recs[-1].imbalance),
            "migrations_total": int(migrations),
            "placed_total": int(sum(r.new_placed for r in recs)),
            "local_bytes": float(local),
            "remote_bytes": float(remote),
            "exec_cost_total": total,
            "exec_cost_per_superstep": total / max(len(recs), 1),
            "adaptation_cost": float(cost.c_mig * migrations * unit),
            "compute_seconds": float(sum(r.compute_seconds for r in recs)),
            "wall_seconds": float(self._run_seconds),
            "bsr": bsr_snapshot(self.graph, self.state.assignment, blk=blk),
            "cut_trajectory": [round(float(r.cut_ratio), 4) for r in recs],
        }

    # -- dual-run comparison (the former scenario harness) --------------------
    def fresh(self, *, strategy: Any = None, seed: Optional[int] = None,
              ) -> "DynamicGraphSystem":
        """A new session over the same initial graph/config — optionally with
        a different strategy or seed. The initial graph is immutable, so
        replays are exact."""
        cfg = self.config if seed is None else self.config.with_seed(seed)
        strat = self.strategy if strategy is None else resolve_strategy(strategy)
        if strategy is not None:
            cfg = cfg.with_strategy(strat.name)
        return DynamicGraphSystem(self._initial_graph, cfg,
                                  assignment=self._initial_assignment,
                                  strategy=strat,
                                  program=self._program_arg)

    def compare(self, stream: StreamLike, *, baseline: Any = "static",
                max_supersteps: Optional[int] = None,
                bsr_blk: Optional[int] = None,
                cost: Optional[CostModel] = None,
                seed: Optional[int] = None) -> Dict:
        """Run the same stream under this session's strategy and under
        ``baseline``, from identical fresh sessions, and compare the
        execution-cost proxy (the paper's adaptive-vs-static comparison).

        ``seed`` varies the sessions' own randomness (placement tie noise,
        migration damping) independently of the stream. Keys follow the
        historical harness layout: the candidate row is ``"adaptive"``, the
        baseline row ``"static"``, whatever the strategies actually are.
        """
        if self.program is None:
            # without a vertex program every superstep records zero message
            # bytes, both totals are 0 and the "reduction" would read 100%
            raise RuntimeError(
                "compare() needs a vertex program to measure execution cost; "
                "set config.compute.program (e.g. 'pagerank') or pass "
                "program= to the session")
        rows: Dict[str, Dict] = {}
        for key, strat in (("adaptive", None), ("static", baseline)):
            system = self.fresh(strategy=strat, seed=seed)
            system.run(stream, max_supersteps=max_supersteps)
            rows[key] = system.score(cost=cost, bsr_blk=bsr_blk)
        adaptive, static = rows["adaptive"], rows["static"]
        s_cost = max(static["exec_cost_total"], 1e-12)
        reduction = 1.0 - adaptive["exec_cost_total"] / s_cost
        s_tiles = max(static["bsr"]["nnzb"], 1)
        times, _, _ = _stream_arrays(stream)
        return {
            "scenario": getattr(stream, "name", None),
            "program": getattr(stream, "program",
                               self.config.compute.program),
            "k": self.config.partition.k,
            "events": int(getattr(stream, "n_events", times.shape[0])),
            "notes": getattr(stream, "notes", ""),
            "adaptive": adaptive,
            "static": static,
            "exec_cost_reduction_pct":
                round(100 * reduction, 1),
            "remote_reduction_pct":
                round(100 * (1 - adaptive["remote_bytes"]
                             / max(static["remote_bytes"], 1e-12)), 1),
            "cut_improvement":
                round(1 - adaptive["cut_final"]
                      / max(static["cut_final"], 1e-12), 3),
            "bsr_tile_reduction_pct":
                round(100 * (1 - adaptive["bsr"]["nnzb"] / s_tiles), 1),
            "meets_50pct_claim": bool(reduction > 0.5),
        }
