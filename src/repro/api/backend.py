"""Pluggable execution backends: where does a session's adaptation run?

The ``PartitionStrategy`` decides *what* the heuristic does; the
``ExecutionBackend`` decides *where* it executes (DESIGN.md §10):

  local    — on-host, delegating straight to the strategy hooks (the
             single-process path every session used before this layer).
  sharded  — partition-per-device SPMD through the cluster engine in
             ``core.distributed``: labels travel by boundary-segment halo
             exchange, capacity by an O(k) psum, and quota ranking by a
             globally-ordered gather — with assignments bit-identical to
             the local path (pinned by the cluster parity suite), plus
             per-device halo/collective byte counters so "cut == comm
             volume" is measurable from the session.

Backends register under a name, exactly like strategies; ``SystemConfig``
selects one via ``cluster.backend`` and ``DynamicGraphSystem.distribute()``
/ ``.gather()`` move a live session between them.

Example — resolve backends from the registry (doctested in CI):

    >>> from repro.api import (ClusterSection, execution_backend_names,
    ...                        resolve_execution_backend)
    >>> execution_backend_names()
    ('local', 'sharded')
    >>> resolve_execution_backend("local").name
    'local'
    >>> cl = ClusterSection(backend="sharded", devices=4)
    >>> resolve_execution_backend("sharded", cluster=cl).cluster.devices
    4
    >>> try:
    ...     resolve_execution_backend("shardedd")
    ... except ValueError as e:
    ...     "execution backends" in str(e)
    True
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import ClusterSection
from repro.api.strategy import StrategyContext
from repro.core.distributed import (BlockLayout, DistGraph,
                                    build_cluster_graph, comm_model,
                                    layout_device_arrays, make_cluster_step)
from repro.core.migration import MigrationStats, flush_pending
from repro.core.partition_state import PartitionState
from repro.core.repartitioner import History
from repro.core.repartitioner import adapt_rounds as _adapt_rounds
from repro.core.repartitioner import run_to_convergence as _run_to_convergence
from repro.graph.structure import Graph
from repro.obs.trace import NULL_TRACER


@runtime_checkable
class ExecutionBackend(Protocol):
    """Structural protocol — anything with these hooks executes a session.

    The three execution hooks mirror the strategy surface the session
    drives (interleaved ``adapt`` per superstep, batch ``converge`` /
    ``adapt_rounds``); the two telemetry hooks feed the session's comm
    counters. A backend receives the *strategy* so non-migrating policies
    can stay on their (free) local hooks.
    """

    name: str

    def adapt(self, strategy: Any, graph: Graph, state: PartitionState,
              ctx: StrategyContext) -> PartitionState: ...

    def converge(self, strategy: Any, graph: Graph, state: PartitionState,
                 ctx: StrategyContext) -> Tuple[PartitionState, History]: ...

    def adapt_rounds(self, strategy: Any, graph: Graph, state: PartitionState,
                     iters: int, ctx: StrategyContext,
                     ) -> Tuple[PartitionState, History]: ...

    def pop_superstep_comm(self) -> Dict[str, int]: ...

    def device_stats(self) -> Optional[Dict[str, Any]]: ...


# ---------------------------------------------------------------------------
# Registry (same contract as the strategy registry: fail loudly on typos)
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[..., Any]] = {}


def register_execution_backend(name: str, *aliases: str
                               ) -> Callable[[Callable[..., Any]],
                                             Callable[..., Any]]:
    """Class decorator: register a backend factory under ``name`` (+aliases)."""

    def deco(factory: Callable[..., Any]) -> Callable[..., Any]:
        for key in (name, *aliases):
            if key in _REGISTRY:
                raise ValueError(f"execution backend {key!r} already registered")
            _REGISTRY[key] = factory
        return factory

    return deco


def execution_backend_names() -> Tuple[str, ...]:
    """Every registered backend name, sorted."""
    return tuple(sorted(_REGISTRY))


def resolve_execution_backend(spec: Any,
                              cluster: Optional[ClusterSection] = None) -> Any:
    """Turn a registry name, backend class, or instance into an instance."""
    if isinstance(spec, str):
        try:
            factory = _REGISTRY[spec]
        except KeyError:
            raise ValueError(
                f"unknown execution backend {spec!r}; registered execution "
                f"backends: {', '.join(execution_backend_names())}") from None
        return factory(cluster=cluster)
    if isinstance(spec, type):
        return spec(cluster=cluster)
    return spec


_ZERO_COMM = {"halo_bytes": 0, "halo_live_bytes": 0, "collective_bytes": 0}


def _graph_fingerprint(graph: Graph) -> Tuple[int, ...]:
    """Cheap content fingerprint of a ``Graph``'s live topology.

    Object identity is not enough to decide whether the device bucketing is
    stale: a caller can mutate a numpy-backed ``Graph`` in place, and the
    streaming path hands over a *new* object every superstep even when the
    delta was empty. An order-sensitive polynomial hash over the live edge
    endpoints and live node ids (int64, wraparound) catches both — O(E)
    numpy, far below the bucketing cost it gates.
    """
    nm = np.asarray(graph.node_mask)
    em = np.asarray(graph.edge_mask)
    s = np.asarray(graph.src)[em].astype(np.int64)
    d = np.asarray(graph.dst)[em].astype(np.int64)
    ei = np.flatnonzero(em).astype(np.int64)
    ni = np.flatnonzero(nm).astype(np.int64)
    with np.errstate(over="ignore"):
        h_edges = int(((s * 0x9E3779B1 + d * 0x85EBCA77)
                       * (ei + 0xC2B2AE3D)).sum()) & (2 ** 63 - 1)
        h_nodes = int((ni * 0x27D4EB2F + 1).sum()) & (2 ** 63 - 1)
    return (nm.shape[0], int(nm.sum()), int(em.sum()), h_edges, h_nodes)


@register_execution_backend("local")
class LocalBackend:
    """On-host execution: straight delegation to the strategy hooks."""

    name = "local"
    # the session re-points these at its own tracer/config (DESIGN.md §11);
    # a directly-constructed backend stays on the no-op defaults
    tracer: Any = NULL_TRACER
    comm_probe = False

    def __init__(self, cluster: Optional[ClusterSection] = None):
        self.cluster = cluster if cluster is not None else ClusterSection()

    def adapt(self, strategy, graph, state, ctx):
        with self.tracer.span("adapt", iters=ctx.adapt_iters) as sp:
            state = strategy.adapt(graph, state, ctx)
            sp.fence(state.assignment)
        return state

    def converge(self, strategy, graph, state, ctx):
        return strategy.converge(graph, state, ctx)

    def adapt_rounds(self, strategy, graph, state, iters, ctx):
        return strategy.adapt_rounds(graph, state, iters, ctx)

    def pop_superstep_comm(self) -> Dict[str, int]:
        return dict(_ZERO_COMM)

    def device_stats(self) -> Optional[Dict[str, Any]]:
        return None

    def invalidate(self) -> None:
        pass

    def __repr__(self) -> str:
        return f"<{type(self).__name__} {self.name!r}>"


@register_execution_backend("sharded")
class ShardedBackend:
    """Partition-per-device SPMD execution over the cluster engine.

    The session keeps its canonical arrays in slot order; this backend
    buckets the graph into device blocks (``build_cluster_graph``, rebuilt
    only when the graph's content fingerprint changes, with padded bucket
    shapes that survive streaming growth), runs the parity migrator under
    ``shard_map``, and maps assignments back. Compiled steps take the
    bucketing as jit *arguments* and are cached per shape signature, so a
    shape-stable rebuild costs zero recompiles — the ``cluster/recompile``
    span fires only on genuine shape growth. Only strategies flagged
    ``cluster_native`` (the xDGP migrator — the deferred-commit step the
    cluster engine implements) route through it; everything else —
    non-adapting baselines *and* rival migrators (spinner/sdp/restream)
    with different step semantics — falls through to its local hooks.

    Decision parity with the local path is exact — same RNG draws, same
    quota order — so ``distribute()``/``gather()`` can move a session
    mid-run without perturbing its trajectory.
    """

    name = "sharded"
    tracer: Any = NULL_TRACER
    comm_probe = False                # timed comm mirrors (telemetry knob)

    def __init__(self, cluster: Optional[ClusterSection] = None):
        self.cluster = (cluster if cluster is not None
                        else ClusterSection(backend="sharded"))
        self._mesh: Optional[jax.sharding.Mesh] = None
        self._mesh_devices = 0
        self._graph_ref: Optional[Graph] = None
        self._graph_fp: Optional[Tuple[int, ...]] = None
        self._dg: Optional[DistGraph] = None
        self._layout: Optional[BlockLayout] = None
        self._comm: Optional[Dict[str, Any]] = None
        self._mig_args: Optional[Tuple[Any, ...]] = None
        # compiled cluster steps keyed by shape signature
        # (P, n_blk, B, E, n_cap, k, tie_break): a streaming rebuild whose
        # padded bucket shapes hold dispatches into the cached executable
        self._migrators: Dict[Tuple[Any, ...], Any] = {}
        self._probed = False
        self._superstep_comm = dict(_ZERO_COMM)
        self._total_comm = dict(_ZERO_COMM)
        self._total_iterations = 0

    # -- mesh / bucketing lifecycle ----------------------------------------
    def required_devices(self, k: int) -> int:
        """Device count this backend will run ``k`` partitions on."""
        P = self.cluster.devices or k
        if P != k:
            raise ValueError(
                f"sharded backend is partition-per-device: cluster.devices "
                f"({P}) must equal partition.k ({k}) or be 0")
        avail = len(jax.devices())
        if P > avail:
            raise RuntimeError(
                f"sharded backend needs {P} devices but only {avail} are "
                f"visible; on CPU hosts launch with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={P}")
        return P

    def invalidate(self) -> None:
        """Drop bucketing/mesh caches (k-change, restore); totals survive."""
        self._mesh = None
        self._mesh_devices = 0
        self._graph_ref = None
        self._graph_fp = None
        self._dg = self._layout = self._comm = None
        self._mig_args = None
        self._migrators.clear()
        self._probed = False

    def _ensure(self, graph: Graph, state: PartitionState,
                ctx: StrategyContext) -> None:
        P = self.required_devices(ctx.k)
        if self._mesh is None or self._mesh_devices != P:
            devs = np.asarray(jax.devices()[:P])
            self._mesh = jax.sharding.Mesh(devs, (self.cluster.axis,))
            self._mesh_devices = P
            # block shapes and compiled executables are mesh-bound
            self._graph_ref = None
            self._graph_fp = None
            self._dg = self._layout = self._comm = None
            self._mig_args = None
            self._migrators.clear()
        fp = _graph_fingerprint(graph)
        if self._dg is not None and fp == self._graph_fp:
            # same live topology (identical object, an in-place no-op, or a
            # quiet streaming superstep): the bucketing is still valid
            self._graph_ref = graph
            return
        # host-side bucketing (runs on every topology change); previous
        # shapes are passed as floors so a rebuild keeps them unless the
        # graph genuinely outgrew a bucket — the compiled step stays hot
        with self.tracer.span("cluster/bucket", devices=P) as sp:
            if self._dg is None:
                floors = {}
            else:
                floors = {"min_block": self._dg.block_size,
                          "min_edges": int(self._dg.src_owner.shape[1]),
                          "min_halo": self._dg.halo_size}
            dg, self._layout = build_cluster_graph(
                graph, np.asarray(state.assignment), P,
                halo_pad=self.cluster.halo_pad,
                block_pad=self.cluster.block_pad,
                edge_pad=self.cluster.edge_pad, **floors)
            self._comm = comm_model(dg, ctx.k)
            # pin device placement once per rebuild: every dispatch then
            # sees identically-sharded avals (stable jit cache key)
            shard = jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec(self.cluster.axis))
            repl = jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec())
            self._dg = jax.device_put(dg, shard)
            blk_live, orig, ng_safe, slot_live = layout_device_arrays(
                self._layout)
            self._mig_args = (self._dg,
                              jax.device_put(blk_live, shard),
                              jax.device_put(orig, shard),
                              jax.device_put(ng_safe, repl),
                              jax.device_put(slot_live, repl))
            sp.set(halo_slots=self._dg.halo_size,
                   block=self._dg.block_size)
        self._graph_ref = graph
        self._graph_fp = fp

    def _charge(self, iters: int = 1) -> None:
        c = self._comm
        P = c["devices"]
        halo = iters * P * c["halo_bytes_per_device"]
        live = iters * P * c["halo_live_bytes_per_device"]
        coll = iters * P * c["collective_bytes_per_device"]
        for acc in (self._superstep_comm, self._total_comm):
            acc["halo_bytes"] += halo
            acc["halo_live_bytes"] += live
            acc["collective_bytes"] += coll
        self._total_iterations += iters

    def _sig(self, ctx: StrategyContext) -> Tuple[Any, ...]:
        """Shape signature a compiled cluster step is keyed by."""
        dg = self._dg
        return (dg.num_devices, dg.block_size, dg.halo_size,
                int(dg.src_owner.shape[1]), self._layout.n_cap,
                ctx.k, ctx.tie_break)

    def _migrator(self, ctx: StrategyContext,
                  state: Optional[PartitionState] = None):
        """The compiled step for the current shapes — built (and, given a
        state, compile-warmed) at most once per shape signature. The
        ``cluster/recompile`` span fires only here: on first use and on
        genuine shape growth past the padded buckets, never on a
        shape-stable streaming rebuild."""
        key = self._sig(ctx)
        mig = self._migrators.get(key)
        if mig is None:
            with self.tracer.span("cluster/recompile", devices=key[0],
                                  block=key[1], halo_slots=key[2],
                                  edge_bucket=key[3], n_cap=key[4]) as sp:
                mig = make_cluster_step(self._mesh, k=ctx.k,
                                        n_cap=self._layout.n_cap,
                                        tie_break=ctx.tie_break,
                                        axis=self.cluster.axis)
                if state is not None:
                    # warm the executable inside the span (pure: the result
                    # is discarded, no comm is charged) so the span, not the
                    # first dispatch, carries the compile cost
                    out = mig(state.assignment, state.pending, state.rng,
                              state.capacity, ctx.s, *self._mig_args)
                    sp.fence(out[0])
            self._migrators[key] = mig
        return mig

    def _step_fn(self, graph: Graph, ctx: StrategyContext,
                 unshard_each: bool = False,
                 state: Optional[PartitionState] = None):
        """state -> (state, MigrationStats) over the cluster engine, in the
        session's canonical slot order (plugs into the shared drivers).
        The migrator handles the slot↔block permutation on device, so one
        iteration is one jit dispatch — no host round-trips.

        ``unshard_each`` places every returned state back on the default
        device: the batch drivers interleave the step with single-device
        jits (cut history, flush) that must not see this mesh's sharding.
        The streaming ``adapt`` loop keeps the state mesh-resident instead
        and unshards once at the end."""
        mig = self._migrator(ctx, state)
        mig_args = self._mig_args
        s = ctx.s

        def step(state: PartitionState):
            a, p, rng, (committed, willing, admitted) = mig(
                state.assignment, state.pending, state.rng, state.capacity,
                s, *mig_args)
            self._charge(1)
            new_state = PartitionState(
                assignment=a, pending=p, capacity=state.capacity, rng=rng,
                iteration=state.iteration + 1, last_moves=committed)
            if unshard_each:
                new_state = self._unshard(new_state)
            return new_state, MigrationStats(committed=committed,
                                             willing=willing,
                                             admitted=admitted)

        return step

    @staticmethod
    def _unshard(state: PartitionState) -> PartitionState:
        """Place the final state back on the default device: the session's
        own jits (tracker updates, vertex program) must not inherit this
        mesh's sharding — it may be gone after a gather()/rescale()."""
        return jax.device_put(state, jax.devices()[0])

    # -- comm probe (DESIGN.md §11) ----------------------------------------
    def _probe_comm(self, state, ctx) -> None:
        """Attribute one migrator iteration to named comm phases.

        The halo exchange and the packed-key quota collective live *inside*
        one jit'd shard_map program, so they cannot be host-timed in situ.
        Instead, tiny jits mirroring exactly those collectives (same shapes,
        same mesh) are timed with fences — min of 3 reps after a compile
        warmup — alongside one full migrator iteration (pure function,
        results discarded: the session trajectory is untouched).  The
        decomposition enters the trace as synthetic spans:

          comm/halo_exchange     boundary-segment all_gather
          comm/quota_collective  packed-key all_gather + global sort
          kernel/score           residual (scoring + decide + damp + commit)

        Probes run ONCE per session (first adapt after enabling): the
        streaming path rebuilds the bucketing every superstep, and
        re-compiling the probe jits each time would dominate the very wall
        time the trace is meant to attribute.  The probe's own cost
        (compiles + reps) is visible as an ``obs/comm_probe`` span.
        """
        mesh, dg, axis = self._mesh, self._dg, self.cluster.axis
        spec_n = jax.sharding.PartitionSpec(axis)
        dg_specs = DistGraph(*([spec_n] * 8))
        P, n_blk = dg.num_devices, dg.block_size

        # each device returns its own gathered copy (out_specs=spec_n): the
        # same collective as the real step, with no replication for
        # shard_map to prove, and results that are discarded anyway
        @jax.jit
        def halo_probe(flat):
            f = jax.shard_map(
                lambda lf, dgl: jax.lax.all_gather(
                    jnp.where(dgl.boundary_ok[0], lf[dgl.boundary[0]], 0),
                    axis, tiled=True),
                mesh=mesh, in_specs=(spec_n, dg_specs), out_specs=spec_n)
            return f(flat, dg)

        @jax.jit
        def quota_probe(keys):
            f = jax.shard_map(
                lambda kb: jnp.sort(jax.lax.all_gather(kb, axis,
                                                       tiled=True)),
                mesh=mesh, in_specs=(spec_n,), out_specs=spec_n)
            return f(keys)

        @jax.jit
        def null_probe(x):
            # dispatch floor: a do-nothing shard_map of the same shape —
            # subtracted so the probes report collective cost, not the
            # per-dispatch overhead every tiny jit pays
            f = jax.shard_map(lambda xb: xb + 1, mesh=mesh,
                              in_specs=(spec_n,), out_specs=spec_n)
            return f(x)

        def best_of(fn, *a, reps: int = 3) -> float:
            jax.block_until_ready(fn(*a))           # compile warmup
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*a))
                best = min(best, time.perf_counter() - t0)
            return best

        iters_before = self._total_iterations
        with self.tracer.span("obs/comm_probe", devices=P):
            flat = jnp.zeros((P * n_blk,), jnp.int32)
            t_null = best_of(null_probe, flat)
            raw_halo = best_of(halo_probe, flat)
            raw_quota = best_of(quota_probe, flat)
            t_halo = max(raw_halo - t_null, 0.0)
            t_quota = max(raw_quota - t_null, 0.0)
            mig_step = self._step_fn(self._graph_ref, ctx, state=state)

            def full_iter():
                s2, _ = mig_step(state)             # pure: result discarded
                return s2.assignment

            t_full = best_of(full_iter)
        # the probe's _charge() calls are rolled back exactly (counted, not
        # hard-coded to best_of's rep count) — the probe must not inflate
        # the session's comm telemetry
        self._charge(-(self._total_iterations - iters_before))
        residual = max(t_full - t_null - t_halo - t_quota, 0.0)
        tr = self.tracer
        tr.add_span("comm/halo_exchange", t_halo, probed=True,
                    halo_slots=dg.halo_size, raw_s=raw_halo,
                    dispatch_floor_s=t_null)
        tr.add_span("comm/quota_collective", t_quota, probed=True,
                    raw_s=raw_quota, dispatch_floor_s=t_null)
        tr.add_span("kernel/score", residual, probed=True,
                    full_iter_s=t_full)

    # -- execution hooks ----------------------------------------------------
    def adapt(self, strategy, graph, state, ctx):
        if not getattr(strategy, "cluster_native", False):
            return strategy.adapt(graph, state, ctx)
        self._ensure(graph, state, ctx)
        first = self._sig(ctx) not in self._migrators
        step = self._step_fn(graph, ctx, state=state)
        tr = self.tracer
        if tr.enabled and self.comm_probe and not self._probed:
            self._probed = True
            self._probe_comm(state, ctx)
        with tr.span("cluster/dispatch", iters=ctx.adapt_iters,
                     compiled=first) as sp:
            for _ in range(ctx.adapt_iters):
                state, _ = step(state)
            sp.fence(state.assignment)
        with tr.span("cluster/host_sync") as sp:
            state = self._unshard(state)
            sp.fence(state.assignment)
        with tr.span("cluster/flush") as sp:
            state = flush_pending(state, graph)
            sp.fence(state.assignment)
        return state

    def converge(self, strategy, graph, state, ctx):
        if not getattr(strategy, "cluster_native", False):
            return strategy.converge(graph, state, ctx)
        self._ensure(graph, state, ctx)
        state, hist = _run_to_convergence(
            graph, state, s=ctx.s, patience=ctx.patience,
            max_iters=ctx.max_iters, tie_break=ctx.tie_break,
            rel_tol=ctx.rel_tol, record_history=ctx.record_history,
            step_fn=self._step_fn(graph, ctx, unshard_each=True,
                                  state=state), tracer=ctx.tracer)
        return state, hist

    def adapt_rounds(self, strategy, graph, state, iters, ctx):
        if not getattr(strategy, "cluster_native", False):
            return strategy.adapt_rounds(graph, state, iters, ctx)
        self._ensure(graph, state, ctx)
        state, hist = _adapt_rounds(
            graph, state, iters, record_history=ctx.record_history,
            step_fn=self._step_fn(graph, ctx, unshard_each=True,
                                  state=state), tracer=ctx.tracer)
        return state, hist

    # -- telemetry ----------------------------------------------------------
    def pop_superstep_comm(self) -> Dict[str, int]:
        out, self._superstep_comm = self._superstep_comm, dict(_ZERO_COMM)
        return out

    def device_stats(self) -> Optional[Dict[str, Any]]:
        """Per-device view of the comm bill (None before the first run)."""
        if self._comm is None:
            return None
        c = self._comm
        return {
            "devices": c["devices"],
            "halo_slots": c["halo_slots"],
            "boundary_live_per_device": c["boundary_live_per_device"],
            "halo_bytes_per_iter_per_device": c["halo_bytes_per_device"],
            "halo_live_bytes_per_iter_per_device":
                c["halo_live_bytes_per_device"],
            "collective_bytes_per_iter_per_device":
                c["collective_bytes_per_device"],
            "halo_bytes_total": self._total_comm["halo_bytes"],
            "halo_live_bytes_total": self._total_comm["halo_live_bytes"],
            "collective_bytes_total": self._total_comm["collective_bytes"],
            "iterations_total": self._total_iterations,
            "compiled_steps": len(self._migrators),
        }

    def __repr__(self) -> str:
        return f"<{type(self).__name__} {self.name!r} {self.cluster!r}>"
