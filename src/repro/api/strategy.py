"""Pluggable partitioning strategies behind one protocol (the Spinner/SDP
shape: partitioning as a swappable policy inside a stable processing API).

A ``PartitionStrategy`` answers the three questions the runtime asks:

  init(graph, k)           -> labels   initial assignment of every vertex slot
  place(delta, ctx)        -> labels   where do *arriving* vertices go?
  adapt(graph, state, ctx) -> state    interleaved repartitioning per superstep

plus two batch-mode extensions used by ``DynamicGraphSystem.converge()`` /
``.adapt()``: ``converge(graph, state, ctx)`` and
``adapt_rounds(graph, state, iters, ctx)``, both returning
``(state, History)``.

Contract for ``place``: it may only relabel vertices that were dead before
the delta (``ctx.node_mask``) — surviving vertices keep their labels, which
is what keeps the incremental ``QualityTracker`` exact (see
``repro.stream.metrics``). Strategies that know exactly how many vertices
they placed report it via ``ctx.placed``; otherwise the system derives the
count from the liveness diff.

Strategies register under a name (plus seed-era aliases) in a module-level
registry; ``resolve_strategy`` turns a name / class / instance into an
instance and raises a ``ValueError`` listing every registered name on a
typo. ``repro.core.initial.initial_partition`` dispatches through the same
registry, so "adaptive vs. static-hash" is two strategy values — never two
code paths. ``canonical_strategy_names()`` lists each strategy exactly once
(primary names, no aliases) — the form every "run all strategies" loop
(arena benchmark, conformance suite) must use, or aliases run duplicates.

Example — resolve strategies from the registry and plug in a custom one
(doctested in CI):

    >>> from repro.api import (register_strategy, resolve_strategy,
    ...                        strategy_names, canonical_strategy_names)
    >>> {"static", "hash", "fennel", "xdgp"} <= set(strategy_names())
    True
    >>> {"spinner", "sdp", "restream"} <= set(canonical_strategy_names())
    True
    >>> "hsh" in strategy_names(), "hsh" in canonical_strategy_names()
    (True, False)
    >>> resolve_strategy("xdgp").name          # name, class or instance
    'xdgp'
    >>> from repro.api.strategy import StrategyBase
    >>> @register_strategy("doctest-noop")
    ... class Noop(StrategyBase):
    ...     name = "doctest-noop"
    >>> resolve_strategy("doctest-noop").name
    'doctest-noop'
    >>> try:
    ...     resolve_strategy("typo")
    ... except ValueError as e:
    ...     "registered strategies" in str(e)
    True
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Protocol, Tuple, runtime_checkable

import jax

from repro.compat import resolve_backend
from repro.core.initial import (block_partition, deterministic_greedy,
                                hash_partition, min_neighbours,
                                modulo_partition, random_partition)
from repro.core.partition_state import PartitionState
from repro.core.repartitioner import (History, adapt_jit, adapt_rounds,
                                      read_rounds, round_row,
                                      run_to_convergence)
from repro.core.restream import restream_state
from repro.core.sdp import sdp_adapt_jit, sdp_refine_step
from repro.core.spinner import spinner_adapt_jit, spinner_step
from repro.graph.structure import Graph, GraphDelta
from repro.obs.trace import NULL_TRACER
from repro.stream.placement import place_delta


@dataclasses.dataclass
class StrategyContext:
    """Everything a strategy may read during one runtime call.

    The partitioning knobs mirror ``SystemConfig.partition``; the array
    fields are filled by the system per call. ``placed`` is the one
    out-parameter: a placement strategy sets it to the exact number of
    vertices it placed. ``tracer`` is the session's (``repro.obs.trace``):
    strategies open their spans on it and count their host reads with it.
    """

    k: int = 8
    s: float = 0.5
    adapt_iters: int = 5
    tie_break: str = "random"
    placement_passes: int = 2
    patience: int = 30
    max_iters: int = 500
    rel_tol: float = 1e-3
    record_history: bool = True
    backend: str = "auto"          # migration scoring backend (DESIGN.md §9)
    # runtime arrays (filled by the system per call)
    node_mask: Optional[jax.Array] = None    # liveness *before* the delta
    assignment: Optional[jax.Array] = None   # current labels
    occupancy: Optional[jax.Array] = None    # (k,) live vertices per partition
    capacity: Optional[jax.Array] = None     # (k,) hard capacities
    rng: Optional[jax.Array] = None          # fresh subkey for this call
    tracer: Any = NULL_TRACER
    # out-parameter
    placed: Optional[int] = None


@runtime_checkable
class PartitionStrategy(Protocol):
    """Structural protocol — anything with these hooks plugs into the system."""

    name: str

    def init(self, graph: Graph, k: int) -> jax.Array: ...

    def place(self, delta: GraphDelta, ctx: StrategyContext) -> jax.Array: ...

    def adapt(self, graph: Graph, state: PartitionState,
              ctx: StrategyContext) -> PartitionState: ...


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[..., "StrategyBase"]] = {}
_CANONICAL: list = []          # primary names only, registration order


def register_strategy(name: str, *aliases: str
                      ) -> Callable[[Callable[..., Any]], Callable[..., Any]]:
    """Class decorator: register a strategy factory under ``name`` (+aliases)."""

    def deco(factory: Callable[..., Any]) -> Callable[..., Any]:
        for key in (name, *aliases):
            if key in _REGISTRY:
                raise ValueError(f"strategy name {key!r} already registered")
            _REGISTRY[key] = factory
        _CANONICAL.append(name)
        return factory

    return deco


def strategy_names() -> Tuple[str, ...]:
    """Every registered name, aliases included, sorted."""
    return tuple(sorted(_REGISTRY))


def canonical_strategy_names() -> Tuple[str, ...]:
    """Each registered strategy exactly once — primary names, no aliases,
    sorted. "Run every strategy" loops (the arena, the conformance suite)
    iterate this; ``strategy_names()`` would silently run ``hash`` again as
    ``hsh``, ``xdgp`` again as ``adaptive``, and so on."""
    return tuple(sorted(_CANONICAL))


def resolve_strategy(spec: Any, **kwargs: Any) -> "StrategyBase":
    """Turn a registry name, strategy class, or instance into an instance.

    Unknown names raise a ``ValueError`` that lists the registered names —
    a typo should cost seconds, not a debugging session.
    """
    if isinstance(spec, str):
        try:
            factory = _REGISTRY[spec]
        except KeyError:
            raise ValueError(
                f"unknown partition strategy {spec!r}; registered strategies: "
                f"{', '.join(strategy_names())}") from None
        return factory(**kwargs)
    if isinstance(spec, type):
        return spec(**kwargs)
    if kwargs:
        raise TypeError(f"cannot apply kwargs {sorted(kwargs)} to an already-"
                        f"constructed strategy instance {spec!r}")
    return spec


# ---------------------------------------------------------------------------
# Concrete strategies
# ---------------------------------------------------------------------------

class StrategyBase:
    """Default behaviour: hash init, arrivals inherit their padded-slot
    label, and no adaptation. Subclasses override the hooks they care about.

    ``adapts`` declares that the strategy's adaptation hooks do real
    migration work (the session uses it for telemetry and drift triggers).
    ``cluster_native`` additionally declares that those hooks implement the
    xDGP deferred-commit step — the one the sharded backend's cluster
    engine reproduces — so the backend may replace them with its SPMD
    migrator. Rival migrators (spinner/sdp/restream) set ``adapts=True``
    but stay ``cluster_native=False``: under a sharded session they run
    their own local hooks on the gathered arrays.
    """

    name = "base"
    adapts = False                 # True → adapt/converge run migrations
    cluster_native = False         # True → sharded backend may take over adapt
    last_plan: Optional[Dict[str, Any]] = None   # describe_plan of the last
                                   # batch-mode scoring plan (None = unfused)

    def _plan(self, graph: Graph, ctx: StrategyContext, backend: str):
        """Pre-pack the adjacency for the fused scorer (batch modes only).

        Streaming ``adapt`` passes ``plan=None`` — the packing-free flat
        plan — because the graph changes every superstep and a host-side
        repack per superstep would cost more than it saves. The batch
        drivers (``converge``/``adapt_rounds``) run many iterations over a
        fixed graph, so one pack amortises across all of them. The plan's
        kind is kept in ``last_plan`` so the session can report it. The
        pack runs inside a ``plan.build`` span (kind, shape and the tile
        bytes uploaded as attributes).
        """
        if backend != "pallas":
            self.last_plan = None
            return None
        from repro.kernels.migration_kernels import build_plan, describe_plan
        with ctx.tracer.span("plan.build") as sp:
            plan = build_plan(graph, tracer=ctx.tracer)
            self.last_plan = describe_plan(plan)
            sp.set(**self.last_plan, tile_bytes=0 if plan.blocks is None
                   else plan.blocks.nbytes)
        return plan

    def init(self, graph: Graph, k: int) -> jax.Array:
        return hash_partition(graph, k)

    def place(self, delta: GraphDelta, ctx: StrategyContext) -> jax.Array:
        return ctx.assignment

    def adapt(self, graph: Graph, state: PartitionState,
              ctx: StrategyContext) -> PartitionState:
        return state

    def converge(self, graph: Graph, state: PartitionState,
                 ctx: StrategyContext) -> Tuple[PartitionState, History]:
        return state, History.empty()

    def adapt_rounds(self, graph: Graph, state: PartitionState, iters: int,
                     ctx: StrategyContext) -> Tuple[PartitionState, History]:
        return state, History.empty()

    def __repr__(self) -> str:
        return f"<{type(self).__name__} {self.name!r}>"


@register_strategy("static")
class Static(StrategyBase):
    """The no-op baseline: hash init, inherited placement, zero adaptation.
    Swapping ``xdgp`` for ``static`` in ``SystemConfig.partition.strategy``
    is the paper's adaptive-vs-static-hash comparison."""

    name = "static"


@register_strategy("hash", "hsh")
class Hash(StrategyBase):
    """HSH: H(v) mod k (paper §5.2.1) — the de-facto standard; scatters."""

    name = "hash"


@register_strategy("random", "rnd")
class Random(StrategyBase):
    """RND: balanced pseudorandom assignment."""

    name = "random"

    def __init__(self, seed: int = 0):
        self.seed = seed

    def init(self, graph: Graph, k: int) -> jax.Array:
        return random_partition(graph, k, seed=self.seed)


@register_strategy("modulo", "mod")
class Modulo(StrategyBase):
    """v mod k without mixing — keeps sequential locality; for ablations."""

    name = "modulo"

    def init(self, graph: Graph, k: int) -> jax.Array:
        return modulo_partition(graph, k)


@register_strategy("block", "blk")
class Block(StrategyBase):
    """Contiguous id blocks (what a range-sharded store would do)."""

    name = "block"

    def init(self, graph: Graph, k: int) -> jax.Array:
        return block_partition(graph, k)


@register_strategy("dgr")
class Dgr(StrategyBase):
    """DGR: Stanton & Kliot linear deterministic greedy (streaming init)."""

    name = "dgr"

    def __init__(self, slack: float = 0.1):
        self.slack = slack

    def init(self, graph: Graph, k: int) -> jax.Array:
        return deterministic_greedy(graph, k, slack=self.slack)


@register_strategy("mnn")
class Mnn(StrategyBase):
    """MNN: minimum number of neighbours (Prabhakaran et al., streaming init)."""

    name = "mnn"

    def __init__(self, slack: float = 0.1):
        self.slack = slack

    def init(self, graph: Graph, k: int) -> jax.Array:
        return min_neighbours(graph, k, slack=self.slack)


@register_strategy("fennel", "online")
class OnlineFennel(StrategyBase):
    """Online Fennel/DGR placement of arriving vertices, no adaptation.

    score(v, j) = |N(v) ∩ P_j| · (1 − occ_j / C_j), computed from the
    delta's own edges only — one fused jit program (see
    ``repro.stream.placement``).
    """

    name = "fennel"

    def __init__(self, passes: Optional[int] = None):
        self.passes = passes            # None = take ctx.placement_passes

    def place(self, delta: GraphDelta, ctx: StrategyContext) -> jax.Array:
        passes = self.passes if self.passes is not None else ctx.placement_passes
        labels, stats = place_delta(
            delta, ctx.node_mask, ctx.assignment, ctx.occupancy,
            ctx.capacity, ctx.rng, k=ctx.k, passes=passes)
        ctx.placed = ctx.tracer.host_read(int, stats.placed, "place")
        return labels


@register_strategy("xdgp", "adaptive")
class XdgpAdaptive(OnlineFennel):
    """The full xDGP policy: online placement of arrivals + interleaved
    greedy vertex migration (paper §3), run to convergence on demand.

    ``placement="inherit"`` keeps arrivals on their padded-slot hash label
    (the seed behaviour) while still adapting — useful for ablating what
    online placement itself buys.
    """

    name = "xdgp"
    adapts = True
    cluster_native = True

    def __init__(self, placement: str = "online", passes: Optional[int] = None):
        if placement not in ("online", "inherit"):
            raise ValueError(f"placement must be 'online' or 'inherit', "
                             f"got {placement!r}")
        super().__init__(passes=passes)
        self.placement = placement
        self._adapt_cache: Dict[Tuple[float, int, str], Callable] = {}

    def place(self, delta: GraphDelta, ctx: StrategyContext) -> jax.Array:
        if self.placement == "inherit":
            return ctx.assignment
        return super().place(delta, ctx)

    def adapt(self, graph: Graph, state: PartitionState,
              ctx: StrategyContext) -> PartitionState:
        backend = resolve_backend(ctx.backend)
        key = (ctx.s, ctx.adapt_iters, ctx.tie_break, backend)
        fn = self._adapt_cache.get(key)
        if fn is None:
            s, iters, tie_break, bk = key
            fn = jax.jit(lambda g, st: adapt_jit(g, st, s=s, iters=iters,
                                                 tie_break=tie_break,
                                                 backend=bk))
            self._adapt_cache[key] = fn
        return fn(graph, state)

    def converge(self, graph: Graph, state: PartitionState,
                 ctx: StrategyContext) -> Tuple[PartitionState, History]:
        backend = resolve_backend(ctx.backend)
        return run_to_convergence(
            graph, state, s=ctx.s, patience=ctx.patience,
            max_iters=ctx.max_iters, tie_break=ctx.tie_break,
            rel_tol=ctx.rel_tol, record_history=ctx.record_history,
            backend=backend, plan=self._plan(graph, ctx, backend),
            tracer=ctx.tracer)

    def adapt_rounds(self, graph: Graph, state: PartitionState, iters: int,
                     ctx: StrategyContext) -> Tuple[PartitionState, History]:
        backend = resolve_backend(ctx.backend)
        return adapt_rounds(graph, state, iters, s=ctx.s,
                            tie_break=ctx.tie_break,
                            record_history=ctx.record_history,
                            backend=backend,
                            plan=self._plan(graph, ctx, backend),
                            tracer=ctx.tracer)


@register_strategy("spinner", "lpa")
class Spinner(StrategyBase):
    """Spinner-style balanced label propagation (arXiv 1404.3861).

    Iterative LPA with an additive free-capacity bonus, Bernoulli(s)
    damping and deterministic free-capacity admission — see
    ``repro.core.spinner``. Spinner is a *batch* repartitioner: arrivals
    inherit their slot label (the paper restreams periodically rather than
    placing online), and every adaptation hook runs balanced-LPA sweeps.
    Shares the fused BSR histogram kernels with xDGP when the pallas
    scoring backend is selected.
    """

    name = "spinner"
    adapts = True

    def __init__(self, balance_weight: float = 0.5):
        self.balance_weight = balance_weight
        self._adapt_cache: Dict[Tuple[float, float, int, str], Callable] = {}

    def _step_fn(self, graph: Graph, ctx: StrategyContext, backend: str):
        plan = self._plan(graph, ctx, backend)
        return lambda st: spinner_step(st, graph, plan,
                                       balance_weight=self.balance_weight,
                                       s=ctx.s, backend=backend)

    def adapt(self, graph: Graph, state: PartitionState,
              ctx: StrategyContext) -> PartitionState:
        backend = resolve_backend(ctx.backend)
        key = (self.balance_weight, ctx.s, ctx.adapt_iters, backend)
        fn = self._adapt_cache.get(key)
        if fn is None:
            w, s, iters, bk = key
            fn = jax.jit(lambda g, st: spinner_adapt_jit(
                g, st, iters=iters, balance_weight=w, s=s, backend=bk))
            self._adapt_cache[key] = fn
        return fn(graph, state)

    def converge(self, graph: Graph, state: PartitionState,
                 ctx: StrategyContext) -> Tuple[PartitionState, History]:
        backend = resolve_backend(ctx.backend)
        return run_to_convergence(
            graph, state, patience=ctx.patience, max_iters=ctx.max_iters,
            tie_break=ctx.tie_break, rel_tol=ctx.rel_tol,
            record_history=ctx.record_history,
            step_fn=self._step_fn(graph, ctx, backend), tracer=ctx.tracer)

    def adapt_rounds(self, graph: Graph, state: PartitionState, iters: int,
                     ctx: StrategyContext) -> Tuple[PartitionState, History]:
        backend = resolve_backend(ctx.backend)
        return adapt_rounds(graph, state, iters,
                            record_history=ctx.record_history,
                            step_fn=self._step_fn(graph, ctx, backend),
                            tracer=ctx.tracer)


@register_strategy("sdp")
class Sdp(OnlineFennel):
    """SDP-style scalable real-time dynamic placement (arXiv 2110.15669).

    Online Fennel placement of arrivals (inherited) plus a boundary-only
    strict-improvement refinement sweep per adaptation call — see
    ``repro.core.sdp``. Cheap by construction: only cut-boundary vertices
    reconsider, and only on a strict greedy·balance gain.
    """

    name = "sdp"
    adapts = True

    def __init__(self, passes: Optional[int] = None):
        super().__init__(passes=passes)
        self._adapt_cache: Dict[Tuple[float, int, str], Callable] = {}

    def _step_fn(self, graph: Graph, ctx: StrategyContext, backend: str):
        plan = self._plan(graph, ctx, backend)
        return lambda st: sdp_refine_step(st, graph, plan, s=ctx.s,
                                          backend=backend)

    def adapt(self, graph: Graph, state: PartitionState,
              ctx: StrategyContext) -> PartitionState:
        backend = resolve_backend(ctx.backend)
        key = (ctx.s, ctx.adapt_iters, backend)
        fn = self._adapt_cache.get(key)
        if fn is None:
            s, iters, bk = key
            fn = jax.jit(lambda g, st: sdp_adapt_jit(g, st, iters=iters,
                                                     s=s, backend=bk))
            self._adapt_cache[key] = fn
        return fn(graph, state)

    def converge(self, graph: Graph, state: PartitionState,
                 ctx: StrategyContext) -> Tuple[PartitionState, History]:
        backend = resolve_backend(ctx.backend)
        return run_to_convergence(
            graph, state, patience=ctx.patience, max_iters=ctx.max_iters,
            tie_break=ctx.tie_break, rel_tol=ctx.rel_tol,
            record_history=ctx.record_history,
            step_fn=self._step_fn(graph, ctx, backend), tracer=ctx.tracer)

    def adapt_rounds(self, graph: Graph, state: PartitionState, iters: int,
                     ctx: StrategyContext) -> Tuple[PartitionState, History]:
        backend = resolve_backend(ctx.backend)
        return adapt_rounds(graph, state, iters,
                            record_history=ctx.record_history,
                            step_fn=self._step_fn(graph, ctx, backend),
                            tracer=ctx.tracer)


@register_strategy("restream", "lemerrer")
class Restream(OnlineFennel):
    """Le Merrer-style restreaming repartitioning (arXiv 1310.8211),
    layered on the online Fennel placement path.

    Arrivals are placed online (inherited); each adaptation call replays
    one sequential restreaming pass over the whole live graph with the
    same greedy·balance rule, seeded by the current assignment — see
    ``repro.core.restream``. ``period`` runs the (host-side, O(V+E)) pass
    every Nth ``adapt`` call on this instance; the default restreams every
    superstep. ``converge`` repeats passes until one moves nothing (a pass
    fixpoint is stable, so further passes are provably no-ops).
    """

    name = "restream"
    adapts = True

    def __init__(self, passes: Optional[int] = None, period: int = 1):
        if period < 1:
            raise ValueError(f"period must be >= 1, got {period}")
        super().__init__(passes=passes)
        self.period = period
        self._calls = 0

    def adapt(self, graph: Graph, state: PartitionState,
              ctx: StrategyContext) -> PartitionState:
        self._calls += 1
        if (self._calls - 1) % self.period:
            return state
        state, _ = restream_state(state, graph)
        return state

    def converge(self, graph: Graph, state: PartitionState,
                 ctx: StrategyContext) -> Tuple[PartitionState, History]:
        hist = History.empty()
        for _ in range(ctx.max_iters):
            state, stats = restream_state(state, graph)
            if ctx.record_history:
                hist.add_rounds(*read_rounds(
                    graph, [round_row(graph, state, stats)], ctx.tracer),
                    state.k)
            if int(stats.committed) == 0:
                break
        return state, hist

    def adapt_rounds(self, graph: Graph, state: PartitionState, iters: int,
                     ctx: StrategyContext) -> Tuple[PartitionState, History]:
        return adapt_rounds(graph, state, iters,
                            record_history=ctx.record_history,
                            step_fn=lambda st: restream_state(st, graph),
                            tracer=ctx.tracer)
