"""SDP-style scalable real-time dynamic placement (arXiv 2110.15669).

SDP keeps partitions good *as the graph changes* with two cheap mechanisms
instead of xDGP's full iterate-to-convergence loop:

  1. arrivals are placed online with a Fennel-style streaming rule
     (the existing ``repro.stream.placement.place_delta`` path — the
     strategy layer wires it in by subclassing ``OnlineFennel``), and
  2. a *boundary-only* refinement sweep: only vertices with at least one
     external neighbour reconsider their placement, scoring partitions with
     the same greedy·balance objective the placer uses,

         score(v, j) = counts[v, j] · (1 − occ_j / C_j)

     and moving only on a *strict* improvement over the current partition
     (ties stay — refinement must be a descent step, or churn never ends).

Like the other migrating strategies, movers pass a Bernoulli(s) gate and a
deterministic free-capacity admission ranking, so the capacity invariant
holds by construction and steps are reproducible from the state's RNG key.
Moves commit within the step (real-time placement cannot defer).

Scoring is float32 elementwise in a fixed op order; the numpy oracle in
``tests/test_strategy_differential.py`` reproduces it bit-for-bit.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.migration import (MigrationStats, _rank_within_group,
                                  neighbour_partition_counts)
from repro.core.partition_state import PartitionState, occupancy
from repro.graph.structure import Graph


def sdp_scores(counts: jax.Array, occ: jax.Array,
               capacity: jax.Array) -> jax.Array:
    """(n_cap, k) float32 greedy·balance score (same objective as the
    streaming placer); the differential oracle mirrors this op order."""
    capf = jnp.maximum(capacity, 1).astype(jnp.float32)
    balance = 1.0 - occ.astype(jnp.float32) / capf
    return counts.astype(jnp.float32) * balance[None, :]


@partial(jax.jit, static_argnames=("s", "backend", "executor"))
def sdp_refine_step(state: PartitionState, graph: Graph, plan=None, *,
                    s: float = 0.5, backend: str = "ref",
                    executor: Optional[str] = None,
                    ) -> Tuple[PartitionState, MigrationStats]:
    """One boundary-refinement sweep: boundary mask → strict-improvement
    argmax → damp → free-capacity admission → immediate commit."""
    k = state.k
    node_mask = graph.node_mask
    assignment = state.assignment

    rng, sub = jax.random.split(state.rng)
    if backend == "pallas":
        from repro.kernels.migration_kernels import label_histogram
        counts = label_histogram(graph, plan, assignment, k,
                                 executor=executor)
    elif backend == "ref":
        counts = neighbour_partition_counts(graph, assignment, k)
    else:
        raise ValueError(f"unknown backend {backend!r}; valid: ref, pallas")

    occ = occupancy(state, node_mask)
    score = sdp_scores(counts, occ, state.capacity)

    cur = jnp.clip(assignment, 0, k - 1)
    cur_count = jnp.take_along_axis(counts, cur[:, None], axis=1)[:, 0]
    cur_score = jnp.take_along_axis(score, cur[:, None], axis=1)[:, 0]
    deg = jnp.sum(counts, axis=1)
    boundary = (deg - cur_count) > 0               # ≥1 external neighbour
    best = jnp.max(score, axis=1)
    target = jnp.argmax(score, axis=1).astype(jnp.int32)

    wants_move = (boundary & (best > cur_score)    # strict improvement only
                  & (target != cur) & node_mask)
    gate = jax.random.bernoulli(sub, p=s, shape=wants_move.shape)
    willing = wants_move & gate
    n_willing = jnp.sum(willing).astype(jnp.int32)

    free = jnp.maximum(state.capacity - occ, 0)
    tgt = jnp.clip(target, 0, k - 1)
    rank = _rank_within_group(tgt, willing, k)
    admitted = willing & (rank < free[tgt])
    moved = jnp.sum(admitted).astype(jnp.int32)

    new_assignment = jnp.where(admitted, target, assignment)
    new_state = PartitionState(
        assignment=new_assignment,
        pending=jnp.full_like(state.pending, -1),   # no deferral in SDP
        capacity=state.capacity,
        rng=rng,
        iteration=state.iteration + 1,
        last_moves=moved,
    )
    return new_state, MigrationStats(committed=moved, willing=n_willing,
                                     admitted=moved)


def sdp_adapt_jit(graph: Graph, state: PartitionState, *, iters: int = 5,
                  s: float = 0.5, backend: str = "ref",
                  plan=None) -> PartitionState:
    """Fixed-iteration refinement as one lax.scan program (per-superstep
    dispatch shape, mirroring ``repartitioner.adapt_jit``)."""

    def body(st, _):
        st, stats = sdp_refine_step(st, graph, plan, s=s, backend=backend)
        return st, stats.committed

    state, _ = jax.lax.scan(body, state, None, length=iters)
    return state
