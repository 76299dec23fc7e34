"""Spinner-style balanced label propagation (arXiv 1404.3861, §3).

Spinner partitions by iterative label propagation with an additive balance
penalty: every vertex scores each partition by the *normalised* share of its
neighbours there plus a bonus for partitions with free capacity,

    score(v, j) = counts[v, j] / deg(v)  +  w · max(C_j − occ_j, 0) / C_j

and greedily moves to the argmax (staying on ties — LPA's fixpoint rule).
Like xDGP, candidate moves pass a Bernoulli(s) gate (Spinner §3.3's
probabilistic migration, which breaks label oscillation) and a free-capacity
admission: movers targeting partition j are ranked deterministically and
only the first ``free_j`` admitted, so the capacity invariant holds by
construction. Unlike xDGP there is no deferral — admitted moves commit
within the step (``pending`` stays empty).

The neighbour-label histogram is the same quantity the xDGP migration
kernels compute, so ``backend="pallas"`` serves it from the fused BSR
kernels (``repro.kernels.migration_kernels.label_histogram``) while
``"ref"`` uses the unfused segment-sum path — bit-identical counts (pinned
by the kernel parity suite), hence bit-identical steps.

All scoring is float32 elementwise arithmetic in a fixed op order, so the
numpy oracle in ``tests/test_strategy_differential.py`` reproduces the jax
path bit-for-bit.
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


def spinner_scores(counts: jax.Array, occ: jax.Array, capacity: jax.Array,
                   balance_weight: float) -> jax.Array:
    """(n_cap, k) float32 Spinner score; the differential oracle mirrors
    this exact op order (divide, divide, multiply-add)."""
    deg = jnp.sum(counts, axis=1)
    degf = jnp.maximum(deg, 1).astype(jnp.float32)
    norm = counts.astype(jnp.float32) / degf[:, None]
    capf = jnp.maximum(capacity, 1).astype(jnp.float32)
    penalty = jnp.maximum(capacity - occ, 0).astype(jnp.float32) / capf
    return norm + jnp.float32(balance_weight) * penalty[None, :]


@partial(jax.jit, static_argnames=("balance_weight", "s", "backend",
                                   "executor"))
def spinner_step(state: PartitionState, graph: Graph, plan=None, *,
                 balance_weight: float = 0.5, s: float = 0.5,
                 backend: str = "ref", executor: Optional[str] = None,
                 ) -> Tuple[PartitionState, MigrationStats]:
    """One balanced-LPA iteration: score → stay-on-tie argmax → damp →
    free-capacity admission → immediate commit."""
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
    score = spinner_scores(counts, occ, state.capacity, balance_weight)

    cur = jnp.clip(assignment, 0, k - 1)
    cur_score = jnp.take_along_axis(score, cur[:, None], axis=1)[:, 0]
    best = jnp.max(score, axis=1)
    deg = jnp.sum(counts, axis=1)
    isolated = (deg == 0) | ~node_mask
    stay = (cur_score >= best) | isolated          # LPA: prefer current on ties
    target = jnp.where(stay, cur,
                       jnp.argmax(score, axis=1).astype(jnp.int32))

    wants_move = (target != cur) & node_mask
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
        pending=jnp.full_like(state.pending, -1),   # no deferral in Spinner
        capacity=state.capacity,
        rng=rng,
        iteration=state.iteration + 1,
        last_moves=moved,
    )
    return new_state, MigrationStats(committed=moved, willing=n_willing,
                                     admitted=moved)


def spinner_adapt_jit(graph: Graph, state: PartitionState, *,
                      iters: int = 5, balance_weight: float = 0.5,
                      s: float = 0.5, backend: str = "ref",
                      plan=None) -> PartitionState:
    """Fixed-iteration Spinner adaptation as one lax.scan program — the
    per-superstep dispatch shape, mirroring ``repartitioner.adapt_jit``."""

    def body(st, _):
        st, stats = spinner_step(st, graph, plan, balance_weight=balance_weight,
                                 s=s, backend=backend)
        return st, stats.committed

    state, _ = jax.lax.scan(body, state, None, length=iters)
    return state
