"""One iteration of the greedy vertex-migration heuristic (paper §3.2–§3.4, §4.2).

Fully vectorised SPMD formulation of the paper's per-vertex loop:

  1. COMMIT   — apply migrations decided in the previous iteration
                (deferred vertex migration, §4.2).
  2. SCORE    — per vertex, count neighbours per partition:
                counts = segment_sum(one_hot(assignment[src]), dst)  (both directions).
  3. DECIDE   — greedy rule: go to argmax partition; stay if the current
                partition is among the argmax set or the vertex is isolated.
  4. DAMP     — Bernoulli(s) gate on willing vertices (anti-chasing, §3.4).
  5. QUOTA    — per (src-partition i, dst-partition j) pair, only the first
                Q^{i,j} = C_free^j / (k-1) movers are admitted (§3.3). Ranking
                is a deterministic within-group prefix count (order-free).
  6. DEFER    — admitted moves are written to ``pending``; they commit at the
                start of the next iteration (step 1).
  7. QUALITY  — the committed assignment's cut edges, E − ½·Σ_v
                counts[v, label(v)], and its occupancy max and sum, from
                what steps 2 and 5 already hold (``MigrationStats``).

Steps 2–4 have two implementations behind ``migrate_step``'s static
``backend`` switch (DESIGN.md §9): ``"ref"`` is the unfused op-by-op
pipeline below (the correctness oracle), ``"pallas"`` dispatches through the
fused kernels in ``repro.kernels.migration_kernels`` — bit-identical
assignments, shared RNG draws, one pass over the adjacency. Steps 5–6 are
shared; the fused path ranks movers with the single-key sort
(``_rank_within_group_fast``), which produces identical ranks.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.graph.structure import Graph
from repro.core.partition_state import PartitionState, occupancy
from repro.kernels.ref import same_label_pairs


class MigrationStats(NamedTuple):
    """One iteration's counts. The quality fields describe the committed
    assignment the iteration scored; a step that leaves them ``None`` has
    its quality computed by the batch drivers instead."""
    committed: jax.Array     # () int32 — migrations committed this iteration
    willing: jax.Array       # () int32 — vertices that wanted to move (post-damping)
    admitted: jax.Array      # () int32 — moves admitted by quotas (== next commit)
    cut_edges: Optional[jax.Array] = None      # () int32 — live edges cut
    occupancy_max: Optional[jax.Array] = None  # () int32 — max |P^i| (live)
    occupancy_sum: Optional[jax.Array] = None  # () int32 — sum |P^i| (live)


def neighbour_partition_counts(graph: Graph, assignment: jax.Array, k: int,
                               chunked: bool = False) -> jax.Array:
    """counts[v, j] = number of v's neighbours currently in partition j.

    The (2E, k) one-hot intermediate is the memory hot spot; ``chunked=True``
    loops over partitions instead (O(2E) per partition) for large graphs.
    On TPU this computation is served by the bsr_spmm Pallas kernel
    (counts = A_bsr @ one_hot(labels)); see repro.kernels.
    """
    n_cap = graph.n_cap
    src2, dst2, mask2 = graph.symmetrized()
    src_safe = jnp.clip(src2, 0, n_cap - 1)
    dst_seg = jnp.where(mask2, dst2, n_cap)          # padding -> dropped segment
    lab = assignment[src_safe]
    if not chunked:
        onehot = jax.nn.one_hot(lab, k, dtype=jnp.int32) * mask2[:, None].astype(jnp.int32)
        counts = jax.ops.segment_sum(onehot, dst_seg, num_segments=n_cap + 1)[:n_cap]
        return counts

    def per_part(j):
        contrib = ((lab == j) & mask2).astype(jnp.int32)
        return jax.ops.segment_sum(contrib, dst_seg, num_segments=n_cap + 1)[:n_cap]

    counts = jax.vmap(per_part)(jnp.arange(k)).T     # (n_cap, k)
    return counts


def greedy_targets(counts: jax.Array, assignment: jax.Array,
                   node_mask: jax.Array, rng: Optional[jax.Array] = None,
                   tie_break: str = "random") -> jax.Array:
    """Paper §3.2 decision rule. Returns desired partition per vertex.

    tie_break="stay":   the paper's literal rule — prefer the current partition
                        whenever it is among the argmax candidates. Converges to
                        zero migrations but freezes tied boundaries (≈0.54 cut
                        improvement on FEM vs the paper's claimed ≥0.6).
    tie_break="random": break argmax ties uniformly at random *including* the
                        current partition (the rule Spinner — the authors'
                        follow-up system — makes explicit). Tied boundaries
                        fluctuate and coarsen, matching the paper's claimed
                        quality (≥0.66 improvement on FEM in our runs).
    """
    k = counts.shape[1]
    best_count = jnp.max(counts, axis=1)
    cur = jnp.clip(assignment, 0, k - 1)
    cur_count = jnp.take_along_axis(counts, cur[:, None], axis=1)[:, 0]
    isolated = (best_count == 0) | ~node_mask
    if tie_break == "stay":
        stay = (cur_count >= best_count) | isolated
        target = jnp.where(stay, cur, jnp.argmax(counts, axis=1).astype(jnp.int32))
    elif tie_break == "random":
        if rng is None:
            raise ValueError("tie_break='random' requires an rng key")
        noise = jax.random.uniform(rng, counts.shape)
        score = counts.astype(jnp.float32) + noise      # < 1 gap → only ties shuffle
        target = jnp.argmax(score, axis=1).astype(jnp.int32)
        target = jnp.where(isolated, cur, target)
    else:
        raise ValueError(f"unknown tie_break {tie_break!r}")
    return target


def _group_starts(sorted_key: jax.Array, bounds: jax.Array) -> jax.Array:
    """First sorted position of each group: one binary search per group
    boundary (``num_groups + 1`` queries), gathered back per element by the
    caller. A prefix max-scan over all n positions gives the same starts,
    but at a million vertices it takes the TPU compiler ~100 s."""
    return jnp.searchsorted(sorted_key, bounds, side="left").astype(jnp.int32)


def _rank_within_group(group: jax.Array, active: jax.Array,
                       num_groups: int) -> jax.Array:
    """Deterministic 0-based rank of each active element within its group.

    ``group`` must lie in ``[0, num_groups)`` where ``active``. Sort by group
    id (inactive pushed to the end), then rank = position −
    position-of-group-start, scattered back. O(n log n), jit-friendly.
    """
    n = group.shape[0]
    keyed = jnp.where(active, group, num_groups).astype(jnp.int32)
    order = jnp.argsort(keyed)                       # stable in jax
    sorted_g = keyed[order]
    pos = jnp.arange(n, dtype=jnp.int32)
    starts = _group_starts(sorted_g,
                           jnp.arange(num_groups + 1, dtype=jnp.int32))
    rank_sorted = pos - starts[sorted_g]
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)
    return jnp.where(active, rank, jnp.int32(0))


def _rank_within_group_fast(group: jax.Array, active: jax.Array,
                            num_groups: int) -> jax.Array:
    """Bit-identical ranks to ``_rank_within_group`` via one unstable sort.

    Packs ``(group, position)`` into a single int32 key (unique ⇒ the
    unstable sort recovers exactly the stable order), so XLA sorts one
    array instead of a stable key/index pair — ~2× faster on CPU. Falls
    back to the stable variant when the packed key would overflow int32.
    """
    n = group.shape[0]
    if (num_groups + 1) * n >= 2 ** 31:      # static shapes: a Python check
        return _rank_within_group(group, active, num_groups)
    pos = jnp.arange(n, dtype=jnp.int32)
    key = jnp.where(active, group, num_groups) * n + pos
    skey = jnp.sort(key)
    g_s = skey // n
    pos_s = skey % n
    # group g's run starts at the first key >= g * n
    starts = _group_starts(skey, jnp.arange(num_groups + 1, dtype=jnp.int32) * n)
    rank_sorted = pos - starts[g_s]
    rank = jnp.zeros((n,), jnp.int32).at[pos_s].set(rank_sorted)
    return jnp.where(active, rank, jnp.int32(0))


@partial(jax.jit, static_argnames=("s", "use_chunked_counts", "tie_break",
                                   "backend", "executor"))
def migrate_step(state: PartitionState, graph: Graph, plan=None, *,
                 s: float = 0.5, use_chunked_counts: bool = False,
                 tie_break: str = "random", backend: str = "ref",
                 executor: Optional[str] = None,
                 ) -> Tuple[PartitionState, MigrationStats]:
    """One full adaptive iteration (commit → score → decide → damp → quota → defer).

    ``backend="ref"`` runs the unfused op pipeline below; ``"pallas"``
    dispatches score/decide/damp through the fused kernels
    (``repro.kernels.migration_kernels.score_select``), optionally over a
    pre-packed ``plan`` (None = the packing-free flat plan — what the
    streaming path uses). Both backends draw the same RNG and produce
    bit-identical assignments. ``executor`` pins the kernel executor
    (``native``/``interpret``/``jax``); None resolves via
    ``repro.compat.pallas_executor()`` at trace time, so an env override
    must be in place before the first traced call.
    """
    k = state.k
    node_mask = graph.node_mask

    # ---- 1. COMMIT deferred migrations from t-1 -------------------------
    with jax.named_scope("commit"):
        has_pending = state.pending >= 0
        assignment = jnp.where(has_pending, state.pending, state.assignment)
        committed = jnp.sum(has_pending & node_mask).astype(jnp.int32)

    with jax.named_scope("score"):
        rng, tie_key, sub = jax.random.split(state.rng, 3)
        if backend == "pallas":
            # ---- 2–4. fused SCORE + DECIDE + DAMP (DESIGN.md §9) --------
            from repro.kernels.migration_kernels import score_select
            n_cap = graph.n_cap
            if tie_break == "random":
                noise = jax.random.uniform(tie_key, (n_cap, k))
            else:
                noise = jnp.zeros((n_cap, k), jnp.float32)
            gate = jax.random.bernoulli(sub, p=s, shape=(n_cap,))
            counts, target, willing, _ = score_select(
                graph, plan, assignment, node_mask, noise, gate, k,
                tie_break=tie_break, executor=executor)
            n_willing = jnp.sum(willing).astype(jnp.int32)
            rank_fn = partial(_rank_within_group_fast, num_groups=k * k)
        elif backend == "ref":
            # ---- 2. SCORE ---------------------------------------------------
            counts = neighbour_partition_counts(graph, assignment, k,
                                                chunked=use_chunked_counts)

            # ---- 3. DECIDE --------------------------------------------------
            target = greedy_targets(counts, assignment, node_mask, rng=tie_key,
                                    tie_break=tie_break)
            wants_move = (target != assignment) & node_mask

            # ---- 4. DAMP (Bernoulli(s), paper §3.4) -------------------------
            gate = jax.random.bernoulli(sub, p=s, shape=wants_move.shape)
            willing = wants_move & gate
            n_willing = jnp.sum(willing).astype(jnp.int32)
            rank_fn = partial(_rank_within_group, num_groups=k * k)
        else:
            raise ValueError(
                f"unknown backend {backend!r}; valid: ref, pallas")

    # ---- 5. QUOTA (paper §3.3) + 6. DEFER ---------------------------------
    with jax.named_scope("quota"):
        occ = occupancy(
            PartitionState(assignment, state.pending, state.capacity, rng,
                           state.iteration, state.last_moves), node_mask)
        free = jnp.maximum(state.capacity - occ, 0)            # C^j_free(t)
        quota = free // jnp.maximum(k - 1, 1)      # Q^{i,j}, same for all i
        src_part = jnp.clip(assignment, 0, k - 1)
        group = src_part * k + jnp.clip(target, 0, k - 1)      # (i, j) pair id
        rank = rank_fn(group, willing)
        admitted = willing & (rank < quota[jnp.clip(target, 0, k - 1)])
        n_admitted = jnp.sum(admitted).astype(jnp.int32)
        pending = jnp.where(admitted, target, jnp.int32(-1))

    # ---- 7. QUALITY of the committed assignment, from the round's own
    # counts and occupancy: no gather over the edges ------------------------
    with jax.named_scope("quality"):
        # The barrier keeps XLA from fusing the reduction into the fused
        # scorer's output. Fused, XLA moved the kernel's label operands
        # out of VMEM: on a v5e at FEM-64 the kernel ran 2.7% slower than
        # with no reduction, and through the barrier 3.5% faster.
        same = same_label_pairs(*jax.lax.optimization_barrier(
            (counts, assignment)))
        cut = (graph.num_edges - same // 2).astype(jnp.int32)
        occ_max = jnp.max(occ).astype(jnp.int32)
        occ_sum = jnp.sum(occ).astype(jnp.int32)

    new_state = PartitionState(
        assignment=assignment,
        pending=pending,
        capacity=state.capacity,
        rng=rng,
        iteration=state.iteration + 1,
        last_moves=committed,
    )
    return new_state, MigrationStats(
        committed=committed, willing=n_willing, admitted=n_admitted,
        cut_edges=cut, occupancy_max=occ_max, occupancy_sum=occ_sum)


@jax.jit
def flush_pending(state: PartitionState, graph: Graph) -> PartitionState:
    """Commit any pending moves without taking new decisions (used at drain)."""
    has_pending = state.pending >= 0
    assignment = jnp.where(has_pending, state.pending, state.assignment)
    return PartitionState(
        assignment=assignment,
        pending=jnp.full_like(state.pending, -1),
        capacity=state.capacity,
        rng=state.rng,
        iteration=state.iteration + 1,
        last_moves=jnp.sum(has_pending & graph.node_mask).astype(jnp.int32),
    )
