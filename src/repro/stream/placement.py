"""Online vertex placement for arriving stream deltas (streaming layer §2).

In the seed, a vertex that arrives mid-stream inherits whatever partition
label the padded-slot hash assigned at startup — effectively random — and
the migration heuristic has to undo that damage over many supersteps. This
module places arriving vertices *at ingest time* with a jit-compatible
Fennel/DGR-style streaming rule:

    score(v, j) = |N(v) ∩ P_j| · (1 − occ_j / C_j)        (greedy · balance)

computed only from the delta's own edges plus the current assignment, so the
whole placer is one fused device program over static shapes (a_cap, n_cap, k).
A small number of refinement passes lets new vertices that only connect to
*other new vertices* see their neighbours' tentative labels (the streaming
equivalent of DGR's sequential scan, without the sequential dependency).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.migration import _rank_within_group
from repro.graph.structure import GraphDelta


class PlacementStats(NamedTuple):
    placed: jax.Array          # () int32 — vertices placed by this call
    with_anchor: jax.Array     # () int32 — placed vertices that had ≥1 placed neighbour
    intra_edges: jax.Array     # () int32 — delta edges made intra-partition


@partial(jax.jit, static_argnames=("k", "passes"))
def place_delta(delta: GraphDelta, node_mask: jax.Array, assignment: jax.Array,
                occupancy: jax.Array, capacity: jax.Array, rng: jax.Array,
                *, k: int, passes: int = 2,
                ) -> Tuple[jax.Array, PlacementStats]:
    """Assign partitions to vertices arriving in ``delta``.

    Args:
      node_mask:  liveness *before* the delta is applied — endpoints outside
                  it are the arriving vertices to place.
      assignment: (n_cap,) current labels (old vertices keep theirs).
      occupancy:  (k,) live-vertex count per partition before the delta.
      capacity:   (k,) hard per-partition capacity.

    Returns the updated assignment and placement stats.
    """
    n_cap = node_mask.shape[0]
    a_cap = delta.add_mask.shape[0]

    su = jnp.clip(delta.add_src, 0, n_cap - 1)
    sv = jnp.clip(delta.add_dst, 0, n_cap - 1)
    m = delta.add_mask

    # arriving vertices: delta endpoints not live before the delta
    is_new = jnp.zeros((n_cap,), bool)
    is_new = is_new.at[jnp.where(m, su, 0)].max(m & ~node_mask[su], mode="drop")
    is_new = is_new.at[jnp.where(m, sv, 0)].max(m & ~node_mask[sv], mode="drop")

    # symmetrised delta edges (the only adjacency the placer may use)
    e_src = jnp.concatenate([su, sv])
    e_dst = jnp.concatenate([sv, su])
    e_ok = jnp.concatenate([m, m]) & (e_src != e_dst)

    labels = assignment.astype(jnp.int32)
    noise = jax.random.uniform(rng, (n_cap, k)) * 1e-3   # spread ties across parts

    def one_pass(labels: jax.Array, include_new: bool) -> jax.Array:
        # neighbour-partition counts for new vertices, from placed endpoints
        placed_src = e_ok & (node_mask[e_src] | include_new)
        seg = jnp.where(placed_src & is_new[e_dst], e_dst, n_cap)
        onehot = jax.nn.one_hot(labels[e_src], k, dtype=jnp.int32)
        counts = jax.ops.segment_sum(onehot * placed_src[:, None].astype(jnp.int32),
                                     seg, num_segments=n_cap + 1)[:n_cap]
        # occupancy including tentative placements of new vertices
        if include_new:
            occ_new = jnp.sum(jax.nn.one_hot(labels, k, dtype=jnp.int32)
                              * is_new[:, None].astype(jnp.int32), axis=0)
        else:
            occ_new = 0
        occ_eff = occupancy + occ_new
        room = occ_eff < capacity
        balance = 1.0 - occ_eff / jnp.maximum(capacity, 1).astype(jnp.float32)
        score = counts.astype(jnp.float32) * balance[None, :]
        # zero-count fallback: least-loaded partition (scaled below any real count)
        score = score + 1e-2 * balance[None, :] + noise
        score = jnp.where(room[None, :], score, -jnp.inf)
        best = jnp.argmax(score, axis=1).astype(jnp.int32)
        all_full = ~jnp.any(room)
        best = jnp.where(all_full, jnp.argmin(occ_eff).astype(jnp.int32), best)
        return jnp.where(is_new, best, labels)

    labels = one_pass(labels, include_new=False)
    for _ in range(max(passes - 1, 0)):
        labels = one_pass(labels, include_new=True)

    # hard-capacity admission: arrivals choosing the same partition are
    # ranked deterministically; those beyond its free room spill across the
    # remaining free slots of all partitions (prefix-sum assignment), so
    # capacity holds whenever total arrivals ≤ total free room. Beyond that
    # the residue lands in the last partition — there is nowhere legal left.
    free = jnp.maximum(capacity - occupancy, 0)
    chosen = jnp.clip(labels, 0, k - 1)
    rank = _rank_within_group(chosen, is_new, k)
    over = is_new & (rank >= free[chosen])
    adm_seg = jnp.where(is_new & ~over, chosen, k)
    admitted = jax.ops.segment_sum(jnp.ones_like(chosen), adm_seg,
                                   num_segments=k + 1)[:k]
    room_left = jnp.maximum(free - admitted, 0)
    spill_rank = _rank_within_group(jnp.zeros_like(chosen), over, 1)
    spill_to = jnp.searchsorted(jnp.cumsum(room_left), spill_rank, side="right")
    spill_to = jnp.clip(spill_to, 0, k - 1).astype(jnp.int32)
    labels = jnp.where(over, spill_to, labels)

    # stats: anchored placements + intra-partition delta edges
    anchor_seg = jnp.where(e_ok & node_mask[e_src] & is_new[e_dst], e_dst, n_cap)
    anchored = jax.ops.segment_max(
        jnp.ones((2 * a_cap,), jnp.int32), anchor_seg, num_segments=n_cap + 1)[:n_cap]
    stats = PlacementStats(
        placed=jnp.sum(is_new).astype(jnp.int32),
        with_anchor=jnp.sum((anchored > 0) & is_new).astype(jnp.int32),
        intra_edges=jnp.sum((labels[e_src] == labels[e_dst]) & e_ok).astype(jnp.int32) // 2,
    )
    return jnp.where(is_new, labels, assignment.astype(jnp.int32)), stats
