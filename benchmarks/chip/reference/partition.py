"""xDGP partitioning, written out plainly (paper §3; Spinner's tie rule).

* ``hash_start`` — HSH: a splitmix64 mix of the vertex id, modulo k.
* ``capacity`` — C = round(ceil(n_cap / k) · (1 + slack)) + 1 per part.
* ``migrate`` — ``rounds`` deferred-migration rounds. Each round commits
  last round's decisions, counts each vertex's neighbours per part, sends
  it to the part with most (ties broken by uniform noise, the current part
  included; isolated and dead vertices stay), lets it go with probability
  s, and admits, per (from, to) pair, the lowest-id movers up to the
  target's free room over k − 1; admitted moves commit next round.
* ``place`` — arrivals of one delta go where most of their already-placed
  delta neighbours are, weighted by free room (two passes, the second
  seeing the first's tentative labels), then are admitted lowest id first
  up to each part's room, the rest spilling over the remaining room.

Random draws follow the session's key schedule: every round splits its
key in three (carry, tie noise, gate); every placement takes a fresh
subkey of the session's placement key.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def hash_start(n_cap: int, k: int) -> np.ndarray:
    x = np.arange(n_cap, dtype=np.uint64)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    x = x ^ (x >> np.uint64(31))
    return (x % np.uint64(k)).astype(np.int32)


def capacity(n_cap: int, k: int, slack: float) -> np.ndarray:
    per = -(-n_cap // k)
    return np.full((k,), int(round(per * (1.0 + slack))) + 1, np.int32)


def rounded(x, low: bool):
    """``x`` rounded to bfloat16's 8-bit significand where ``low``. An
    explicit rounding: a cast to bfloat16 and back may be folded away by
    the compiler, which then computes the control in float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7) \
        if low else x


def neighbour_counts(src2, dst2, mask2, labels, k):
    """counts[v, j]: v's live neighbours in part j (directed edge list)."""
    n = labels.shape[0]
    lab = labels[jnp.clip(src2, 0, n - 1)]
    bins = jnp.where(mask2, jnp.clip(dst2, 0, n - 1) * k + lab, n * k)
    return jnp.zeros((n * k + 1,), jnp.int32).at[bins].add(1)[:n * k] \
        .reshape(n, k)


def rank_in_group(group, active, groups):
    """For active i: how many active j < i share i's group. Sorting the
    keys group·n + i puts each group's members in id order, so a member's
    rank is its sorted position less its group's first position."""
    n = group.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    key = jnp.sort(jnp.where(active, group, groups) * n + idx)
    first = jnp.searchsorted(key, jnp.arange(groups + 1, dtype=jnp.int32) * n)
    rank = jnp.zeros((n,), jnp.int32).at[key % n].set(idx - first[key // n])
    return jnp.where(active, rank, 0)


def part_sizes(labels, node_mask, k):
    return jnp.zeros((k + 1,), jnp.int32).at[
        jnp.where(node_mask, labels, k)].add(1)[:k]


def migrate_round(src2, dst2, mask2, node_mask, cap, s, k, low, carry):
    labels, pending, key = carry
    labels = jnp.where(pending >= 0, pending, labels)
    key, tie_key, gate_key = jax.random.split(key, 3)
    n = labels.shape[0]
    counts = neighbour_counts(src2, dst2, mask2, labels, k)
    noise = rounded(jax.random.uniform(tie_key, (n, k)), low)
    score = rounded(counts.astype(jnp.float32) + noise, low)
    target = jnp.argmax(score, axis=1).astype(jnp.int32)
    stay = (counts.max(axis=1) == 0) | ~node_mask
    target = jnp.where(stay, labels, target)
    gate = rounded(jax.random.uniform(gate_key, (n,)), low) < s
    movers = (target != labels) & node_mask & gate
    free = jnp.maximum(cap - part_sizes(labels, node_mask, k), 0)
    quota = free // max(k - 1, 1)
    rank = rank_in_group(labels * k + target, movers, k * k)
    admitted = movers & (rank < quota[target])
    return labels, jnp.where(admitted, target, -1), key


@partial(jax.jit, static_argnames=("rounds", "s", "k", "flush", "low"))
def migrate(src, dst, edge_mask, node_mask, labels, pending, cap, key, *,
            rounds: int, s: float, k: int, flush: bool, low: bool = False):
    """``rounds`` rounds; ``flush`` commits the last decisions at the end.
    Returns (labels, pending, key)."""
    src2 = jnp.concatenate([src, dst])
    dst2 = jnp.concatenate([dst, src])
    mask2 = jnp.concatenate([edge_mask, edge_mask])
    body = partial(migrate_round, src2, dst2, mask2, node_mask, cap, s, k,
                   low)
    labels, pending, key = jax.lax.fori_loop(
        0, rounds, lambda _, c: body(c), (labels, pending, key))
    if flush:
        labels = jnp.where(pending >= 0, pending, labels)
        pending = jnp.full_like(pending, -1)
    return labels, pending, key


@partial(jax.jit, static_argnames=("k", "passes"))
def place(add_src, add_dst, add_mask, node_mask, labels, cap, key, *,
          k: int, passes: int = 2):
    """Labels after placing the vertices a delta's additions bring to life
    (``node_mask`` is liveness before the delta)."""
    n = node_mask.shape[0]
    su = jnp.clip(add_src, 0, n - 1)
    sv = jnp.clip(add_dst, 0, n - 1)
    new = jnp.zeros((n,), bool)
    new = new.at[jnp.where(add_mask, su, 0)].max(add_mask & ~node_mask[su])
    new = new.at[jnp.where(add_mask, sv, 0)].max(add_mask & ~node_mask[sv])
    e_src = jnp.concatenate([su, sv])
    e_dst = jnp.concatenate([sv, su])
    e_ok = jnp.concatenate([add_mask, add_mask]) & (e_src != e_dst)
    sizes = part_sizes(labels, node_mask, k)
    noise = jax.random.uniform(key, (n, k)) * 1e-3
    out = labels
    for p in range(passes):
        seen = e_ok & (node_mask[e_src] | (p > 0)) & new[e_dst]
        counts = neighbour_counts(e_src, e_dst, seen, out, k)
        fill = sizes + (part_sizes(out, new, k) if p > 0 else 0)
        room = fill < cap
        balance = 1.0 - fill / jnp.maximum(cap, 1).astype(jnp.float32)
        score = counts.astype(jnp.float32) * balance[None, :]
        score = score + 1e-2 * balance[None, :] + noise
        best = jnp.argmax(jnp.where(room[None, :], score, -jnp.inf), axis=1)
        best = jnp.where(room.any(), best, jnp.argmin(fill))
        out = jnp.where(new, best.astype(jnp.int32), out)
    free = jnp.maximum(cap - sizes, 0)
    rank = rank_in_group(out, new, k)
    over = new & (rank >= free[out])
    room_left = jnp.maximum(free - part_sizes(out, new & ~over, k), 0)
    spill_rank = rank_in_group(jnp.zeros_like(out), over, 1)
    spill = jnp.searchsorted(jnp.cumsum(room_left), spill_rank, side="right")
    out = jnp.where(over, jnp.clip(spill, 0, k - 1).astype(jnp.int32), out)
    return jnp.where(new, out, labels)


def cut_edges(src, dst, edge_mask, labels):
    n = labels.shape[0]
    a = labels[jnp.clip(src, 0, n - 1)]
    b = labels[jnp.clip(dst, 0, n - 1)]
    return jnp.sum((a != b) & edge_mask)
