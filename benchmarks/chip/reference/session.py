"""Plain replay of one streaming session, superstep by superstep.

Per superstep, in the session's order: take up to ``a_cap`` queued edges
(FIFO, the whole history of batches handed to ``step``), write the r-th of
them into the r-th free edge slot and bring their endpoints to life, place
the arrivals, run ``adapt_iters`` migration rounds and commit what they
left pending, then one PageRank superstep (vertices born this superstep
start at 1/|V|). The window never expires anything and ``dedupe`` is off,
so every queued edge is inserted once.

``Replay.step`` returns the cut and live edge count after the superstep,
which the benchmark holds against the session's incrementally tracked
numbers; its arrays after the last superstep are compared with the
session's.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import partition


@jax.jit
def insert(src, dst, edge_mask, node_mask, add_src, add_dst, add_mask):
    """The r-th valid addition goes to the r-th free slot."""
    free = ~edge_mask
    slot_rank = jnp.cumsum(free) - 1
    add_rank = jnp.cumsum(add_mask) - 1
    a_cap = add_mask.shape[0]
    which = jnp.full((a_cap,), -1, jnp.int32).at[
        jnp.where(add_mask, add_rank, a_cap)].set(
        jnp.arange(a_cap, dtype=jnp.int32), mode="drop")
    takes = free & (slot_rank < jnp.sum(add_mask))
    pick = jnp.where(takes, which[jnp.clip(slot_rank, 0, a_cap - 1)], -1)
    takes = takes & (pick >= 0)
    pick = jnp.clip(pick, 0, a_cap - 1)
    src = jnp.where(takes, add_src[pick], src)
    dst = jnp.where(takes, add_dst[pick], dst)
    ends = jnp.concatenate([jnp.where(add_mask, add_src, 0),
                            jnp.where(add_mask, add_dst, 0)])
    node_mask = node_mask.at[ends].max(jnp.concatenate([add_mask, add_mask]))
    return src, dst, edge_mask | takes, node_mask


@partial(jax.jit, static_argnames=("damping", "low"))
def pagerank_step(src, dst, edge_mask, born, node_mask, rank, *,
                  damping: float = 0.85, low: bool = False):
    r = partial(partition.rounded, low=low)
    n = rank.shape[0]
    live = jnp.maximum(jnp.sum(node_mask), 1).astype(jnp.float32)
    rank = r(jnp.where(born, 1.0 / live, rank))
    ends = jnp.concatenate([src, dst])
    others = jnp.concatenate([dst, src])
    mask2 = jnp.concatenate([edge_mask, edge_mask])
    deg = jnp.zeros((n + 1,), jnp.int32).at[
        jnp.where(mask2, ends, n)].add(1)[:n]
    share = r(rank / jnp.maximum(deg, 1).astype(jnp.float32))
    msg = jnp.where(mask2, share[jnp.clip(ends, 0, n - 1)], 0.0)
    agg = r(jnp.zeros((n + 1,), jnp.float32).at[
        jnp.where(mask2, others, n)].add(msg)[:n])
    new = r((1.0 - damping) / live + damping * agg)
    return jnp.where(node_mask, new, 0.0)


class Replay:
    """The reference session. ``src``/``dst``/``edge_mask``/``node_mask``
    are the generated base graph; ``seed`` the session seed."""

    def __init__(self, src, dst, edge_mask, node_mask, *, k: int, s: float,
                 slack: float, adapt_iters: int, a_cap: int, seed: int,
                 placement_passes: int = 2, damping: float = 0.85,
                 low: bool = False):
        n_cap = node_mask.shape[0]
        self.k, self.s, self.iters, self.a_cap = k, s, adapt_iters, a_cap
        self.passes, self.damping, self.low = placement_passes, damping, low
        self.src, self.dst = src, dst
        self.edge_mask, self.node_mask = edge_mask, node_mask
        self.labels = jnp.asarray(partition.hash_start(n_cap, k))
        self.pending = jnp.full((n_cap,), -1, jnp.int32)
        self.cap = jnp.asarray(partition.capacity(n_cap, k, slack))
        self.key = jax.random.PRNGKey(seed)
        self.place_key = jax.random.PRNGKey(seed ^ 0x5EED)
        live = jnp.maximum(jnp.sum(node_mask), 1).astype(jnp.float32)
        self.rank = jnp.where(node_mask, 1.0 / live, 0.0)
        self._queue: list = []
        self._queued = 0

    def step(self, events: np.ndarray):
        """One superstep on the batch ``events`` ((m, 3) rows t, u, v)."""
        if events.shape[0]:
            self._queue.append(events)
            self._queued += events.shape[0]
        take = min(self.a_cap, self._queued)
        adds = self._pop(take)
        before = self.node_mask
        labels = self.labels
        if take:
            a_src = np.full((self.a_cap,), -1, np.int32)
            a_dst = np.full((self.a_cap,), -1, np.int32)
            a_src[:take], a_dst[:take] = adds[:, 1], adds[:, 2]
            a_mask = np.arange(self.a_cap) < take
            self.src, self.dst, self.edge_mask, self.node_mask = insert(
                self.src, self.dst, self.edge_mask, self.node_mask,
                a_src, a_dst, a_mask)
            self.place_key, sub = jax.random.split(self.place_key)
            labels = partition.place(a_src, a_dst, a_mask, before, labels,
                                     self.cap, sub, k=self.k,
                                     passes=self.passes)
        self.labels, self.pending, self.key = partition.migrate(
            self.src, self.dst, self.edge_mask, self.node_mask, labels,
            self.pending, self.cap, self.key, rounds=self.iters, s=self.s,
            k=self.k, flush=True, low=self.low)
        self.rank = pagerank_step(
            self.src, self.dst, self.edge_mask, self.node_mask & ~before,
            self.node_mask, self.rank, damping=self.damping, low=self.low)
        cut = partition.cut_edges(self.src, self.dst, self.edge_mask,
                                  self.labels)
        return take, cut, jnp.sum(self.edge_mask)

    def _pop(self, take: int) -> np.ndarray:
        out, got = [], 0
        while got < take:
            head = self._queue[0]
            n = min(head.shape[0], take - got)
            out.append(head[:n])
            if n < head.shape[0]:
                self._queue[0] = head[n:]
            else:
                self._queue.pop(0)
            got += n
        self._queued -= take
        return np.concatenate(out) if out else np.empty((0, 3), np.int64)
