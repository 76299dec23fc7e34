"""Inputs of the benchmark, all made from ``--seed``.

* ``kronecker_edges`` — the Graph500 Kronecker generator (initiator
  A/B/C/D, one bit of each endpoint per level), drawn on the device in one
  jitted call, endpoints relabelled by a random vertex permutation as the
  specification asks.
* ``dedupe_graph`` — the base graph from a generated edge list in one
  device pass: self-loops dropped, duplicate undirected edges merged,
  compacted into ``e_cap`` padded slots sorted by (lo, hi).
* ``fem_cube_edges`` — the regular 3-D lattice of the xDGP paper's FEM use
  case (6-neighbourhood), each undirected edge once.
* ``due_offsets`` — open-loop arrival times of a traffic mix (seconds from
  the window's start): ``uniform`` at a fixed rate, or ``poisson``.

Keys: ``data_key(seed, stream)`` derives one independent key per input
stream (base graph, permutation, live stream, arrivals), so a seed fixes
every input and two streams of one seed never share draws.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# input streams of one seed
BASE_EDGES, PERMUTATION, LIVE_EDGES, ARRIVALS = 1, 2, 3, 4


def data_key(seed: int, stream: int) -> jax.Array:
    """Key of one input stream of ``seed`` (any non-negative int < 2**64)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), stream)


@partial(jax.jit, static_argnames=("scale", "m", "a", "b", "c"))
def _kronecker(key: jax.Array, perm_key: jax.Array, *, scale: int, m: int,
               a: float, b: float, c: float) -> Tuple[jax.Array, jax.Array]:
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab

    def level(i, uv):
        u, v = uv
        k_i, k_j = jax.random.split(jax.random.fold_in(key, i))
        ii = jax.random.uniform(k_i, (m,)) > ab
        jj = jax.random.uniform(k_j, (m,)) > jnp.where(ii, c_norm, a_norm)
        return (u | (ii.astype(jnp.int32) << i),
                v | (jj.astype(jnp.int32) << i))

    zero = jnp.zeros((m,), jnp.int32)
    u, v = jax.lax.fori_loop(0, scale, level, (zero, zero))
    perm = jax.random.permutation(perm_key, 1 << scale).astype(jnp.int32)
    return perm[u], perm[v]


def kronecker_edges(seed: int, stream: int, *, scale: int, m: int,
                    a: float, b: float, c: float,
                    ) -> Tuple[jax.Array, jax.Array]:
    """``m`` Kronecker edges of input ``stream``, under the seed's vertex
    permutation (the same for every stream of one seed)."""
    return _kronecker(data_key(seed, stream), data_key(seed, PERMUTATION),
                      scale=scale, m=m, a=a, b=b, c=c)


@partial(jax.jit, static_argnames=("n", "e_cap"))
def dedupe_graph(u: jax.Array, v: jax.Array, *, n: int, e_cap: int):
    """(src, dst, edge_mask, node_mask, edges): the undirected simple graph
    of an edge list, each edge once as (lo, hi), sorted, in ``e_cap`` slots;
    a vertex is live where it has an edge."""
    lo = jnp.minimum(u, v)
    hi = jnp.maximum(u, v)
    loop = lo == hi
    lo = jnp.where(loop, n, lo)
    hi = jnp.where(loop, n, hi)
    lo, hi = jax.lax.sort((lo, hi), num_keys=2)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    keep = first & (lo < n)
    slot = jnp.where(keep, jnp.cumsum(keep) - 1, e_cap)
    src = jnp.full((e_cap,), -1, jnp.int32).at[slot].set(lo, mode="drop")
    dst = jnp.full((e_cap,), -1, jnp.int32).at[slot].set(hi, mode="drop")
    edges = jnp.sum(keep)
    edge_mask = jnp.arange(e_cap) < edges
    node_mask = (jnp.zeros((n,), bool)
                 .at[jnp.where(keep, lo, n)].set(True, mode="drop")
                 .at[jnp.where(keep, hi, n)].set(True, mode="drop"))
    return src, dst, edge_mask, node_mask, edges


def stream_events(seed: int, count: int, *, scale: int, a: float, b: float,
                  c: float) -> np.ndarray:
    """(count, 3) int64 rows (t, u, v) of the live edge stream: Kronecker
    edges of the seed's live stream under its permutation, self-loops left
    out, ``t`` the event's index."""
    m = count + count // 256 + 1024          # self-loops are ~1e-4 of draws
    u, v = jax.device_get(kronecker_edges(seed, LIVE_EDGES, scale=scale, m=m,
                                          a=a, b=b, c=c))
    keep = u != v
    u, v = u[keep][:count], v[keep][:count]
    if u.shape[0] < count:
        raise RuntimeError(f"live stream too short: {u.shape[0]} < {count}")
    t = np.arange(count, dtype=np.int64)
    return np.stack([t, u.astype(np.int64), v.astype(np.int64)], axis=1)


def fem_cube_edges(side: int) -> Tuple[np.ndarray, np.ndarray]:
    """+x, +y, +z neighbour pairs of a ``side``³ lattice (ids x + y·side +
    z·side²), each undirected edge once."""
    ids = np.arange(side ** 3, dtype=np.int64)
    x, y, z = ids % side, (ids // side) % side, ids // (side * side)
    src, dst = [], []
    for ok, step in ((x + 1 < side, 1), (y + 1 < side, side),
                     (z + 1 < side, side * side)):
        src.append(ids[ok])
        dst.append(ids[ok] + step)
    return np.concatenate(src), np.concatenate(dst)


def due_offsets(arrivals: str, rate: float, count: int, seed: int
                ) -> np.ndarray:
    """(count,) seconds from the window's start at which each event is due."""
    if rate <= 0:
        raise ValueError(f"rate must be positive, got {rate}")
    if arrivals == "uniform":
        return np.arange(count, dtype=np.float64) / rate
    if arrivals == "poisson":
        gaps = np.asarray(jax.random.exponential(data_key(seed, ARRIVALS),
                                                 (count,)), np.float64)
        return np.cumsum(gaps) / rate - gaps[0] / rate
    raise ValueError(f"unknown arrivals {arrivals!r}: uniform or poisson")
