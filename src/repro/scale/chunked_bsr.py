"""Chunked BSR construction: bit-identical to ``graph_to_bsr`` with
bounded peak host memory (DESIGN.md §14).

The monolithic packer materialises the full symmetrised COO (2·|E| int64
triples) plus a same-length ``np.unique`` workspace before a single
scatter-add — ~100 bytes/edge of transient peak, which at 10M vertices ×
degree 16 is >10 GB of scratch for a packing whose *output* may be far
smaller.  This builder replaces the one-shot pass with a two-pass
count-then-fill over edge chunks:

  pass 1 (count) — stream chunks, fold each chunk's unique tile keys into
      one sorted key set (``np.union1d``); peak state = key set + 1 chunk.
  pass 2 (fill)  — allocate the packed arrays once (guarded by
      ``memory_budget``), re-stream the same chunks, and scatter each
      chunk into its tiles via ``searchsorted`` into the global key set.

Bit-identity with ``graph_to_bsr`` is a contract, not an accident, and the
two ingredients are pinned by ``tests/test_scale.py``:

* the global tile index of every entry is identical — ``searchsorted``
  into the sorted key set equals ``np.unique(..., return_inverse=True)``
  over all entries at once;
* the float accumulation order is identical — chunks are iterated
  **direction-major** (every s→d chunk, then every d→s chunk), which is
  exactly the order ``np.concatenate([s, d])`` feeds ``np.add.at``.

Overflow policy: every quantity headed for an int32 container goes through
``check_int32_index`` and fails loudly (the same guard the monolithic
packer uses).  Memory policy: ``memory_budget`` bounds the bytes this call
may allocate for the packed blocks + key set; exceeding it raises
``MemoryBudgetError`` *before* the allocation, never after the host OOMs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterator, Optional, Tuple

import numpy as np
import jax.numpy as jnp

from repro.graph.bsr import BSRMatrix, check_int32_index
from repro.graph.structure import Graph
from repro.obs.trace import NULL_TRACER


class MemoryBudgetError(MemoryError):
    """The packed BSR would exceed the caller's ``memory_budget``."""


def iter_edge_chunks(graph: Graph, chunk_edges: int
                     ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Live edges of ``graph`` in edge-slot order, ``chunk_edges`` at a
    time, as (src, dst) int64 arrays."""
    em = np.asarray(graph.edge_mask)
    idx = np.flatnonzero(em)
    src = np.asarray(graph.src)
    dst = np.asarray(graph.dst)
    for lo in range(0, idx.size, chunk_edges):
        sel = idx[lo:lo + chunk_edges]
        yield src[sel].astype(np.int64), dst[sel].astype(np.int64)
    if idx.size == 0:
        yield (np.empty((0,), np.int64),) * 2


def _direction_major(graph: Graph, chunk_edges: int
                     ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    # the monolithic packer processes np.concatenate([s, d]) → all forward
    # entries, then all reversed ones; replaying chunks in the same global
    # order keeps the scatter-add float accumulation bit-identical
    for s, d in iter_edge_chunks(graph, chunk_edges):
        yield s, d
    for s, d in iter_edge_chunks(graph, chunk_edges):
        yield d, s


def graph_to_bsr_chunked(graph: Graph, blk: int = 128,
                         normalize: Optional[str] = None,
                         nnzb_cap: Optional[int] = None, dtype=np.float32,
                         chunk_edges: int = 1 << 20,
                         memory_budget: Optional[int] = None,
                         tracer: Any = NULL_TRACER) -> BSRMatrix:
    """Two-pass chunked equivalent of ``graph_to_bsr`` — same signature
    plus the chunk size and an optional byte budget for the packed output.
    The graph's edge arrays are read back to the host once each, counted
    by ``tracer`` (site ``plan``); every pass then reads the host copies.
    """
    if normalize not in (None, "sym", "row"):
        raise ValueError(normalize)
    graph = dataclasses.replace(graph, **{
        f: tracer.host_read(np.asarray, getattr(graph, f), "plan")
        for f in ("edge_mask", "src", "dst")})
    n_cap = graph.n_cap
    n_pad = -(-n_cap // blk) * blk
    n_blocks = n_pad // blk
    check_int32_index(n_blocks, "n_blocks (tile rows)")

    # ---- pass 0: degrees (only when normalising) -------------------------
    deg = None
    if normalize is not None:
        deg = np.zeros((n_pad,), np.float64)
        for rows, _ in _direction_major(graph, chunk_edges):
            deg += np.bincount(rows, minlength=n_pad)
        deg = np.maximum(deg, 1.0)

    # ---- pass 1: count — fold chunk tile keys into one sorted set --------
    uniq = np.empty((0,), np.int64)
    for rows, cols in _direction_major(graph, chunk_edges):
        key = (rows // blk) * np.int64(n_blocks) + (cols // blk)
        uniq = np.union1d(uniq, key)
    nnzb = check_int32_index(uniq.shape[0], "nnzb (nonzero tile count)")
    cap = int(nnzb_cap if nnzb_cap is not None else max(nnzb, 1))
    if cap < nnzb:
        raise ValueError(f"nnzb_cap {cap} < required {nnzb}")

    # ---- budget gate: refuse *before* allocating the packed arrays -------
    itemsize = np.dtype(dtype).itemsize
    blocks_bytes = cap * blk * blk * itemsize
    planned = blocks_bytes + uniq.nbytes + cap * 4 + (n_blocks + 1) * 4
    if memory_budget is not None and planned > memory_budget:
        raise MemoryBudgetError(
            f"chunked BSR needs ~{planned / 2**20:.0f} MiB "
            f"({cap} tiles of {blk}x{blk} {np.dtype(dtype).name}) but "
            f"memory_budget is {memory_budget / 2**20:.0f} MiB; raise the "
            f"budget, raise blk, or relocate the graph first so tiles "
            f"concentrate")

    # ---- pass 2: fill — identical layout math to the monolithic packer ---
    blocks = np.zeros((cap, blk, blk), dtype=dtype)
    block_cols = np.full((cap,), -1, np.int32)
    block_cols[:nnzb] = (uniq % n_blocks).astype(np.int64)
    row_counts = np.zeros(n_blocks, dtype=np.int64)
    np.add.at(row_counts, (uniq // n_blocks).astype(np.int64), 1)
    row_ptr = np.zeros(n_blocks + 1, dtype=np.int32)
    np.cumsum(row_counts, out=row_ptr[1:])
    flat_blocks = blocks.reshape(-1)
    for rows, cols in _direction_major(graph, chunk_edges):
        key = (rows // blk) * np.int64(n_blocks) + (cols // blk)
        tile_of = np.searchsorted(uniq, key)
        vals = np.ones(rows.shape[0], dtype=np.float64)
        if normalize == "sym":
            vals /= np.sqrt(deg[rows] * deg[cols])
        elif normalize == "row":
            vals /= deg[rows]
        flat = tile_of * (blk * blk) + (rows % blk) * blk + (cols % blk)
        np.add.at(flat_blocks, flat, vals)
    return BSRMatrix(blocks=jnp.asarray(blocks),
                     block_cols=jnp.asarray(block_cols),
                     row_ptr=jnp.asarray(row_ptr),
                     nnzb=jnp.asarray(nnzb, jnp.int32))
