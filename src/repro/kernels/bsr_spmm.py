"""Block-sparse-row SpMM Pallas kernel (the TPU-native sparse adjacency op).

out[rowblock] = Σ_j A_tile[row_ptr[i]+j] @ X[block_cols[row_ptr[i]+j]]

128×128 dense tiles stream through the MXU; tile indices are scalar-
prefetched so the X block index map can chase the column pointer
(pltpu.PrefetchScalarGridSpec — the TPU gather idiom). Used for:

  * GNN sum-aggregation (GIN, GCN-normalised variants)
  * the xDGP migration scorer: counts = A @ one_hot(labels)  (DESIGN.md §2)

After xDGP repartitioning + relocation, tiles concentrate near the diagonal;
``max_tiles_per_row`` (the grid's inner extent) shrinks, cutting both DMA
and MXU work — partition quality becomes kernel speedup.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(row_ptr_ref, cols_ref, a_ref, x_ref, o_ref, *, max_per_row: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    start = row_ptr_ref[i]
    end = row_ptr_ref[i + 1]

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(start + j < end)
    def _accum():
        a = a_ref[0]                                     # (blk, blk)
        x = x_ref[0]                                     # (blk, d)
        o_ref[0] += jax.lax.dot(a, x, preferred_element_type=jnp.float32
                                ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("max_per_row", "interpret"))
def bsr_spmm(blocks: jax.Array, block_cols: jax.Array, row_ptr: jax.Array,
             x: jax.Array, *, max_per_row: int, interpret: bool = False
             ) -> jax.Array:
    """blocks (nnzb,blk,blk) · x (n_blocks*blk, d) → (n_blocks*blk, d).

    max_per_row: static upper bound on tiles per row-block (host-computed:
    ``int(np.diff(row_ptr).max())``).
    """
    nnzb, blk, _ = blocks.shape
    n_blocks = row_ptr.shape[0] - 1
    d = x.shape[1]
    xb = x.reshape(n_blocks, blk, d)

    def a_index(i, j, row_ptr_s, cols_s):
        idx = jnp.clip(row_ptr_s[i] + j, 0, nnzb - 1)
        return (idx, 0, 0)

    def x_index(i, j, row_ptr_s, cols_s):
        idx = jnp.clip(row_ptr_s[i] + j, 0, nnzb - 1)
        col = jnp.clip(cols_s[idx], 0, n_blocks - 1)
        return (col, 0, 0)

    def o_index(i, j, row_ptr_s, cols_s):
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks, max_per_row),
        in_specs=[
            pl.BlockSpec((1, blk, blk), a_index),
            pl.BlockSpec((1, blk, d), x_index),
        ],
        out_specs=pl.BlockSpec((1, blk, d), o_index),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, max_per_row=max_per_row),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, blk, d), x.dtype),
        interpret=interpret,
    )(row_ptr, block_cols, blocks, xb)
    return out.reshape(n_blocks * blk, d)


def max_tiles_per_row(row_ptr: np.ndarray) -> int:
    return int(max(1, np.diff(np.asarray(row_ptr)).max()))
