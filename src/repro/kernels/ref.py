"""Pure-jnp oracles for every Pallas kernel (the correctness contracts)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def ref_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        softcap: Optional[float] = None) -> jax.Array:
    """q: (B,H,Sq,D), k/v: (B,KV,Sk,D), GQA by head folding. window 0 = full."""
    b, h, sq, d = q.shape
    kv = k.shape[1]
    rep = h // kv
    qg = q.reshape(b, kv, rep, sq, d)
    scores = jnp.einsum("bkrqd,bksd->bkrqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    sk = k.shape[2]
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkrqs,bksd->bkrqd", p, v.astype(jnp.float32))
    return out.reshape(b, h, sq, d).astype(q.dtype)


def ref_bsr_spmm(blocks: jax.Array, block_cols: jax.Array, row_ptr: jax.Array,
                 x: jax.Array) -> jax.Array:
    """BSR (nnzb, blk, blk) × dense X (n_blocks*blk, d) → (n_blocks*blk, d).

    Padding tiles have block_cols == -1 and are skipped.
    """
    nnzb, blk, _ = blocks.shape
    n_blocks = row_ptr.shape[0] - 1
    d = x.shape[1]
    xb = x.reshape(n_blocks, blk, d)
    # per-tile row id
    rows = jnp.searchsorted(row_ptr, jnp.arange(nnzb), side="right") - 1
    valid = block_cols >= 0
    cols_safe = jnp.clip(block_cols, 0, n_blocks - 1)
    prods = jnp.einsum("nij,njd->nid", blocks.astype(jnp.float32),
                       xb[cols_safe].astype(jnp.float32))
    prods = jnp.where(valid[:, None, None], prods, 0.0)
    out = jax.ops.segment_sum(prods, jnp.clip(rows, 0, n_blocks - 1),
                              num_segments=n_blocks)
    return out.reshape(n_blocks * blk, d).astype(x.dtype)


def ref_bsr_label_histogram(blocks: jax.Array, block_cols: jax.Array,
                            row_ptr: jax.Array, labels: jax.Array,
                            k: int) -> jax.Array:
    """Oracle for the fused migration-scoring kernel's histogram stage.

    counts[v, j] = Σ_u A[v, u] · [labels[u] == j] over the BSR tiles —
    ``A @ one_hot(labels)`` with the one-hot built inside the contraction,
    exactly as the Pallas kernel does. Padding tiles (``block_cols == -1``)
    contribute nothing. Returns float32 ``(n_blocks*blk, k)``; entries are
    exact integers for unweighted adjacencies.
    """
    nnzb, blk, _ = blocks.shape
    n_blocks = row_ptr.shape[0] - 1
    onehot = jax.nn.one_hot(labels, k, dtype=jnp.float32)   # out-of-range → 0
    onehot = onehot.reshape(n_blocks, blk, k)
    rows = jnp.searchsorted(row_ptr, jnp.arange(nnzb), side="right") - 1
    valid = block_cols >= 0
    cols_safe = jnp.clip(block_cols, 0, n_blocks - 1)
    prods = jnp.einsum("nij,njd->nid", blocks.astype(jnp.float32),
                       onehot[cols_safe])
    prods = jnp.where(valid[:, None, None], prods, 0.0)
    out = jax.ops.segment_sum(prods, jnp.clip(rows, 0, n_blocks - 1),
                              num_segments=n_blocks)
    return out.reshape(n_blocks * blk, k)


def ref_score_select(counts: jax.Array, assignment: jax.Array,
                     node_mask: jax.Array, noise: jax.Array,
                     gate: jax.Array, *, tie_break: str = "random"
                     ) -> tuple:
    """Oracle for the kernel's fused decide+damp epilogue (paper §3.2/§3.4).

    Given per-vertex neighbour-label ``counts`` (exact integers, any float
    or int dtype), the current ``assignment``, liveness ``node_mask``,
    pre-drawn tie-break ``noise`` (same shape as counts) and Bernoulli
    damping ``gate``, returns ``(target, willing, gain)``:

      target  — desired partition per vertex (the greedy rule)
      willing — wants to move AND survived damping
      gain    — best_count − current_count (≥ 0; diagnostic)

    ``tie_break="random"``: argmax of ``counts + noise`` (a < 1 gap means
    only ties shuffle). ``tie_break="stay"``: prefer the current partition
    whenever it is among the argmax set; noise is ignored.
    """
    k = counts.shape[1]
    c = counts.astype(jnp.float32)
    cur = jnp.clip(assignment, 0, k - 1)
    cur_count = jnp.take_along_axis(c, cur[:, None], axis=1)[:, 0]
    best_count = jnp.max(c, axis=1)
    isolated = (best_count == 0) | ~node_mask
    if tie_break == "stay":
        stay = (cur_count >= best_count) | isolated
        target = jnp.where(stay, cur, jnp.argmax(c, axis=1).astype(jnp.int32))
    elif tie_break == "random":
        score = c + noise
        target = jnp.argmax(score, axis=1).astype(jnp.int32)
        target = jnp.where(isolated, cur, target)
    else:
        raise ValueError(f"unknown tie_break {tie_break!r}")
    willing = (target != assignment) & node_mask & gate
    gain = (best_count - cur_count).astype(jnp.float32)
    return target, willing, gain


def same_label_pairs(counts: jax.Array, labels: jax.Array) -> jax.Array:
    """Σ_v counts[v, labels[v]] over (n, k) ``counts``; labels outside
    ``[0, k)`` count nothing. On the scorer's counts this is twice the
    live edges inside a partition, so cut = E − same/2 without a gather
    over the edges. Summed as int32, exact at any size."""
    iota = jax.lax.broadcasted_iota(jnp.int32, counts.shape, 1)
    hit = iota == labels[:, None]
    return jnp.sum(jnp.where(hit, counts.astype(jnp.int32), 0))


def ref_embedding_bag(table: jax.Array, indices: jax.Array,
                      combine: str = "sum") -> jax.Array:
    """(V,D) table, (B,n_hot) indices (−1 pad) → (B,D)."""
    b, h = indices.shape
    valid = indices >= 0
    safe = jnp.clip(indices, 0, table.shape[0] - 1)
    rows = jnp.take(table, safe.reshape(-1), axis=0).reshape(b, h, -1)
    rows = jnp.where(valid[..., None], rows.astype(jnp.float32), 0.0)
    out = rows.sum(axis=1)
    if combine == "mean":
        out = out / jnp.maximum(valid.sum(1, keepdims=True), 1)
    return out.astype(table.dtype)
