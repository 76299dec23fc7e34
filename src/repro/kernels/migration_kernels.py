"""Fused Pallas kernels for the xDGP superstep hot path (DESIGN.md §9).

The per-iteration cost of the paper's adaptive loop is scoring: for every
vertex, a histogram of its neighbours' partition labels (paper §3.2) —
``counts = A @ one_hot(labels)`` on the BSR-tiled adjacency — followed by
the greedy target selection and Bernoulli damping. This module fuses those
three stages into one kernel pass over the BSR tiles:

  * **histogram** — 128×128 (or smaller) adjacency tiles stream through the
    MXU; the one-hot of the column block's labels is built *inside* the
    kernel, so the (n, k) one-hot never materialises in HBM.
  * **score**     — the epilogue (last tile of each row block) computes the
    capacity-relevant gain ``best − current`` and the greedy target with
    either tie-break rule, reading the accumulated counts from VMEM.
  * **select**    — the Bernoulli(s) damping gate and liveness mask are
    applied in the same epilogue, emitting the per-vertex ``willing`` flag
    that feeds the quota stage.

The quota stage itself (paper §3.3) stays outside the kernel by design: it
is the paper's O(k) *global* coordination step (a k-vector of free
capacities), not a per-vertex sparse reduction.

Execution is selected by ``repro.compat.pallas_executor()``:

  * ``"native"``    — Mosaic-compiled on TPU.
  * ``"interpret"`` — the same kernel body under ``interpret=True``
    (bit-faithful; the CPU parity CI forces this).
  * ``"jax"``       — the fused pure-jax oracle (``kernels/ref.py`` +
    the ELL/flat histogram below); the CPU default.

All executors produce bit-identical results to the unfused reference path
in ``core/migration.py`` — partition counts are exact integers in float32,
the RNG draws are shared, and argmax tie handling matches ``jnp.argmax``
(first index). ``tests/test_migration_kernels.py`` holds this parity as a
property over random BSR graphs, padded/empty tiles and full partitions.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.graph.structure import Graph
from repro.kernels import ref
from repro.kernels.bsr_spmm import max_tiles_per_row
from repro.obs.trace import NULL_TRACER
from repro.scale.chunked_bsr import MemoryBudgetError, graph_to_bsr_chunked



# ---------------------------------------------------------------------------
# Plan: the host-packed view of the graph the kernels run over
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MigrationPlan:
    """Pre-packed adjacency for the fused scorer (host-built, reused across
    iterations on a fixed graph — converge/adapt amortise one pack over the
    whole superstep).

    kind:
      "flat" — no packing; the scorer scatters into flattened
               ``dst*k + label`` bins straight from the padded COO graph.
               The only kind that needs no host work, so it is what the
               streaming path uses (the graph changes every superstep).
      "ell"  — padded neighbour lists ``(n_cap, deg_cap)``; turns the
               histogram into dense gather+compare (the CPU winner on
               low-skew graphs like the paper's FEM meshes).
      "bsr"  — the BSR tiles from ``graph_to_bsr_chunked``; what the Pallas
               kernel streams through the MXU (``native``/``interpret``),
               built only when the pack fits the device-memory budget.
    """

    kind: str
    nbrs: Optional[jax.Array] = None          # ("ell") (n_cap, deg_cap) int32
    blocks: Optional[jax.Array] = None        # ("bsr") (nnzb_cap, blk, blk)
    block_cols: Optional[jax.Array] = None    # ("bsr") (nnzb_cap,)
    row_ptr: Optional[jax.Array] = None       # ("bsr") (n_blocks + 1,)
    max_per_row: int = 1                      # ("bsr") static inner grid extent


jax.tree_util.register_dataclass(
    MigrationPlan,
    data_fields=("nbrs", "blocks", "block_cols", "row_ptr"),
    meta_fields=("kind", "max_per_row"))

FLAT_PLAN = MigrationPlan(kind="flat")

# Share of the default device's memory a packed BSR plan may take; the rest
# holds the graph, the session state and the scorer's temporaries.
PLAN_MEMORY_FRACTION = 0.25
# Budget where the device reports no memory limit (the CPU backend).
_UNREPORTED_PLAN_BUDGET = 2 << 30


def plan_memory_budget() -> int:
    """Bytes a BSR plan may take on the default device."""
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    return int(limit * PLAN_MEMORY_FRACTION) if limit else \
        _UNREPORTED_PLAN_BUDGET


def describe_plan(plan: MigrationPlan) -> dict:
    """The plan's kind and packed shape, for snapshots and run logs."""
    if plan.kind == "bsr":
        nnzb, blk, _ = plan.blocks.shape
        return {"kind": "bsr", "blk": int(blk), "nnzb": int(nnzb),
                "max_per_row": int(plan.max_per_row)}
    if plan.kind == "ell":
        return {"kind": "ell", "deg_cap": int(plan.nbrs.shape[1])}
    return {"kind": plan.kind}


def build_plan(graph: Graph, *, executor: Optional[str] = None,
               blk: int = 64, ell_max_overhead: float = 4.0,
               tracer: Any = NULL_TRACER) -> MigrationPlan:
    """Pack ``graph`` for the fused scorer (host-side numpy).

    ``executor`` (default: :func:`repro.compat.pallas_executor`) picks the
    representation: BSR tiles for the Pallas executors, ELL neighbour lists
    for the pure-jax oracle. Either degrades to the packing-free "flat"
    plan (still fused) when packing would not pay: a BSR pack larger than
    :func:`plan_memory_budget` is refused before it is allocated, and
    degree skew that would pad ELL beyond
    ``ell_max_overhead``× the edge count is not packed. Every read of a
    device array back to the host is counted by ``tracer`` (site ``plan``).
    """
    executor = compat.pallas_executor() if executor is None else executor
    read = tracer.host_read
    if executor in ("native", "interpret"):
        try:
            bsr = graph_to_bsr_chunked(graph, blk=blk,
                                       memory_budget=plan_memory_budget(),
                                       tracer=tracer)
        except MemoryBudgetError:
            return FLAT_PLAN                  # the tiles would not fit
        return MigrationPlan(
            kind="bsr", blocks=bsr.blocks, block_cols=bsr.block_cols,
            row_ptr=bsr.row_ptr,
            max_per_row=max_tiles_per_row(read(np.asarray, bsr.row_ptr,
                                               "plan")))
    em = read(np.asarray, graph.edge_mask, "plan")
    s = read(np.asarray, graph.src, "plan")[em].astype(np.int64)
    d = read(np.asarray, graph.dst, "plan")[em].astype(np.int64)
    src2 = np.concatenate([s, d])
    dst2 = np.concatenate([d, s])
    n_cap = graph.n_cap
    deg = np.bincount(dst2, minlength=n_cap)
    deg_cap = int(max(deg.max() if deg.size else 0, 1))
    if n_cap * deg_cap > ell_max_overhead * max(src2.shape[0], 1):
        return FLAT_PLAN                      # high skew: padding would blow up
    order = np.argsort(dst2, kind="stable")
    starts = np.zeros(n_cap + 1, dtype=np.int64)
    np.cumsum(deg, out=starts[1:])
    slot = np.arange(src2.shape[0]) - starts[dst2[order]]
    nbrs = np.full((n_cap, deg_cap), -1, dtype=np.int32)
    nbrs[dst2[order], slot] = src2[order].astype(np.int32)
    return MigrationPlan(kind="ell", nbrs=jnp.asarray(nbrs))


# ---------------------------------------------------------------------------
# Pure-jax fused histograms (the "jax" executor)
# ---------------------------------------------------------------------------

def _counts_flat(graph: Graph, assignment: jax.Array, k: int) -> jax.Array:
    """Histogram by scattering 1s into flattened ``dst*k + label`` bins —
    the (2E, k) one-hot of the reference path never materialises."""
    n_cap = graph.n_cap
    src2, dst2, mask2 = graph.symmetrized()
    lab = assignment[jnp.clip(src2, 0, n_cap - 1)]
    ok = mask2 & (lab >= 0) & (lab < k)       # one_hot drops out-of-range too
    idx = jnp.where(ok, dst2 * k + lab, n_cap * k)
    c = jax.ops.segment_sum(jnp.ones_like(idx), idx,
                            num_segments=n_cap * k + 1)[: n_cap * k]
    return c.reshape(n_cap, k)


def _counts_ell(nbrs: jax.Array, assignment: jax.Array, k: int) -> jax.Array:
    """Histogram over padded neighbour lists: gather + compare, no scatter."""
    n_cap = nbrs.shape[0]
    valid = nbrs >= 0
    lab = assignment[jnp.clip(nbrs, 0, n_cap - 1)]       # (n_cap, deg_cap)
    onehot = (lab[..., None] == jnp.arange(k, dtype=lab.dtype)) \
        & valid[..., None]
    return jnp.sum(onehot.astype(jnp.int32), axis=1)


# ---------------------------------------------------------------------------
# The fused Pallas kernel ("native"/"interpret" executors)
# ---------------------------------------------------------------------------

def _fused_kernel(row_ptr_ref, cols_ref, a_ref, lab_ref, cur_ref, mask_ref,
                  noise_ref, gate_ref, counts_ref, target_ref, willing_ref,
                  gain_ref, *, k: int, max_per_row: int, tie_break: str):
    # vertices run along lanes: per-vertex blocks are (1, blk) rows and the
    # histogram is accumulated transposed, (k, blk), so no operand needs a
    # lane->sublane relayout
    i = pl.program_id(0)
    j = pl.program_id(1)
    start = row_ptr_ref[i]
    end = row_ptr_ref[i + 1]
    blk = a_ref.shape[-1]
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (k, blk), 0)

    @pl.when(j == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    @pl.when(start + j < end)
    def _accum():
        a = a_ref[0]                                      # (blk, blk) [row, col]
        onehot_t = (lab_ref[0] == iota_k).astype(jnp.float32)  # (k, blk) [j, col]
        # counts^T[j, row] += sum_col onehot_t[j, col] * a[row, col]
        counts_ref[0] += jax.lax.dot_general(
            onehot_t, a, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == max_per_row - 1)
    def _select():
        c = counts_ref[0]                                 # (k, blk) exact ints
        cur = cur_ref[0]                                  # (1, blk)
        mask = mask_ref[0] != 0
        fiota = iota_k.astype(jnp.float32)
        cur_cl = jnp.clip(cur, 0, k - 1)
        cur_count = jnp.sum(jnp.where(iota_k == cur_cl, c, 0.0), axis=0,
                            keepdims=True)
        best = jnp.max(c, axis=0, keepdims=True)
        isolated = (best == 0.0) | ~mask
        if tie_break == "stay":
            first = jnp.min(jnp.where(c == best, fiota, float(k)), axis=0,
                            keepdims=True).astype(jnp.int32)
            stay = (cur_count >= best) | isolated
            tgt = jnp.where(stay, cur_cl, first)
        else:
            score = c + noise_ref[0]
            smax = jnp.max(score, axis=0, keepdims=True)
            first = jnp.min(jnp.where(score == smax, fiota, float(k)), axis=0,
                            keepdims=True).astype(jnp.int32)
            tgt = jnp.where(isolated, cur_cl, first)
        willing = (tgt != cur) & mask & (gate_ref[0] != 0)
        target_ref[0] = tgt
        willing_ref[0] = willing.astype(jnp.int32)
        gain_ref[0] = best - cur_count


@functools.partial(jax.jit, static_argnames=("k", "max_per_row", "tie_break",
                                             "interpret"))
def pallas_score_select(blocks: jax.Array, block_cols: jax.Array,
                        row_ptr: jax.Array, assignment: jax.Array,
                        node_mask: jax.Array, noise: jax.Array,
                        gate: jax.Array, *, k: int, max_per_row: int,
                        tie_break: str = "random", interpret: bool = False
                        ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused histogram+score+select over BSR tiles.

    All per-vertex inputs are padded to ``n_pad = n_blocks * blk`` rows
    (``assignment``/``node_mask``/``gate`` with dead slots, ``noise`` with
    zeros). Returns ``(counts f32, target i32, willing i32, gain f32)`` at
    ``n_pad`` rows; callers slice back to ``n_cap``. Padding tiles
    (``block_cols == -1``) are never visited: ``row_ptr`` only addresses
    the packed prefix, and the ``start + j < end`` guard masks the rest.

    Per-vertex operands are laid out ``(n_blocks, rows, blk)`` with the
    vertex on the last (lane) axis, so every block's last two dimensions
    are the array's own — the TPU tiling rule for blocks that are not
    multiples of (8, 128).
    """
    nnzb, blk, _ = blocks.shape
    n_blocks = row_ptr.shape[0] - 1
    lab_b = assignment.reshape(n_blocks, 1, blk)
    mask_b = node_mask.astype(jnp.int32).reshape(n_blocks, 1, blk)
    noise_b = noise.reshape(n_blocks, blk, k).transpose(0, 2, 1)
    gate_b = gate.astype(jnp.int32).reshape(n_blocks, 1, blk)

    def a_index(i, j, row_ptr_s, cols_s):
        return (jnp.clip(row_ptr_s[i] + j, 0, nnzb - 1), 0, 0)

    def col_index(i, j, row_ptr_s, cols_s):
        idx = jnp.clip(row_ptr_s[i] + j, 0, nnzb - 1)
        return (jnp.clip(cols_s[idx], 0, n_blocks - 1), 0, 0)

    def row_index(i, j, row_ptr_s, cols_s):
        return (i, 0, 0)

    row = pl.BlockSpec((1, 1, blk), row_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks, max_per_row),
        in_specs=[
            pl.BlockSpec((1, blk, blk), a_index),
            pl.BlockSpec((1, 1, blk), col_index),
            row,
            row,
            pl.BlockSpec((1, k, blk), row_index),
            row,
        ],
        out_specs=[pl.BlockSpec((1, k, blk), row_index), row, row, row],
    )
    counts, target, willing, gain = pl.pallas_call(
        functools.partial(_fused_kernel, k=k, max_per_row=max_per_row,
                          tie_break=tie_break),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, k, blk), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, 1, blk), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, 1, blk), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, 1, blk), jnp.float32),
        ],
        interpret=interpret,
        name="pallas_score_select",
    )(row_ptr, block_cols, blocks, lab_b, lab_b, mask_b, noise_b, gate_b)
    n_pad = n_blocks * blk
    return (counts.transpose(0, 2, 1).reshape(n_pad, k), target.reshape(n_pad),
            willing.reshape(n_pad), gain.reshape(n_pad))


# ---------------------------------------------------------------------------
# Dispatch: one fused score/select entry point for every executor
# ---------------------------------------------------------------------------

def _pad_rows(x: jax.Array, n_pad: int, fill) -> jax.Array:
    pad = n_pad - x.shape[0]
    if pad == 0:
        return x
    widths = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


def score_select(graph: Graph, plan: Optional[MigrationPlan],
                 assignment: jax.Array, node_mask: jax.Array,
                 noise: jax.Array, gate: jax.Array, k: int, *,
                 tie_break: str = "random", executor: Optional[str] = None,
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused scoring for one migration iteration: neighbour-label histogram,
    greedy target selection, damping — one pass, executor-dispatched.

    Returns ``(counts i32, target i32, willing bool, gain f32)`` at
    ``n_cap`` rows, bit-identical across executors and to the unfused
    reference path (``core.migration.neighbour_partition_counts`` +
    ``greedy_targets`` + the Bernoulli gate).
    """
    executor = compat.pallas_executor() if executor is None else executor
    plan = FLAT_PLAN if plan is None else plan
    n_cap = graph.n_cap
    if plan.kind == "bsr" and executor in ("native", "interpret"):
        n_pad = (plan.row_ptr.shape[0] - 1) * plan.blocks.shape[1]
        counts, target, willing, gain = pallas_score_select(
            plan.blocks, plan.block_cols, plan.row_ptr,
            _pad_rows(assignment, n_pad, -1),
            _pad_rows(node_mask, n_pad, False),
            _pad_rows(noise, n_pad, 0.0),
            _pad_rows(gate, n_pad, False),
            k=k, max_per_row=plan.max_per_row, tie_break=tie_break,
            interpret=executor == "interpret")
        return (counts[:n_cap].astype(jnp.int32), target[:n_cap],
                willing[:n_cap].astype(bool), gain[:n_cap])
    if plan.kind == "ell":
        counts = _counts_ell(plan.nbrs, assignment, k)
    elif plan.kind == "bsr":          # BSR plan but jax executor: use oracle
        counts = ref.ref_bsr_label_histogram(
            plan.blocks, plan.block_cols, plan.row_ptr,
            _pad_rows(assignment, (plan.row_ptr.shape[0] - 1)
                      * plan.blocks.shape[1], -1),
            k)[:n_cap].astype(jnp.int32)
    else:
        counts = _counts_flat(graph, assignment, k)
    target, willing, gain = ref.ref_score_select(
        counts, assignment, node_mask, noise, gate, tie_break=tie_break)
    return counts, target, willing, gain


def label_histogram(graph: Graph, plan: Optional[MigrationPlan],
                    assignment: jax.Array, k: int, *,
                    executor: Optional[str] = None) -> jax.Array:
    """Per-vertex neighbour-label histogram alone (diagnostics/tests):
    ``counts[v, j]`` = number of v's live neighbours with label j."""
    n_cap = graph.n_cap
    counts, _, _, _ = score_select(
        graph, plan, assignment, jnp.ones((n_cap,), bool),
        jnp.zeros((n_cap, k), jnp.float32), jnp.zeros((n_cap,), bool), k,
        tie_break="stay", executor=executor)
    return counts
