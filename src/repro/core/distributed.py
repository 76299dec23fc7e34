"""Distributed xDGP engine: shard_map over a device mesh.

Paper ↔ SPMD mapping (see DESIGN.md §2):

  worker/JVM            → device; partition p ≡ node-slot block p (k == P)
  vertex objects        → rows of sharded feature / assignment arrays
  capacity messages     → ``jax.lax.psum`` of a k-vector (O(k) traffic, the
                          paper's scalability argument verbatim)
  neighbour messages    → halo exchange: each device ``all_gather``s only the
                          *boundary segment* of every block; cut edges decide
                          how large that segment must be, so partition quality
                          IS the collective volume (roofline collective term)
  deferred migration    → pending committed next superstep; the physical move
                          is the block-permuted relocation (all_to_all)

The engine keeps every shape static: edges are bucketed per destination
device and padded to the max bucket; the halo is padded to the max boundary
(optionally with head-room, see ``halo_pad``).

Two migration engines share the bucketing/halo machinery:

* ``make_distributed_migrator`` — the pure O(k)-message engine: per-block
  quota ranking, per-device RNG streams. Decentralised exactly like the
  paper, but its trajectories differ from the single-host heuristic.
* ``make_cluster_migrator`` — the *parity* engine behind the ``"sharded"``
  ``ExecutionBackend`` (DESIGN.md §10): a bit-exact SPMD mirror of
  ``core.migration.migrate_step``. RNG draws are made in the session's
  original slot order, quota ranking is a global order recovered from one
  all_gather of packed rank keys, and the capacity vector is psum'd —
  so a cluster session produces bit-identical assignments to a local one.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.structure import Graph

# Trace-time counters: bumped inside jitted function *bodies*, so they count
# traces (→ compiles), not calls. The compile-cache tests assert on these;
# the sharded backend's whole performance story is that after warmup these
# stop moving (DESIGN.md §10).
TRACE_COUNTS = {"cluster_step": 0}


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DistGraph:
    """Device-bucketed graph. Leading axis of every field = device axis P.

    Edge endpoints are encoded for halo addressing:
      src_owner (P,E): owning device of the edge source
      src_slot  (P,E): slot of the source *within its owner's boundary segment*
                       if remote, or within the local block if local
      src_local (P,E): bool — source lives on this device
      dst_local (P,E): destination slot within the local block
      edge_ok   (P,E): validity mask
      boundary  (P,B): local slots exported to other devices (halo source),
                       padded with 0 and masked by boundary_ok
    """

    src_owner: jax.Array
    src_slot: jax.Array
    src_local: jax.Array
    dst_local: jax.Array
    edge_ok: jax.Array
    boundary: jax.Array
    boundary_ok: jax.Array
    node_ok: jax.Array        # (P, n_blk) live-node mask per block

    @property
    def num_devices(self) -> int:
        return self.src_owner.shape[0]

    @property
    def block_size(self) -> int:
        return self.node_ok.shape[1]

    @property
    def halo_size(self) -> int:
        return self.boundary.shape[1]


@dataclasses.dataclass(frozen=True)
class BlockLayout:
    """Host-side mapping between session slot space and device-block space.

    The cluster engine stores vertices in partition-per-device blocks while
    the session keeps its canonical arrays in the original slot order; this
    is the dictionary between the two (the cluster migrator turns it into
    device-side gathers, so per-iteration conversion never touches the
    host).
    """

    perm: np.ndarray        # (n_cap,) new-slot-order -> old id (lexsort order)
    new_global: np.ndarray  # (n_cap,) old id -> block-space slot (-1 = dead)
    orig_id: np.ndarray     # (P*n_blk,) block-space slot -> old id (-1 = pad)
    n_cap: int
    n_blk: int
    num_devices: int


def build_dist_graph(graph: Graph, assignment: np.ndarray, num_devices: int,
                     block_size: Optional[int] = None,
                     ) -> Tuple[DistGraph, np.ndarray]:
    """Host-side bucketing of a partitioned graph onto P devices.

    Nodes are permuted so partition p occupies block p (the "vertex
    migration" materialised). Returns (DistGraph, perm) where perm maps
    new global slot -> old node id. (Compat surface over
    ``build_cluster_graph``, which additionally returns the full layout.)
    """
    dg, layout = build_cluster_graph(graph, assignment, num_devices,
                                     block_size=block_size)
    return dg, layout.perm


def _grow(need: int, floor: int, pad: float) -> int:
    """Padded-bucket growth policy (DESIGN.md §10).

    Reuse the previous size while the need fits (shape-stable: the jit
    executable keyed on it stays valid); on genuine growth jump by a
    fractional head-room so the next few supersteps fit too — O(log) shape
    buckets over a stream instead of one per superstep.
    """
    if need <= floor:
        return floor
    return max(need, int(np.ceil(need * (1.0 + pad))))


def build_cluster_graph(graph: Graph, assignment: np.ndarray, num_devices: int,
                        *, block_size: Optional[int] = None,
                        halo_pad: float = 0.0,
                        block_pad: float = 0.0, edge_pad: float = 0.0,
                        min_block: int = 0, min_edges: int = 0,
                        min_halo: int = 0,
                        ) -> Tuple[DistGraph, "BlockLayout"]:
    """Bucketing + halo build behind the backend interface.

    ``halo_pad`` is the halo padding policy: fractional head-room added on
    top of the largest boundary segment, so that all devices exchange the
    same (padded) halo volume and a later engine could grow boundaries
    without an immediate rebuild. ``block_pad`` / ``edge_pad`` are the
    sibling policies for the node-block and edge-bucket dimensions, and the
    ``min_*`` floors carry the previous build's shapes so a streaming
    rebuild keeps them unless the graph genuinely outgrew them — shape
    stability is what lets the backend reuse one compiled step across
    rebuilds instead of re-jitting every superstep.
    """
    if halo_pad < 0:
        raise ValueError(f"halo_pad must be >= 0, got {halo_pad}")
    if block_pad < 0 or edge_pad < 0:
        raise ValueError(f"block_pad/edge_pad must be >= 0, got "
                         f"{block_pad}/{edge_pad}")
    P = num_devices
    assignment = np.asarray(assignment)
    node_mask = np.asarray(graph.node_mask)
    n_cap = node_mask.shape[0]

    # --- permute nodes into partition blocks (stable: live first) --------
    order = np.lexsort((np.arange(n_cap), ~node_mask, assignment))
    perm = order                                   # new slot -> old id
    counts = np.bincount(assignment[node_mask], minlength=P)
    if block_size:
        n_blk = int(block_size)
    else:
        n_blk = _grow(int(max(1, counts.max())), min_block, block_pad)
    over = np.flatnonzero(counts > n_blk)
    if over.size:
        p = int(over[0])
        raise ValueError(f"partition {p} has {counts[p]} nodes > block {n_blk}")
    # per-partition compaction: slot within block — the lexsort already
    # groups each partition's live nodes contiguously in original-id order,
    # so a searchsorted over the sorted labels yields every in-block slot
    sorted_live = node_mask[order]
    live_pos = np.flatnonzero(sorted_live)
    lab_live = assignment[order][live_pos]          # non-decreasing
    ids_live = order[live_pos]
    p_starts = np.searchsorted(lab_live, np.arange(P))
    new_global = np.full(n_cap, -1, dtype=np.int64)
    new_global[ids_live] = (lab_live * n_blk
                            + np.arange(live_pos.size) - p_starts[lab_live])
    live_ids = np.flatnonzero(node_mask)
    assert (new_global[live_ids] >= 0).all()

    # --- symmetrised live edges in new coordinates ------------------------
    em = np.asarray(graph.edge_mask)
    s = np.asarray(graph.src)[em]
    d = np.asarray(graph.dst)[em]
    s2 = np.concatenate([s, d]).astype(np.int64)
    d2 = np.concatenate([d, s]).astype(np.int64)
    gs = new_global[s2]
    gd = new_global[d2]
    src_dev, src_off = gs // n_blk, gs % n_blk
    dst_dev, dst_off = gd // n_blk, gd % n_blk

    # --- boundary sets: local slots referenced by remote edges ------------
    # one sorted unique over packed (dev, off) keys replaces the per-device
    # set builds + the (dev, off) -> halo-index dict
    cut = src_dev != dst_dev
    b_uniq = np.unique(src_dev[cut] * n_blk + src_off[cut])   # sorted keys
    b_dev = b_uniq // n_blk
    b_counts = np.bincount(b_dev, minlength=P) if P else np.zeros(0, np.int64)
    b_starts = np.searchsorted(b_dev, np.arange(P))
    b_max = int(b_counts.max()) if P else 1
    B = _grow(max(1, b_max), min_halo, halo_pad)
    boundary = np.zeros((P, B), dtype=np.int32)
    boundary_ok = np.zeros((P, B), dtype=bool)
    b_pos = np.arange(b_uniq.size) - b_starts[b_dev]
    boundary[b_dev, b_pos] = b_uniq % n_blk
    boundary_ok[b_dev, b_pos] = True

    # --- bucket edges by destination device --------------------------------
    e_counts = np.bincount(dst_dev, minlength=P) if P else np.zeros(0, np.int64)
    E = _grow(int(max(1, e_counts.max())) if P else 1, min_edges, edge_pad)
    src_owner = np.zeros((P, E), dtype=np.int32)
    src_slot = np.zeros((P, E), dtype=np.int32)
    src_local = np.zeros((P, E), dtype=bool)
    dst_local = np.zeros((P, E), dtype=np.int32)
    edge_ok = np.zeros((P, E), dtype=bool)
    # stable sort keeps each bucket in original edge order, matching the
    # per-device flatnonzero scan this replaces bit for bit
    e_order = np.argsort(dst_dev, kind="stable")
    e_dev = dst_dev[e_order]
    e_pos = np.arange(e_order.size) - np.searchsorted(e_dev, np.arange(P))[e_dev]
    loc = (src_dev == dst_dev)[e_order]
    # halo index of a remote source = rank of its packed key within its
    # owner's boundary set (valid only where ~loc; masked by the where)
    halo_of = (np.searchsorted(b_uniq, (src_dev * n_blk + src_off)[e_order])
               - b_starts[src_dev[e_order]])
    src_owner[e_dev, e_pos] = src_dev[e_order]
    src_slot[e_dev, e_pos] = np.where(loc, src_off[e_order], halo_of)
    src_local[e_dev, e_pos] = loc
    dst_local[e_dev, e_pos] = dst_off[e_order]
    edge_ok[e_dev, e_pos] = True

    node_ok = np.arange(n_blk)[None, :] < counts[:, None]

    dg = DistGraph(
        src_owner=jnp.asarray(src_owner), src_slot=jnp.asarray(src_slot),
        src_local=jnp.asarray(src_local), dst_local=jnp.asarray(dst_local),
        edge_ok=jnp.asarray(edge_ok), boundary=jnp.asarray(boundary),
        boundary_ok=jnp.asarray(boundary_ok), node_ok=jnp.asarray(node_ok))
    orig_id = np.full((P * n_blk,), -1, np.int64)
    orig_id[new_global[live_ids]] = live_ids
    layout = BlockLayout(perm=perm, new_global=new_global, orig_id=orig_id,
                         n_cap=n_cap, n_blk=n_blk, num_devices=P)
    return dg, layout


# ---------------------------------------------------------------------------
# shard_map programs (mesh axis name: "nodes")
# ---------------------------------------------------------------------------

AXIS = "nodes"


def _halo_exchange(local_feat: jax.Array, dg_local: DistGraph,
                   axis: str = AXIS) -> jax.Array:
    """all_gather of every device's boundary segment → (P*B, d) halo buffer.

    Collective volume per device = P·B·d — proportional to the cut, which is
    what the adaptive heuristic minimises.
    """
    bnd = local_feat[dg_local.boundary[0]]              # (B, d)
    bnd = jnp.where(dg_local.boundary_ok[0][:, None], bnd, 0)
    halo = jax.lax.all_gather(bnd, axis, tiled=True)     # (P*B, d)
    return halo


def superstep_shard(local_feat: jax.Array, dg_local: DistGraph,
                    halo_size: int, combine: str = "sum") -> jax.Array:
    """One distributed neighbour aggregation for a (n_blk, d) feature block."""
    halo = _halo_exchange(local_feat, dg_local)
    src_owner = dg_local.src_owner[0]
    src_slot = dg_local.src_slot[0]
    src_local = dg_local.src_local[0]
    dst_local = dg_local.dst_local[0]
    edge_ok = dg_local.edge_ok[0]
    halo_idx = src_owner * halo_size + src_slot
    feat_remote = halo[jnp.clip(halo_idx, 0, halo.shape[0] - 1)]
    feat_local = local_feat[src_slot]
    feat_src = jnp.where(src_local[:, None], feat_local, feat_remote)
    feat_src = jnp.where(edge_ok[:, None], feat_src, 0)
    n_blk = local_feat.shape[0]
    seg = jnp.where(edge_ok, dst_local, n_blk)
    agg = jax.ops.segment_sum(feat_src, seg, num_segments=n_blk + 1)[:n_blk]
    return agg


def make_distributed_aggregate(mesh: jax.sharding.Mesh, dg: DistGraph):
    """Returns jit'd (features -> aggregated neighbour sum) over the mesh."""
    P = dg.num_devices
    halo = dg.halo_size
    spec = jax.sharding.PartitionSpec(AXIS)
    dg_specs = DistGraph(*([spec] * 8))  # all fields sharded on leading axis

    @jax.jit
    def agg_fn(features: jax.Array) -> jax.Array:
        f = jax.shard_map(
            lambda lf, dgl: superstep_shard(lf, dgl, halo),
            mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(AXIS, None), dg_specs),
            out_specs=jax.sharding.PartitionSpec(AXIS, None),
        )
        flat = features.reshape(P * dg.block_size, -1)
        return f(flat, dg).reshape(features.shape)

    return agg_fn


def migrate_step_shard(assignment_blk: jax.Array, pending_blk: jax.Array,
                       rng_blk: jax.Array, dg_local: DistGraph,
                       capacity: jax.Array, k: int, halo_size: int,
                       s: float = 0.5) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One adaptive-migration iteration per device block (k == P).

    The label halo plays the role of the paper's neighbour-location
    knowledge; the psum'd occupancy vector is the capacity message.
    Because partition i is device i, quota ranking of partition i's movers
    is fully local — the paper's decentralisation argument holds exactly.
    """
    my = jax.lax.axis_index(AXIS)
    node_ok = dg_local.node_ok[0]
    # COMMIT
    assignment_blk = jnp.where(pending_blk >= 0, pending_blk, assignment_blk)
    # label halo exchange (int32 labels travel as-is: no float32 round-trip,
    # precision-safe for label spaces beyond 2^24)
    halo = _halo_exchange(assignment_blk[:, None], dg_local)[:, 0]
    src_owner = dg_local.src_owner[0]
    src_slot = dg_local.src_slot[0]
    src_is_local = dg_local.src_local[0]
    dst_local = dg_local.dst_local[0]
    edge_ok = dg_local.edge_ok[0]
    lab_remote = halo[jnp.clip(src_owner * halo_size + src_slot, 0, halo.shape[0] - 1)]
    lab_local = assignment_blk[src_slot]
    lab_src = jnp.where(src_is_local, lab_local, lab_remote)
    n_blk = assignment_blk.shape[0]
    seg = jnp.where(edge_ok, dst_local, n_blk)
    onehot = jax.nn.one_hot(lab_src, k, dtype=jnp.int32) * edge_ok[:, None]
    counts = jax.ops.segment_sum(onehot, seg, num_segments=n_blk + 1)[:n_blk]
    # DECIDE (random tie-break) + DAMP
    # (rng_blk is replicated; fold in the device id for per-device randomness
    #  but return a device-independent successor key)
    rng, k1, k2 = jax.random.split(rng_blk, 3)
    r1 = jax.random.fold_in(k1, my)
    r2 = jax.random.fold_in(k2, my)
    noise = jax.random.uniform(r1, counts.shape)
    target = jnp.argmax(counts.astype(jnp.float32) + noise, axis=1).astype(jnp.int32)
    isolated = jnp.max(counts, axis=1) == 0
    target = jnp.where(isolated | ~node_ok, assignment_blk, target)
    wants = (target != assignment_blk) & node_ok
    gate = jax.random.bernoulli(r2, s, wants.shape)
    willing = wants & gate
    # CAPACITY psum (k-vector, the paper's worker-to-worker message)
    occ_local = jax.ops.segment_sum(node_ok.astype(jnp.int32),
                                    jnp.where(node_ok, assignment_blk, k),
                                    num_segments=k + 1)[:k]
    occ = jax.lax.psum(occ_local, AXIS)
    free = jnp.maximum(capacity - occ, 0)
    # Paper's Q^{i,j} assumes partition i lives wholly on worker i; with
    # deferred physical relocation a partition's vertices can span several
    # storage blocks, so the per-block quota must bound the TOTAL influx:
    # free // P guarantees sum over blocks ≤ free for any label placement.
    n_blocks = jax.lax.axis_size(AXIS)
    quota = free // jnp.maximum(n_blocks, 1)
    # QUOTA: local ranking of this block's movers per destination
    tgt_safe = jnp.clip(target, 0, k - 1)
    order = jnp.argsort(jnp.where(willing, tgt_safe, k + 1))
    sorted_t = jnp.where(willing, tgt_safe, k + 1)[order]
    pos = jnp.arange(n_blk, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones((1,), bool), sorted_t[1:] != sorted_t[:-1]])
    run_start = jax.lax.associative_scan(jnp.maximum, jnp.where(is_start, pos, 0))
    rank_sorted = pos - run_start
    rank = jnp.zeros((n_blk,), jnp.int32).at[order].set(rank_sorted)
    admitted = willing & (rank < quota[tgt_safe])
    pending = jnp.where(admitted, target, jnp.int32(-1))
    return assignment_blk, pending, rng


def make_distributed_migrator(mesh: jax.sharding.Mesh, dg: DistGraph, k: int,
                              s: float = 0.5):
    """jit'd distributed migration step over the mesh (k == P required)."""
    P = dg.num_devices
    if k != P:
        raise ValueError(f"distributed engine requires k == num_devices ({k} != {P})")
    halo = dg.halo_size
    spec_n = jax.sharding.PartitionSpec(AXIS)
    dg_specs = DistGraph(*([spec_n] * 8))

    @jax.jit
    def step(assignment: jax.Array, pending: jax.Array, rng: jax.Array,
             capacity: jax.Array):
        f = jax.shard_map(
            partial(migrate_step_shard, k=k, halo_size=halo, s=s),
            mesh=mesh,
            in_specs=(spec_n, spec_n, jax.sharding.PartitionSpec(), dg_specs,
                      jax.sharding.PartitionSpec()),
            out_specs=(spec_n, spec_n, jax.sharding.PartitionSpec()),
        )
        return f(assignment, pending, rng, dg, capacity)

    return step


# ---------------------------------------------------------------------------
# Parity engine: bit-exact SPMD mirror of core.migration.migrate_step
# (the execution layer behind repro.api's "sharded" backend, DESIGN.md §10)
# ---------------------------------------------------------------------------


def rank_key_dtype(k: int, n_cap: int):
    """The narrowest dtype the quota ranking's packed ``group·n_cap +
    orig_id`` keys fit in — int32 while they fit (the historical layout,
    byte-identical on the wire), uint32 out to ~4.3e9 key values (k=8 at
    ~66M vertices without needing x64), int64 beyond that when JAX x64 is
    enabled.  Fails loudly instead of wrapping: a silently aliased key
    would merge two (src, dst) quota groups and admit the wrong movers."""
    span = (k * k) * n_cap + n_cap       # strict upper bound on any key
    if span < 2 ** 31:
        return jnp.int32
    if span < 2 ** 32:
        return jnp.uint32
    if span < 2 ** 63 and jax.dtypes.canonicalize_dtype(jnp.int64) == jnp.int64:
        return jnp.int64
    raise OverflowError(
        f"quota rank keys span {span} values (k={k}, n_cap={n_cap}), which "
        f"overflows uint32 and JAX x64 is disabled — enable jax_enable_x64 "
        f"or reduce n_cap")


def cluster_migrate_shard(assignment_blk: jax.Array, pending_blk: jax.Array,
                          noise_blk: jax.Array, gate_blk: jax.Array,
                          orig_blk: jax.Array, dg_local: DistGraph,
                          capacity: jax.Array, *, k: int, halo_size: int,
                          n_cap: int, tie_break: str, axis: str = AXIS,
                          key_dtype=jnp.int32,
                          ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                     jax.Array, jax.Array]:
    """One adaptive iteration per device block — decision-identical to the
    single-host ``migrate_step`` (commit → score → decide → damp → quota →
    defer), with the distribution showing only in *where* terms come from:

      neighbour labels      → boundary-segment halo exchange (all_gather)
      occupancy/capacity    → psum of a k-vector (the paper's O(k) message)
      quota ranking         → the single-host rank orders movers of a
                              (src, dst) pair by original slot id; that order
                              is recovered exactly from one all_gather of
                              packed ``group · n_cap + orig_id`` keys

    ``noise_blk``/``gate_blk`` are the *same* RNG draws the local step makes
    (drawn over the original slot space and scattered into blocks by the
    caller), so damping and tie-breaking match draw for draw.
    """
    node_ok = dg_local.node_ok[0]
    # ---- 1. COMMIT deferred migrations from t-1 -------------------------
    has_pending = pending_blk >= 0
    assignment_blk = jnp.where(has_pending, pending_blk, assignment_blk)
    committed = jax.lax.psum(
        jnp.sum(has_pending & node_ok).astype(jnp.int32), axis)

    # ---- 2. SCORE: neighbour-label histogram via the label halo ----------
    # int32 labels exchanged directly (no float32 round-trip on the hot path)
    halo = _halo_exchange(assignment_blk[:, None], dg_local, axis)[:, 0]
    src_owner = dg_local.src_owner[0]
    src_slot = dg_local.src_slot[0]
    src_is_local = dg_local.src_local[0]
    dst_local = dg_local.dst_local[0]
    edge_ok = dg_local.edge_ok[0]
    lab_remote = halo[jnp.clip(src_owner * halo_size + src_slot,
                               0, halo.shape[0] - 1)]
    lab_src = jnp.where(src_is_local, assignment_blk[src_slot], lab_remote)
    n_blk = assignment_blk.shape[0]
    seg = jnp.where(edge_ok, dst_local, n_blk)
    onehot = jax.nn.one_hot(lab_src, k, dtype=jnp.int32) * edge_ok[:, None]
    counts = jax.ops.segment_sum(onehot, seg, num_segments=n_blk + 1)[:n_blk]

    # ---- 3. DECIDE (same rule, expressions and dtypes as greedy_targets) --
    best_count = jnp.max(counts, axis=1)
    cur = jnp.clip(assignment_blk, 0, k - 1)
    isolated = (best_count == 0) | ~node_ok
    if tie_break == "stay":
        cur_count = jnp.take_along_axis(counts, cur[:, None], axis=1)[:, 0]
        stay = (cur_count >= best_count) | isolated
        target = jnp.where(stay, cur,
                           jnp.argmax(counts, axis=1).astype(jnp.int32))
    else:                                   # "random" (validated by caller)
        score = counts.astype(jnp.float32) + noise_blk
        target = jnp.argmax(score, axis=1).astype(jnp.int32)
        target = jnp.where(isolated, cur, target)
    wants_move = (target != assignment_blk) & node_ok

    # ---- 4. DAMP (the session's own Bernoulli(s) draw, pre-scattered) ----
    willing = wants_move & gate_blk
    n_willing = jax.lax.psum(jnp.sum(willing).astype(jnp.int32), axis)

    # ---- 5. QUOTA: psum'd occupancy + globally-ordered ranking -----------
    occ_local = jax.ops.segment_sum(
        node_ok.astype(jnp.int32),
        jnp.where(node_ok, assignment_blk, k), num_segments=k + 1)[:k]
    occ = jax.lax.psum(occ_local, axis)
    free = jnp.maximum(capacity - occ, 0)
    quota = free // jnp.maximum(k - 1, 1)
    src_part = jnp.clip(assignment_blk, 0, k - 1)
    tgt_safe = jnp.clip(target, 0, k - 1)
    group = src_part * k + tgt_safe
    # keys pack (src, dst, orig slot) into one integer; the dtype is chosen
    # by rank_key_dtype so the packing can never silently wrap at scale
    big = jnp.iinfo(key_dtype).max
    group_base = group.astype(key_dtype) * jnp.asarray(n_cap, key_dtype)
    key = jnp.where(willing, group_base + orig_blk.astype(key_dtype), big)
    all_keys = jnp.sort(jax.lax.all_gather(key, axis, tiled=True))
    # rank within (i, j) group in original slot order: position of my key
    # among all active keys minus the position where my group begins
    rank = (jnp.searchsorted(all_keys, key)
            - jnp.searchsorted(all_keys, group_base)).astype(jnp.int32)
    admitted = willing & (rank < quota[tgt_safe])
    n_admitted = jax.lax.psum(jnp.sum(admitted).astype(jnp.int32), axis)

    # ---- 6. DEFER ---------------------------------------------------------
    pending = jnp.where(admitted, target, jnp.int32(-1))
    return assignment_blk, pending, committed, n_willing, n_admitted


def layout_device_arrays(layout: BlockLayout
                         ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                    jax.Array]:
    """The four scatter/gather arrays a cluster step consumes, as device
    arrays: ``(blk_live, orig, ng_safe, slot_live)``. They are jit
    *arguments* of ``make_cluster_step`` (not closure constants), so a
    rebuilt layout with the same shapes reuses the compiled executable.
    """
    blk_live = jnp.asarray(layout.orig_id >= 0)
    orig = jnp.asarray(np.maximum(layout.orig_id, 0), jnp.int32)
    slot_live = jnp.asarray(layout.new_global >= 0)
    ng_safe = jnp.asarray(
        np.clip(layout.new_global, 0, layout.orig_id.shape[0] - 1), jnp.int32)
    return blk_live, orig, ng_safe, slot_live


def make_cluster_step(mesh: jax.sharding.Mesh, *, k: int, n_cap: int,
                      tie_break: str = "random", axis: str = AXIS,
                      key_dtype=None):
    """jit'd parity migration step over the mesh (k == P required).

    Returns ``step(assignment, pending, rng, capacity, s, dg, blk_live,
    orig, ng_safe, slot_live) -> (assignment, pending, rng, (committed,
    willing, admitted))`` operating on the session's canonical (n_cap,)
    slot-space arrays: the slot↔block permutation happens as device-side
    gathers inside the one jit program, so an iteration costs no host
    round-trip. Stats are the same integers the local ``migrate_step``
    reports, and successive calls thread the session RNG exactly like the
    local step does (one 3-way split per iteration).

    Everything that changes across streaming rebuilds — the bucketing
    (``dg``), the layout scatter/gather arrays, the damping ``s`` — enters
    as a jit *argument*, so the compiled executable is keyed only on array
    shapes: as long as the padded bucket shapes hold (see ``_grow``), a
    rebuilt graph dispatches straight into the cached executable instead of
    re-tracing every superstep. ``s`` is traced as a weak scalar, so
    different damping values share one executable too (``bernoulli(key, p)``
    is ``uniform(key) < p`` — bitwise-identical to a baked-in constant).
    """
    P = int(np.prod(mesh.devices.shape))
    if k != P:
        raise ValueError(f"cluster engine is partition-per-device: k must "
                         f"equal the device count ({k} != {P})")
    if tie_break not in ("random", "stay"):
        raise ValueError(f"unknown tie_break {tie_break!r}")
    if key_dtype is None:       # widen past int32 as n_cap·k² grows; the
        key_dtype = rank_key_dtype(k, n_cap)   # ranks are dtype-invariant
    spec_n = jax.sharding.PartitionSpec(axis)
    spec_r = jax.sharding.PartitionSpec()
    dg_specs = DistGraph(*([spec_n] * 8))

    @jax.jit
    def step(assignment: jax.Array, pending: jax.Array, rng: jax.Array,
             capacity: jax.Array, s: jax.Array, dg: DistGraph,
             blk_live: jax.Array, orig: jax.Array, ng_safe: jax.Array,
             slot_live: jax.Array):
        # body runs only when jit traces → counts compiles, not dispatches
        TRACE_COUNTS["cluster_step"] += 1
        halo = dg.halo_size                     # static under trace
        orig_safe = jnp.clip(orig, 0, n_cap - 1)
        # scatter slot-space state into blocks (pad slots: stay, no pending)
        assignment_blk = jnp.where(blk_live, assignment[orig_safe], 0)
        pending_blk = jnp.where(blk_live, pending[orig_safe], -1)
        # identical split order and draw shapes to migrate_step: the draws
        # live in ORIGINAL slot space and are scattered into blocks
        rng_next, tie_key, sub = jax.random.split(rng, 3)
        if tie_break == "random":
            noise_blk = jax.random.uniform(tie_key, (n_cap, k))[orig_safe]
        else:
            noise_blk = jnp.zeros((orig.shape[0], k), jnp.float32)
        gate_blk = jax.random.bernoulli(sub, p=s, shape=(n_cap,))[orig_safe]
        f = jax.shard_map(
            partial(cluster_migrate_shard, k=k, halo_size=halo, n_cap=n_cap,
                    tie_break=tie_break, axis=axis, key_dtype=key_dtype),
            mesh=mesh,
            in_specs=(spec_n, spec_n, spec_n, spec_n, spec_n, dg_specs,
                      spec_r),
            out_specs=(spec_n, spec_n, spec_r, spec_r, spec_r),
        )
        a_blk, p_blk, committed, willing, admitted = f(
            assignment_blk, pending_blk, noise_blk, gate_blk, orig, dg,
            capacity)
        # gather back to slot space; dead slots keep their labels (they
        # never migrate locally either) and carry no pending
        a = jnp.where(slot_live, a_blk[ng_safe], assignment)
        p = jnp.where(slot_live, p_blk[ng_safe], -1)
        return a, p, rng_next, (committed, willing, admitted)

    replicated = jax.sharding.NamedSharding(mesh,
                                            jax.sharding.PartitionSpec())

    def step_on_mesh(assignment: jax.Array, pending: jax.Array,
                     rng: jax.Array, capacity: jax.Array, s, dg: DistGraph,
                     blk_live: jax.Array, orig: jax.Array,
                     ng_safe: jax.Array, slot_live: jax.Array):
        # state arrays may still be committed to a previous mesh (local
        # execution, or a pre-rescale device count) — a no-op when already
        # placed here, a copy exactly once after a backend/mesh change.
        # Pinning the placement also pins the jit cache key: every dispatch
        # sees identically-sharded avals.
        args = jax.device_put((assignment, pending, rng, capacity),
                              replicated)
        return step(*args, float(s), dg, blk_live, orig, ng_safe, slot_live)

    step_on_mesh.jitted = step      # the program itself, for AOT lowering
    return step_on_mesh


def make_cluster_migrator(mesh: jax.sharding.Mesh, dg: DistGraph,
                          layout: BlockLayout, k: int, *, s: float = 0.5,
                          tie_break: str = "random", axis: str = AXIS):
    """Compat surface over ``make_cluster_step``: binds one bucketing and a
    fixed ``s`` and returns ``step(assignment, pending, rng, capacity)``.

    The backend no longer uses this (it keys ``make_cluster_step``
    executables by shape signature and threads ``dg``/layout per call); it
    remains for direct callers and the parity tests.
    """
    step = make_cluster_step(mesh, k=k, n_cap=layout.n_cap,
                             tie_break=tie_break, axis=axis)
    mig_args = (dg, *layout_device_arrays(layout))

    def bound_step(assignment: jax.Array, pending: jax.Array,
                   rng: jax.Array, capacity: jax.Array):
        return step(assignment, pending, rng, capacity, s, *mig_args)

    return bound_step


def comm_model(dg: DistGraph, k: int, label_bytes: int = 4) -> dict:
    """Per-iteration communication bill of the cluster engine, per device.

    Derived host-side from the (static) bucketing shapes — the wire volume
    of a shard_map iteration is fully determined by them:

      halo          — each device receives every boundary segment: P·B·b
                      bytes (padded); the *live* fraction is the cut
                      frontier, which is what the heuristic shrinks.
      capacity psum — the paper's O(k) worker message: k·b bytes.
      rank gather   — the quota-parity all_gather: P·n_blk·b bytes (the
                      price of bit-exact global ranking; the pure O(k)
                      engine in ``make_distributed_migrator`` skips it).
    """
    P, B, n_blk = dg.num_devices, dg.halo_size, dg.block_size
    live_boundary = np.asarray(dg.boundary_ok).sum(axis=1).astype(int)
    return {
        "devices": P,
        "halo_slots": B,
        "halo_bytes_per_device": P * B * label_bytes,
        "halo_live_bytes_per_device": int(live_boundary.sum()) * label_bytes,
        "boundary_live_per_device": live_boundary.tolist(),
        "collective_bytes_per_device": (k + P * n_blk) * label_bytes,
        "rank_gather_bytes_per_device": P * n_blk * label_bytes,
        "capacity_psum_bytes_per_device": k * label_bytes,
    }
