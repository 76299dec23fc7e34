"""Halo-exchange GNN training: the paper's technique as a sharding pass.

Under pure GSPMD, distributed aggregation all-gathers node features
regardless of where edges actually point — collective volume is
shape-determined. The xDGP runtime instead buckets edges per owning device
(core.distributed.DistGraph) and exchanges only each block's *boundary
segment*; the halo width B is a static shape derived from the partition
quality, so better partitioning (the paper's contribution) shrinks the
compiled collective term directly.

This module provides shard_map GIN / GatedGCN forwards + train steps over a
DistGraph, plus the boundary-fraction measurement used to size the halo for
the dry-run (measured on a same-family graph at feasible scale, then applied
to the full-scale shapes — methodology in EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import AXIS, DistGraph, _halo_exchange
from repro.models.gnn import GINConfig, _layernorm, _linear, _mlp2

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# boundary-fraction measurement (sizes the halo)
# ---------------------------------------------------------------------------

def measure_boundary_fraction(n: int, avg_degree: float, k: int,
                              adapt_iters: int = 60, seed: int = 0,
                              strategy: str = "adapted") -> float:
    """Max over partitions of |boundary(P_i)| / |P_i| on a Chung–Lu graph.

    strategy "hash" → initial hash partitioning; "adapted" → after running
    the xDGP heuristic for ``adapt_iters`` iterations.
    """
    from repro.graph import generators
    from repro.core import adapt_rounds, initial_partition, make_state

    g = generators.chung_lu(n, avg_degree, seed=seed)
    lab = initial_partition(g, k, "hsh")
    if strategy == "adapted":
        state = make_state(g, lab, k)
        state, _ = adapt_rounds(g, state, adapt_iters)
        lab = state.assignment
    lab_np = np.asarray(lab)
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    em = np.asarray(g.edge_mask)
    s, d = src[em], dst[em]
    cross = lab_np[s] != lab_np[d]
    boundary_nodes = np.unique(np.concatenate([s[cross], d[cross]]))
    counts = np.bincount(lab_np[: g.n_cap], minlength=k).astype(np.float64)
    bcounts = np.bincount(lab_np[boundary_nodes], minlength=k).astype(np.float64)
    frac = bcounts / np.maximum(counts, 1)
    return float(frac.max())


# ---------------------------------------------------------------------------
# shard_map GIN over DistGraph
# ---------------------------------------------------------------------------

def _gin_layer_local(lp, h_loc, dgl: DistGraph, halo_size: int):
    halo = _halo_exchange(h_loc, dgl)
    src_owner = dgl.src_owner[0]
    src_slot = dgl.src_slot[0]
    src_local = dgl.src_local[0]
    dst_local = dgl.dst_local[0]
    edge_ok = dgl.edge_ok[0]
    feat_remote = halo[jnp.clip(src_owner * halo_size + src_slot, 0,
                                halo.shape[0] - 1)]
    feat_local = h_loc[src_slot]
    feat = jnp.where(src_local[:, None], feat_local, feat_remote)
    feat = jnp.where(edge_ok[:, None], feat, 0)
    n_blk = h_loc.shape[0]
    agg = jax.ops.segment_sum(feat, jnp.where(edge_ok, dst_local, n_blk),
                              num_segments=n_blk + 1)[:n_blk]
    h = _mlp2(lp["mlp"], (1.0 + lp["eps"]) * h_loc + agg)
    h = jax.nn.relu(_layernorm(lp["ln"], h))
    return jnp.where(dgl.node_ok[0][:, None], h, 0)


def gin_halo_forward(params: Params, dg: DistGraph, feats: jax.Array,
                     cfg: GINConfig, mesh) -> jax.Array:
    """GIN over the halo engine. feats: (P*n_blk, d_in) node features."""
    P = dg.num_devices
    halo = dg.halo_size
    spec_n = jax.sharding.PartitionSpec(AXIS, None)
    dg_specs = DistGraph(*([jax.sharding.PartitionSpec(AXIS)] * 8))

    def body(feats_loc, dgl):
        h = _linear(params["encode"], feats_loc)

        def layer(lp, h):
            return _gin_layer_local(lp, h, dgl, halo)

        step = jax.checkpoint(layer) if cfg.remat else layer
        for lp in params["layers"]:
            h = step(lp, h)
        return _mlp2(params["decode"], h)

    return jax.shard_map(body, mesh=mesh, in_specs=(spec_n, dg_specs),
                     out_specs=spec_n)(feats, dg)


def gin_halo_loss(params: Params, dg: DistGraph, feats: jax.Array,
                  labels: jax.Array, cfg: GINConfig, mesh) -> jax.Array:
    logits = gin_halo_forward(params, dg, feats, cfg, mesh)
    node_ok = dg.node_ok.reshape(-1)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    ll = jnp.take_along_axis(logp, jnp.clip(labels, 0, cfg.n_out - 1)[:, None],
                             -1)[:, 0]
    m = node_ok.astype(jnp.float32)
    return -(ll * m).sum() / jnp.maximum(m.sum(), 1.0)


def abstract_dist_graph(num_devices: int, n_blk: int, e_blk: int,
                        halo: int) -> DistGraph:
    """ShapeDtypeStruct DistGraph for dry-run lowering (no allocation)."""
    P = num_devices
    i32, b8 = jnp.int32, jnp.bool_
    sds = jax.ShapeDtypeStruct
    return DistGraph(
        src_owner=sds((P, e_blk), i32), src_slot=sds((P, e_blk), i32),
        src_local=sds((P, e_blk), b8), dst_local=sds((P, e_blk), i32),
        edge_ok=sds((P, e_blk), b8), boundary=sds((P, halo), i32),
        boundary_ok=sds((P, halo), b8), node_ok=sds((P, n_blk), b8))
