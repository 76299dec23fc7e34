"""Roofline cost estimates for migration plans and host-memory probes
(DESIGN.md §11, §14).

``plan_cost(plan, graph, k)`` is an analytic FLOP/byte bill of one fused
score/select pass over a ``MigrationPlan`` (``repro.kernels.
migration_kernels``), per packing kind, with the same peak numbers
``benchmarks/roofline.py`` uses (imported from here so the constants have
one home).  Comparing a measured ``kernel/score`` span against ``t_bound``
says how far the kernel sits from the roofline.  A device timeline of the
kernels comes from any ``jax.profiler`` capture, where the session's spans
appear too (``repro.obs.trace``).
"""
from __future__ import annotations

import sys
from typing import Any, Dict, Optional

import numpy as np

# Roofline peaks (TPU v5e) — single source of truth, re-exported by
# benchmarks/roofline.py.
PEAK_FLOPS = 197e12           # bf16 FLOP/s per chip
HBM_BW = 819e9                # HBM bytes/s per chip
ICI_BW = 50e9                 # bytes/s per ICI link (conservative)


def _proc_status_kb(field: str) -> Optional[int]:
    """One ``/proc/self/status`` field in kB (Linux; None elsewhere)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith(field + ":"):
                    return int(line.split()[1])
    except OSError:
        pass
    return None


def current_rss_bytes() -> Optional[int]:
    """Resident set size right now (``VmRSS``); None off-Linux."""
    kb = _proc_status_kb("VmRSS")
    return None if kb is None else kb * 1024


def peak_rss_bytes() -> int:
    """Peak resident set size of this process, in bytes.

    The scale tier's headline memory number (DESIGN.md §14): a monotonic
    high-water mark, so a bounded-memory claim holds iff this stays flat
    while |V| grows.  Source: ``VmHWM`` from ``/proc/self/status`` where
    available, else ``getrusage`` (kB on Linux, bytes on macOS)."""
    kb = _proc_status_kb("VmHWM")
    if kb is not None:
        return kb * 1024
    try:
        import resource
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(peak if sys.platform == "darwin" else peak * 1024)
    except Exception:                                # pragma: no cover
        return 0


def memory_probe() -> Dict[str, Any]:
    """One host-memory sample for manifests and per-row bench records."""
    return {"peak_rss_bytes": peak_rss_bytes(),
            "current_rss_bytes": current_rss_bytes()}


def _live_edges(graph: Any) -> int:
    return int(np.asarray(graph.edge_mask).sum())


def plan_cost(plan: Any, graph: Any, k: int,
              label_bytes: int = 4) -> Dict[str, Any]:
    """Analytic cost of one fused score/select pass over ``plan``.

    Counts the histogram (the dominant term) plus the (n, k) epilogue, per
    packing kind (DESIGN.md §9):

      flat — scatter-adds over the 2E symmetrised COO edges;
      ell  — dense gather+compare over the (n_cap, deg_cap) pad;
      bsr  — blk×blk×k MXU dots per nonzero tile.

    Returns flops / hbm_bytes plus the roofline terms ``t_compute`` /
    ``t_memory`` (seconds at peak), their max ``t_bound``, the dominant
    side, and the arithmetic intensity — directly comparable to a measured
    ``kernel/score`` span and to ``benchmarks/roofline.py`` cells.
    """
    n_cap = int(graph.n_cap)
    e2 = 2 * _live_edges(graph)
    epilogue_flops = 4.0 * n_cap * k          # argmax/gain/select epilogue
    epilogue_bytes = float(n_cap * k * label_bytes)
    kind = plan.kind if plan is not None else "flat"
    if kind == "bsr":
        nnzb, blk, _ = plan.blocks.shape
        flops = 2.0 * nnzb * blk * blk * k + epilogue_flops
        hbm = (nnzb * blk * blk * 4.0          # adjacency tiles (f32)
               + nnzb * blk * label_bytes      # column-block labels
               + epilogue_bytes)
        shape = {"nnzb": int(nnzb), "blk": int(blk),
                 "max_per_row": int(plan.max_per_row)}
    elif kind == "ell":
        n_rows, deg_cap = plan.nbrs.shape
        flops = 2.0 * n_rows * deg_cap * k + epilogue_flops
        hbm = (n_rows * deg_cap * 2.0 * label_bytes   # nbr ids + their labels
               + epilogue_bytes)
        shape = {"rows": int(n_rows), "deg_cap": int(deg_cap)}
    elif kind == "flat":
        flops = 2.0 * e2 + epilogue_flops
        hbm = (e2 * 3.0 * label_bytes          # src, dst, gathered labels
               + epilogue_bytes)
        shape = {"edges2": int(e2)}
    else:
        raise ValueError(f"unknown plan kind {kind!r}")
    t_compute = flops / PEAK_FLOPS
    t_memory = hbm / HBM_BW
    t_bound = max(t_compute, t_memory)
    return {
        "kind": kind, "k": int(k), "n_cap": n_cap, "live_edges2": e2,
        "flops": float(flops), "hbm_bytes": float(hbm),
        "intensity_flops_per_byte": float(flops / max(hbm, 1.0)),
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_bound_s": t_bound,
        "dominant": "compute" if t_compute >= t_memory else "memory",
        **shape,
    }
