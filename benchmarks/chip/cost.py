"""Work that one scoring pass of the migration heuristic must do.

Counted from the graph alone, whatever representation a plan packs it in,
so the roofline of a scorer stays comparable when its tile format changes:

* bytes: each live directed edge reads its source's label and its own two
  endpoints (3 × 4 bytes); the (n_cap, k) int32 histogram is written once.
* operations: a compare and an add per live directed edge; per histogram
  cell, the noise add, the running max, the compare and the select.

The least time is the larger of bytes over the chip's memory bandwidth and
operations over its peak rate (``peaks.json``).
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def scoring_pass(live_directed_edges: int, n_cap: int, k: int
                 ) -> Dict[str, int]:
    return {"bytes": 12 * live_directed_edges + 4 * n_cap * k,
            "flops": 2 * live_directed_edges + 4 * n_cap * k}


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks; a kind the table does not list is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def least_time(work: Dict[str, int], peak: Dict[str, float]
               ) -> Tuple[float, str]:
    """(seconds, "memory" or "compute"): which bound the work hits first."""
    t_mem = work["bytes"] / peak["hbm_bytes_per_s"]
    t_ops = work["flops"] / peak["flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
