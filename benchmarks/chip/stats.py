"""Due-time latency arithmetic of an open-loop stream.

Events are committed in FIFO order: superstep j commits the next
``committed[j]`` events of the queue. So event i (0-based, in due order) is
committed by the first superstep whose running total exceeds i, and its
latency runs from when it was due at the generator to when that superstep
returned.
"""
from __future__ import annotations

import math
from typing import Sequence

import numpy as np


def commit_latencies(due_s: np.ndarray, committed: Sequence[int],
                     returned_s: Sequence[float]) -> np.ndarray:
    """Seconds from due to commit for each of ``due_s``'s events (all of
    them must be committed by the supersteps given)."""
    total = np.cumsum(np.asarray(committed, np.int64))
    if total.size == 0 or total[-1] < due_s.shape[0]:
        raise ValueError(f"{due_s.shape[0]} events due but only "
                         f"{int(total[-1]) if total.size else 0} committed")
    step = np.searchsorted(total, np.arange(due_s.shape[0]), side="right")
    return np.asarray(returned_s, np.float64)[step] - due_s


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    sample at or below it."""
    if values.size == 0:
        raise ValueError("percentile of an empty sample")
    rank = max(math.ceil(q / 100.0 * values.size), 1)
    return float(np.partition(values, rank - 1)[rank - 1])
