"""What the per-layer readers in ``metrics/`` share.

A reader gets the run's data (``run``: the session's phase spans of the
window, the window's superstep records, counters, the reduced profiler
trace and the work counts) and returns a number, or None where the run has
nothing to read — the harness then leaves the metric out.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from . import cost


def mean_span_ms(run: Dict[str, Any], name: str) -> Optional[float]:
    """Mean duration of the session's ``name`` spans in the window."""
    durs = [e["dur_us"] for e in run.get("spans", ())
            if e.get("type") == "span" and e["name"] == name]
    return sum(durs) / len(durs) / 1e3 if durs else None


def idle_pct(run: Dict[str, Any]) -> Optional[float]:
    """Share of the profiled window in which the chip ran nothing."""
    tr = run.get("trace")
    if not tr or tr["busy_s"] <= 0 or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def kernel_ms_per_round(run: Dict[str, Any]) -> Optional[float]:
    """Device time of the scoring kernel's events per profiled round."""
    tr = run.get("trace")
    rounds = run.get("counters", {}).get("rounds_profiled", 0)
    if not tr or not tr["kernel_events"] or not rounds:
        return None
    return 1e3 * tr["kernel_s"] / rounds


def roofline_pct(run: Dict[str, Any]) -> Optional[float]:
    """Least time of one scoring pass over its measured device time."""
    per_round = kernel_ms_per_round(run)
    if per_round is None:
        return None
    least, _ = cost.least_time(run["work"], cost.peaks(run["device_kind"]))
    return 100.0 * least / (per_round / 1e3)
