"""Observability layer: span tracing, metrics, profiling, provenance.

See DESIGN.md §11.  Everything here is off the hot path unless
``SystemConfig.telemetry.trace`` / ``.metrics`` turns it on — the session
holds ``NULL_TRACER`` otherwise, whose hooks are constant-time no-ops.
"""
from repro.obs.manifest import config_hash, git_sha, run_manifest
from repro.obs.metrics import (MetricsRegistry, record_cluster,
                               record_superstep)
from repro.obs.profiling import HBM_BW, ICI_BW, PEAK_FLOPS, plan_cost
from repro.obs.trace import NULL_TRACER, NullTracer, Span, Tracer

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "Span",
    "MetricsRegistry", "record_superstep", "record_cluster",
    "plan_cost", "PEAK_FLOPS", "HBM_BW", "ICI_BW",
    "run_manifest", "git_sha", "config_hash",
]
