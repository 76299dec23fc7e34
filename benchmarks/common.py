"""Shared helpers for the paper-figure benchmarks."""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from repro.core.vertex_program import CostModel
from repro.obs.manifest import run_manifest

RESULTS_DIR = os.environ.get("REPRO_RESULTS", "results")


def run_main(main: Callable[[], Any]) -> Any:
    """A benchmark script's entry point: run ``main`` with JAX's persistent
    compilation cache on (``repro.compat.enable_compile_cache``), so runs
    of the same shapes compile once."""
    from repro.compat import enable_compile_cache
    enable_compile_cache()
    return main()


def save(name: str, payload: Any, *, config: Any = None) -> str:
    """Write a result payload, stamped with a provenance manifest (git sha,
    jax versions, device kind, timestamp — DESIGN.md §11) so committed
    numbers stay citable.  ``config`` adds its hash to the manifest."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    if isinstance(payload, dict) and "manifest" not in payload:
        payload = {**payload, "manifest": run_manifest(config)}
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path


def timed(fn, *args, repeats: int = 1, warmup: int = 0, **kw):
    """Mean wall time of ``fn`` with a sync fence per call.

    JAX dispatch is asynchronous: without ``jax.block_until_ready`` on the
    result this would measure dispatch, not device time.  ``warmup`` extra
    un-timed calls first absorb jit compilation.
    """
    import jax
    out = None
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = jax.block_until_ready(fn(*args, **kw))
    dt = (time.perf_counter() - t0) / repeats
    return out, dt


class CommModel(CostModel):
    """Iteration-time model from the paper's observation that network
    messages dominate (>80% of iteration time, §5.3): t = c_cpu·msgs_local +
    c_net·msgs_remote, with c_net/c_cpu = 25 (≈ 10GbE RTT vs in-memory
    hand-off). Used where wall-clock would only reflect this CPU container.

    Thin message-unit façade over ``repro.core.vertex_program.CostModel`` —
    the single source of truth for the cost constants, shared with the
    scenario suite.
    """

    def step_time(self, local_msgs: float, remote_msgs: float,
                  migrations: float = 0.0, c_mig: Optional[float] = None) -> float:
        model = self if c_mig is None else dataclasses.replace(self, c_mig=c_mig)
        return model.superstep_cost(local_msgs, remote_msgs, migrations,
                                    unit_bytes=1.0)
