"""One run of one cell: load it by name, drive it, check it, report it.

``BENCHMARK.json`` names the cell; the cell names its configuration
(``configs/<config>.json``) and its traffic mix (``traffic/<traffic>.json``).
The traffic's ``mode`` picks the runner (``stream`` or ``adapt``); each
per-layer metric is read by ``metrics/<metric>.py``. Nothing here knows a
cell by name, so a later cell that fits a runner needs data files only.

A runner returns an ``Outcome``: the end-to-end numbers it timed, what the
per-layer readers read (``run``), and the comparisons against the plain
reference, each with its limit. ``correct`` holds when every compared
number is within its limit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown cell, bad data)."""


@dataclasses.dataclass
class Outcome:
    metrics: Dict[str, float]                 # end-to-end, by name
    run: Dict[str, Any]                       # what the per-layer readers read
    checks: Dict[str, Dict[str, float]]       # name -> {"value", "limit"}
    attempted: int
    failed: int
    memory_peak_bytes: int
    notes: List[str] = dataclasses.field(default_factory=list)

    @property
    def correct(self) -> bool:
        return all(c["value"] <= c["limit"] for c in self.checks.values())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = _merge(out[key], value)
        else:
            out[key] = value
    return out


def _applies(metric: Dict[str, Any], cell: str, e2e: List[Dict]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    moved = [m for m in e2e if m["name"] == metric.get("moves")]
    return not moved or _applies(moved[0], cell, [])


def load_cell(name: str, overrides: Optional[Dict[str, Any]] = None,
              root: str = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, its files merged with
    ``overrides`` (``{"config": {...}, "traffic": {...}}``, tests only)."""
    bench_path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(bench_path):
        raise BenchError(f"no BENCHMARK.json at {root}")
    bench = _json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    overrides = overrides or {}
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, [])]
    layer = [m for m in bench["per_layer"]
             if _applies(m, name, bench["end_to_end"])]
    return Cell(name=name, chips=int(w["chips"]),
                config=_merge(config, overrides.get("config", {})),
                traffic=_merge(traffic, overrides.get("traffic", {})),
                end_to_end=e2e, per_layer=layer)


def reader(metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """``read`` of ``metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise BenchError(f"no reader for metric {metric!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts executables built or loaded while ``armed`` (the window)."""

    def __init__(self) -> None:
        import jax
        self.armed = False
        self.built = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_: Any) -> None:
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def _event(self, event: str, **_: Any) -> None:
        if self.armed and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def chips(count: int):
    """The TPU devices of this host; fewer than ``count`` is an error."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX sees {len(devices)} "
                         f"{devices[0].platform} device(s)")
    if len(devices) < count:
        raise BenchError(f"the cell needs {count} chips, JAX sees "
                         f"{len(devices)}")
    return devices


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def runner(mode: str):
    """The module that runs a traffic ``mode``: its ``run`` drives a cell
    once, its ``control`` puts the reference in bfloat16 in the session's
    place on a run's own inputs."""
    if mode == "stream":
        from . import stream_cell
        return stream_cell
    if mode == "adapt":
        from . import adapt_cell
        return adapt_cell
    raise BenchError(f"unknown traffic mode {mode!r}: stream or adapt")


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             started: float, devices) -> Outcome:
    """Drive ``cell`` once; ``started`` is the process's start on the
    ``time.perf_counter`` clock (set-up is timed from there)."""
    return runner(cell.traffic["mode"]).run(
        cell, seed=seed, seconds=seconds, trace=trace, started=started,
        devices=devices)


def result_line(cell: Cell, outcome: Outcome, *, trace: bool, devices
                ) -> Dict[str, Any]:
    if trace:
        metrics = {}
        outcome.run.setdefault("device_kind", devices[0].device_kind)
        for m in cell.per_layer:
            value = reader(m["name"])(outcome.run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": outcome.metrics[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    line: Dict[str, Any] = {"correct": outcome.correct,
                            "attempted": outcome.attempted,
                            "failed": outcome.failed,
                            "metrics": metrics, "device": device}
    if trace:
        tr = outcome.run.get("trace") or {}
        device["busy_s"] = tr.get("busy_s", 0.0)
        device["window_s"] = tr.get("window_s", 0.0)
        line["breakdown"] = {"device_ops": tr.get("device_ops", []),
                             "idle_gaps": tr.get("idle_gaps", [])}
    line["checked"] = outcome.checks
    return line


def main(argv: Optional[List[str]] = None, started: Optional[float] = None
         ) -> int:
    import argparse
    started = time.perf_counter() if started is None else started
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise BenchError(f"--seed must be non-negative, got {args.seed}")
    cell = load_cell(args.workload)
    devices = chips(cell.chips)[:cell.chips]
    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}; workload {cell.name}, seed {args.seed}, "
        f"{args.seconds} s, trace {args.trace}")
    outcome = run_cell(cell, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), started=started,
                       devices=devices)
    line = result_line(cell, outcome, trace=bool(args.trace),
                       devices=devices)
    for note in outcome.notes:
        log(note)
    for name, c in outcome.checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0
