"""Schema validation for the observability file formats (DESIGN.md §11).

The trace and metrics JSONL files are contracts: the report CLI, the CI
smoke job, and any external consumer parse them blind.  These validators
are deliberately hand-rolled (no jsonschema dependency) and strict about
the fields the consumers rely on, so an exporter drift fails the schema
tests loudly instead of silently producing unreadable artifacts.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List

from repro.obs.metrics import METRICS_SCHEMA_VERSION
from repro.obs.trace import TRACE_SCHEMA_VERSION


class SchemaError(ValueError):
    """A trace/metrics line violated the published schema."""


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SchemaError(msg)


def _num(d: Dict[str, Any], key: str, line: int) -> None:
    _require(isinstance(d.get(key), (int, float))
             and not isinstance(d.get(key), bool),
             f"line {line}: {key!r} must be a number, got {d.get(key)!r}")


def _index(v: Any) -> bool:
    return isinstance(v, int) and not isinstance(v, bool) and v >= 0


# ---------------------------------------------------------------------------
# trace JSONL
# ---------------------------------------------------------------------------

def validate_trace_line(obj: Dict[str, Any], line: int = 0) -> None:
    """One trace event (post-header).  Raises SchemaError on violation."""
    _require(isinstance(obj, dict), f"line {line}: not an object")
    kind = obj.get("type")
    if kind == "span":
        _require(isinstance(obj.get("name"), str) and obj["name"],
                 f"line {line}: span needs a non-empty name")
        _num(obj, "ts_us", line)
        _num(obj, "dur_us", line)
        _require(obj["dur_us"] >= 0, f"line {line}: negative dur_us")
        _num(obj, "depth", line)
        _require(obj["depth"] >= 0, f"line {line}: negative depth")
        if "id" in obj:
            _require(_index(obj["id"]), f"line {line}: 'id' must be a "
                     f"non-negative integer, got {obj['id']!r}")
        if "parent" in obj:
            _require(obj["parent"] is None or _index(obj["parent"]),
                     f"line {line}: 'parent' must be null or a span id, "
                     f"got {obj['parent']!r}")
        if "attrs" in obj:
            _require(isinstance(obj["attrs"], dict),
                     f"line {line}: attrs must be an object")
    elif kind == "counter":
        _require(isinstance(obj.get("name"), str) and obj["name"],
                 f"line {line}: counter needs a non-empty name")
        _num(obj, "ts_us", line)
        _num(obj, "value", line)
    else:
        raise SchemaError(f"line {line}: unknown event type {kind!r}")


def validate_trace_header(obj: Dict[str, Any]) -> None:
    _require(isinstance(obj, dict) and obj.get("type") == "meta",
             "first line must be a meta header")
    _require(obj.get("schema") == TRACE_SCHEMA_VERSION,
             f"trace schema {obj.get('schema')!r}, "
             f"expected {TRACE_SCHEMA_VERSION}")
    _require(obj.get("clock") == "perf_counter_ns",
             f"unknown clock {obj.get('clock')!r}")
    _require(obj.get("unit") == "us", f"unknown unit {obj.get('unit')!r}")


def validate_trace_file(path: str) -> List[Dict[str, Any]]:
    """Validate a trace JSONL file; returns the parsed events (header
    excluded) so callers can validate *and* consume in one pass."""
    events: List[Dict[str, Any]] = []
    with open(path) as f:
        for i, raw in enumerate(f):
            raw = raw.strip()
            if not raw:
                continue
            try:
                obj = json.loads(raw)
            except json.JSONDecodeError as e:
                raise SchemaError(f"line {i}: invalid JSON: {e}") from e
            if i == 0:
                validate_trace_header(obj)
                continue
            validate_trace_line(obj, line=i)
            events.append(obj)
    return events


# ---------------------------------------------------------------------------
# metrics JSONL
# ---------------------------------------------------------------------------

_METRIC_KINDS = ("counter", "gauge", "histogram")


def validate_metrics_line(obj: Dict[str, Any], line: int = 0) -> None:
    _require(isinstance(obj, dict), f"line {line}: not an object")
    _require(obj.get("type") == "sample",
             f"line {line}: expected type 'sample', got {obj.get('type')!r}")
    _require(isinstance(obj.get("name"), str) and obj["name"],
             f"line {line}: sample needs a non-empty name")
    _require(obj.get("kind") in _METRIC_KINDS,
             f"line {line}: unknown metric kind {obj.get('kind')!r}")
    _require(isinstance(obj.get("labels"), dict),
             f"line {line}: labels must be an object")
    _num(obj, "value", line)


def validate_serve_bench(payload: Dict[str, Any]) -> None:
    """The serving benchmark result contract (results/bench_serve_sessions
    .json, DESIGN.md §12): headline throughput + tail latency across N
    tenants and the kill-and-recover drill outcome.  CI re-validates the
    committed file so the schema and the artifact cannot drift apart."""
    _require(isinstance(payload, dict), "serve bench: not an object")
    _require(isinstance(payload.get("tenants"), int)
             and payload["tenants"] >= 1,
             f"serve bench: 'tenants' must be a positive int, "
             f"got {payload.get('tenants')!r}")
    for key in ("events_total", "supersteps_total", "ticks"):
        _require(isinstance(payload.get(key), int) and payload[key] >= 0,
                 f"serve bench: {key!r} must be a non-negative int, "
                 f"got {payload.get(key)!r}")
    for key in ("wall_seconds", "events_per_sec",
                "ingest_p50_s", "ingest_p99_s"):
        _num(payload, key, 0)
        _require(payload[key] >= 0, f"serve bench: negative {key!r}")
    _require(payload["ingest_p99_s"] >= payload["ingest_p50_s"],
             "serve bench: p99 below p50")
    per = payload.get("per_tenant")
    _require(isinstance(per, dict) and len(per) == payload["tenants"],
             "serve bench: 'per_tenant' must map every tenant")
    for name, t in per.items():
        _require(isinstance(t, dict), f"serve bench: tenant {name!r} entry "
                 f"must be an object")
        for key in ("events", "supersteps", "rejected", "shed"):
            _require(isinstance(t.get(key), int) and t[key] >= 0,
                     f"serve bench: tenant {name!r} {key!r} must be a "
                     f"non-negative int, got {t.get(key)!r}")
    rec = payload.get("recovery")
    _require(isinstance(rec, dict), "serve bench: 'recovery' must be an "
             "object (the kill-and-recover drill outcome)")
    _num(rec, "seconds", 0)
    _require(rec.get("bit_exact") is True,
             "serve bench: recovery was not bit-exact")
    _require(isinstance(rec.get("tenants"), int)
             and rec["tenants"] == payload["tenants"],
             "serve bench: recovery must cover every tenant")


def validate_serve_bench_file(path: str) -> Dict[str, Any]:
    with open(path) as f:
        payload = json.load(f)
    validate_serve_bench(payload)
    return payload


_ARENA_ROW_NUMS = ("cut_final", "cut_mean", "imbalance_final",
                   "wall_seconds", "exec_cost_total")


def validate_arena_bench(payload: Dict[str, Any]) -> None:
    """The strategy-arena result contract (results/bench_strategy_arena
    .json, DESIGN.md §13): one row per (scenario, strategy) cell — full
    cross product, no silently missing cells — scoring cut, balance,
    migration volume, wall time and the cost-model total, plus per-scenario
    winners drawn from the swept strategies.  CI re-validates the committed
    file so the schema and the artifact cannot drift apart."""
    _require(isinstance(payload, dict), "arena bench: not an object")
    _require(payload.get("bench") == "strategy_arena",
             f"arena bench: 'bench' must be 'strategy_arena', "
             f"got {payload.get('bench')!r}")
    for key in ("scenarios", "strategies"):
        val = payload.get(key)
        _require(isinstance(val, list) and val
                 and all(isinstance(x, str) and x for x in val),
                 f"arena bench: {key!r} must be a non-empty list of names")
        _require(len(set(val)) == len(val),
                 f"arena bench: duplicate entries in {key!r} — canonical "
                 f"names only, aliases would run a strategy twice")
    scenarios = payload["scenarios"]
    strategies = payload["strategies"]
    rows = payload.get("rows")
    _require(isinstance(rows, list), "arena bench: 'rows' must be a list")
    _require(len(rows) == len(scenarios) * len(strategies),
             f"arena bench: expected {len(scenarios) * len(strategies)} rows "
             f"(full scenario x strategy cross product), got "
             f"{len(rows) if isinstance(rows, list) else rows!r}")
    seen = set()
    for i, row in enumerate(rows):
        _require(isinstance(row, dict), f"arena bench: row {i} not an object")
        _require(row.get("scenario") in scenarios,
                 f"arena bench: row {i} scenario {row.get('scenario')!r} "
                 f"not in 'scenarios'")
        _require(row.get("strategy") in strategies,
                 f"arena bench: row {i} strategy {row.get('strategy')!r} "
                 f"not in 'strategies'")
        cell = (row["scenario"], row["strategy"])
        _require(cell not in seen, f"arena bench: duplicate cell {cell}")
        seen.add(cell)
        for key in ("events", "supersteps", "migrations_total"):
            _require(isinstance(row.get(key), int) and row[key] >= 0,
                     f"arena bench: row {i} {key!r} must be a non-negative "
                     f"int, got {row.get(key)!r}")
        for key in _ARENA_ROW_NUMS:
            _num(row, key, i)
            _require(row[key] >= 0, f"arena bench: row {i} negative {key!r}")
        _require(0.0 <= row["cut_final"] <= 1.0,
                 f"arena bench: row {i} cut_final out of [0, 1]")
    winners = payload.get("winners")
    _require(isinstance(winners, dict)
             and set(winners) == set(scenarios),
             "arena bench: 'winners' must map every scenario")
    for scn, w in winners.items():
        _require(isinstance(w, dict) and w, f"arena bench: winners[{scn!r}] "
                 f"must be a non-empty object")
        for metric, strat in w.items():
            _require(strat in strategies,
                     f"arena bench: winners[{scn!r}][{metric!r}] = "
                     f"{strat!r} is not a swept strategy")


def validate_arena_bench_file(path: str) -> Dict[str, Any]:
    with open(path) as f:
        payload = json.load(f)
    validate_arena_bench(payload)
    return payload


_SCALE_ROW_NUMS = ("build_seconds", "ingest_events_per_sec",
                   "superstep_seconds", "adapt_seconds",
                   "cut_before", "cut_after")


def validate_scale_bench(payload: Dict[str, Any]) -> None:
    """The scale-sweep result contract (results/bench_scale_sweep.json,
    DESIGN.md §14): one row per (vertices, backend) cell — full cross
    product — recording end-to-end build / ingest / adapt timings and the
    host-memory high-water mark, plus the chunked-BSR outcome (packed
    stats, or the budget refusal that bounded memory).  CI re-validates
    both a fresh smoke sweep and the committed million-vertex artifact."""
    _require(isinstance(payload, dict), "scale bench: not an object")
    _require(payload.get("bench") == "scale_sweep",
             f"scale bench: 'bench' must be 'scale_sweep', "
             f"got {payload.get('bench')!r}")
    _require(isinstance(payload.get("generator"), str) and payload["generator"],
             "scale bench: 'generator' must name the edge stream")
    for key in ("k", "chunk_edges"):
        _require(isinstance(payload.get(key), int) and payload[key] >= 1,
                 f"scale bench: {key!r} must be a positive int, "
                 f"got {payload.get(key)!r}")
    sizes = payload.get("sizes")
    _require(isinstance(sizes, list) and sizes
             and all(isinstance(s, int) and s > 0 for s in sizes)
             and len(set(sizes)) == len(sizes),
             "scale bench: 'sizes' must be distinct positive vertex counts")
    backends = payload.get("backends")
    _require(isinstance(backends, list) and backends
             and all(isinstance(b, str) and b for b in backends)
             and len(set(backends)) == len(backends),
             "scale bench: 'backends' must be distinct backend names")
    rows = payload.get("rows")
    _require(isinstance(rows, list), "scale bench: 'rows' must be a list")
    _require(len(rows) == len(sizes) * len(backends),
             f"scale bench: expected {len(sizes) * len(backends)} rows "
             f"(full size x backend cross product), got "
             f"{len(rows) if isinstance(rows, list) else rows!r}")
    seen = set()
    for i, row in enumerate(rows):
        _require(isinstance(row, dict), f"scale bench: row {i} not an object")
        _require(row.get("vertices") in sizes,
                 f"scale bench: row {i} vertices {row.get('vertices')!r} "
                 f"not in 'sizes'")
        _require(row.get("backend") in backends,
                 f"scale bench: row {i} backend {row.get('backend')!r} "
                 f"not in 'backends'")
        cell = (row["vertices"], row["backend"])
        _require(cell not in seen, f"scale bench: duplicate cell {cell}")
        seen.add(cell)
        for key in ("edges", "events", "supersteps", "migrations",
                    "peak_rss_bytes"):
            _require(isinstance(row.get(key), int) and row[key] >= 0,
                     f"scale bench: row {i} {key!r} must be a non-negative "
                     f"int, got {row.get(key)!r}")
        _require(row["edges"] > 0 and row["peak_rss_bytes"] > 0,
                 f"scale bench: row {i} edges/peak_rss_bytes must be "
                 f"positive (an empty run measures nothing)")
        for key in _SCALE_ROW_NUMS:
            _num(row, key, i)
            _require(row[key] >= 0, f"scale bench: row {i} negative {key!r}")
        for key in ("cut_before", "cut_after"):
            _require(0.0 <= row[key] <= 1.0,
                     f"scale bench: row {i} {key!r} out of [0, 1]")
        bsr = row.get("bsr")
        _require(isinstance(bsr, dict), f"scale bench: row {i} 'bsr' must "
                 f"be an object (packed stats or a budget refusal)")
        if "skipped" in bsr:
            _require(isinstance(bsr["skipped"], str) and bsr["skipped"],
                     f"scale bench: row {i} bsr 'skipped' needs a reason")
        else:
            for key in ("nnzb", "blocks_bytes"):
                _require(isinstance(bsr.get(key), int) and bsr[key] >= 0,
                         f"scale bench: row {i} bsr {key!r} must be a "
                         f"non-negative int, got {bsr.get(key)!r}")
            _num(bsr, "build_seconds", i)


def validate_scale_bench_file(path: str) -> Dict[str, Any]:
    with open(path) as f:
        payload = json.load(f)
    validate_scale_bench(payload)
    return payload


def validate_metrics_file(path: str) -> List[Dict[str, Any]]:
    samples: List[Dict[str, Any]] = []
    with open(path) as f:
        for i, raw in enumerate(f):
            raw = raw.strip()
            if not raw:
                continue
            try:
                obj = json.loads(raw)
            except json.JSONDecodeError as e:
                raise SchemaError(f"line {i}: invalid JSON: {e}") from e
            if i == 0:
                _require(obj.get("type") == "meta",
                         "first line must be a meta header")
                _require(obj.get("schema") == METRICS_SCHEMA_VERSION,
                         f"metrics schema {obj.get('schema')!r}, "
                         f"expected {METRICS_SCHEMA_VERSION}")
                continue
            validate_metrics_line(obj, line=i)
            samples.append(obj)
    return samples
