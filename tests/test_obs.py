"""Observability-layer tests (DESIGN.md §11).

Four groups:
  * schema snapshots — ``SuperstepRecord.as_dict()`` keys and the
    trace/metrics JSONL formats are contracts; exporters fail loudly here
    instead of drifting silently;
  * tracer/metrics mechanics — nesting, exports, the null-object path;
  * the overhead budget — enabled tracing costs <3% of superstep wall
    time, the disabled path touches no clock and allocates nothing;
  * traced smoke — a traced session on the local backend in-process, and
    the sharded backend (with the comm probe) in a subprocess under 8 fake
    devices, both validated against the schema and the named-span list the
    bench deliverable relies on.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import DynamicGraphSystem, PartitionSection, SystemConfig
from repro.api.config import GraphSection, TelemetrySection
from repro.api.telemetry import SuperstepRecord
from repro.graph import generators
from repro.obs import (MetricsRegistry, NULL_TRACER, Tracer, config_hash,
                       plan_cost, record_cluster, record_superstep,
                       run_manifest)
from repro.obs.report import main as report_main
from repro.obs.schema import (SchemaError, validate_metrics_file,
                              validate_trace_file, validate_trace_line)
from repro.obs.trace import _NULL_SPAN

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(snippet: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", snippet], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def _events(n: int, n_nodes: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.stack([np.arange(n) // 4,
                     rng.integers(0, n_nodes, n),
                     rng.integers(0, n_nodes, n)], axis=1).astype(np.int64)


def _session(trace: bool, **tele) -> DynamicGraphSystem:
    cfg = SystemConfig(
        graph=GraphSection(n_cap=256, e_cap=2048),
        partition=PartitionSection(strategy="xdgp", k=4, adapt_iters=2),
        telemetry=TelemetrySection(trace=trace, **tele))
    return DynamicGraphSystem(None, cfg)


# ---------------------------------------------------------------------------
# Telemetry schema snapshots
# ---------------------------------------------------------------------------

# the exporter contract: SuperstepRecord.as_dict() keys, frozen.  A field
# added to the record must be added HERE and to the metrics mapping
# (repro.obs.metrics) in the same change.
RECORD_KEYS = (
    "superstep", "now", "events", "adds", "dels", "backlog_adds",
    "backlog_dels", "invalid_events", "stale_dropped", "new_placed",
    "migrations", "cut_edges", "live_edges", "cut_ratio", "imbalance",
    "ingest_seconds", "step_seconds", "drift", "dup_dropped",
    "local_bytes", "remote_bytes", "compute_seconds", "halo_bytes",
    "halo_live_bytes", "collective_bytes", "events_per_second",
)


def test_superstep_record_as_dict_keys_frozen():
    rec = SuperstepRecord(superstep=1, now=0, events=0, adds=0, dels=0,
                          backlog_adds=0, backlog_dels=0, invalid_events=0,
                          stale_dropped=0, new_placed=0, migrations=0,
                          cut_edges=0, live_edges=0, cut_ratio=0.0,
                          imbalance=1.0, ingest_seconds=0.0,
                          step_seconds=0.0, drift=None)
    assert tuple(rec.as_dict()) == RECORD_KEYS


def test_record_metrics_mapping_covers_every_numeric_field():
    # every record field lands in exactly one metric family
    from repro.obs.metrics import (_RECORD_COUNTERS, _RECORD_GAUGES,
                                   _RECORD_HISTOGRAMS)
    mapped = set(_RECORD_COUNTERS) | set(_RECORD_GAUGES) | \
        set(_RECORD_HISTOGRAMS)
    fields = set(RECORD_KEYS) - {"drift", "events_per_second"}
    assert mapped == fields
    assert not (set(_RECORD_COUNTERS) & set(_RECORD_GAUGES))


# ---------------------------------------------------------------------------
# Tracer mechanics + trace schema
# ---------------------------------------------------------------------------

def test_tracer_spans_nest_and_export(tmp_path):
    tr = Tracer(meta={"label": "t"})
    with tr.span("superstep", superstep=1):
        with tr.span("ingest"):
            pass
        with tr.span("migrate") as sp:
            sp.set(moved=3)
            sp.fence(jnp.ones(4))
    tr.add_span("comm/halo_exchange", 0.002, probed=True)
    tr.counter("migrations", 3)
    names = [e["name"] for e in tr.events if e["type"] == "span"]
    # children emit at exit, before their parent
    assert names == ["ingest", "migrate", "superstep",
                     "comm/halo_exchange"]
    by = {e["name"]: e for e in tr.events if e["type"] == "span"}
    assert by["superstep"]["depth"] == 0 and by["ingest"]["depth"] == 1
    assert by["migrate"]["attrs"]["moved"] == 3
    # children are contained in the parent interval (Perfetto nesting)
    for child in ("ingest", "migrate"):
        assert by[child]["ts_us"] >= by["superstep"]["ts_us"]
        assert (by[child]["ts_us"] + by[child]["dur_us"]
                <= by["superstep"]["ts_us"] + by["superstep"]["dur_us"] + 1)

    p = tr.write_jsonl(str(tmp_path / "t.jsonl"))
    events = validate_trace_file(p)
    assert len(events) == len(tr.events)
    header = json.loads(open(p).read().splitlines()[0])
    assert header["type"] == "meta" and header["label"] == "t"

    chrome = tr.write_chrome(str(tmp_path / "t.trace.json"))
    doc = json.load(open(chrome))
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert {"M", "X", "C"} <= phases

    totals = tr.phase_totals()
    assert totals["superstep"]["count"] == 1
    assert totals["comm/halo_exchange"]["total_s"] == pytest.approx(0.002)


def test_span_events_name_their_parent():
    tr = Tracer()
    with tr.span("adapt"):
        with tr.span("plan.build"):
            pass
        with tr.span("adapt.history"):
            tr.add_span("probe", 0.001)
    by = {e["name"]: e for e in tr.events}
    assert by["adapt"]["parent"] is None and by["adapt"]["depth"] == 0
    assert by["plan.build"]["parent"] == by["adapt"]["id"]
    assert by["adapt.history"]["parent"] == by["adapt"]["id"]
    assert by["probe"]["parent"] == by["adapt.history"]["id"]
    assert by["probe"]["depth"] == 2
    assert len({e["id"] for e in tr.events}) == 4
    for i, ev in enumerate(tr.events):
        validate_trace_line(ev, line=i)
    # self time: the parent's duration less its children's
    kids = sum(e["dur_us"] for e in tr.events
               if e["parent"] == by["adapt"]["id"])
    assert by["adapt"]["dur_us"] - kids >= 0


@pytest.mark.parametrize("bad", [{"id": -1}, {"id": "a"}, {"parent": -2},
                                 {"parent": True}])
def test_trace_schema_rejects_bad_span_ids(bad):
    line = {"type": "span", "name": "x", "ts_us": 0, "dur_us": 1,
            "depth": 0, "id": 0, "parent": None}
    validate_trace_line(line)
    with pytest.raises(SchemaError):
        validate_trace_line(dict(line, **bad))


def test_trace_schema_rejects_bad_lines(tmp_path):
    with pytest.raises(SchemaError, match="negative dur_us"):
        validate_trace_line({"type": "span", "name": "x", "ts_us": 0,
                             "dur_us": -1, "depth": 0})
    with pytest.raises(SchemaError, match="unknown event type"):
        validate_trace_line({"type": "spam", "name": "x"})
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"type": "meta", "schema": 999, "clock": '
                   '"perf_counter_ns", "unit": "us"}\n')
    with pytest.raises(SchemaError, match="schema"):
        validate_trace_file(str(bad))


# ---------------------------------------------------------------------------
# Metrics registry + metrics schema
# ---------------------------------------------------------------------------

def test_metrics_registry_exports(tmp_path):
    reg = MetricsRegistry(namespace="t")
    reg.counter("events_total", "events seen").inc(5)
    reg.counter("events_total").inc(2, backend="sharded")
    reg.gauge("cut_ratio").set(0.25)
    reg.histogram("step_seconds").observe(0.004)
    reg.histogram("step_seconds").observe(9.0)   # beyond last bucket
    with pytest.raises(ValueError, match="cannot decrease"):
        reg.counter("events_total").inc(-1)
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("events_total")

    p = reg.write_jsonl(str(tmp_path / "m.jsonl"))
    samples = validate_metrics_file(p)
    by = {(s["name"], tuple(sorted(s["labels"].items()))): s["value"]
          for s in samples}
    assert by[("t_events_total", ())] == 5
    assert by[("t_events_total", (("backend", "sharded"),))] == 2
    # +Inf bucket counts every observation; the 9.0 one only lands there
    assert by[("t_step_seconds_bucket", (("le", "+Inf"),))] == 2
    assert by[("t_step_seconds_count", ())] == 2

    text = reg.to_prometheus()
    assert "# TYPE t_events_total counter" in text
    assert 't_events_total{backend="sharded"} 2.0' in text
    assert '# HELP t_events_total events seen' in text
    assert 't_step_seconds_bucket{le="+Inf"} 2.0' in text


def test_record_superstep_and_cluster_feed():
    reg = MetricsRegistry()
    rec = SuperstepRecord(superstep=1, now=10, events=20, adds=5, dels=1,
                          backlog_adds=0, backlog_dels=0, invalid_events=0,
                          stale_dropped=0, new_placed=3, migrations=7,
                          cut_edges=4, live_edges=16, cut_ratio=0.25,
                          imbalance=1.1, ingest_seconds=0.001,
                          step_seconds=0.02, drift=None, halo_bytes=64)
    record_superstep(reg, rec, backend="local")
    assert reg.counter("migrations_total").values[
        (("backend", "local"),)] == 7
    assert reg.gauge("cut_ratio").values[(("backend", "local"),)] == 0.25
    record_cluster(reg, None)                     # local backend: no-op
    record_cluster(reg, {
        "devices": 2, "halo_slots": 4, "boundary_live_per_device": [3, 2],
        "halo_bytes_per_iter_per_device": 32,
        "halo_live_bytes_per_iter_per_device": 24,
        "collective_bytes_per_iter_per_device": 16,
        "halo_bytes_total": 640, "halo_live_bytes_total": 480,
        "collective_bytes_total": 320,
        "iterations_total": 10, "compiled_steps": 1})
    assert reg.gauge("cluster_devices").values[()] == 2
    assert reg.gauge("cluster_boundary_live").values[
        (("device", "1"),)] == 2


# ---------------------------------------------------------------------------
# Manifest / profiling / common.timed
# ---------------------------------------------------------------------------

def test_run_manifest_and_config_hash():
    cfg = SystemConfig()
    m = run_manifest(cfg, label="test")
    for key in ("manifest_version", "git_sha", "python", "timestamp_utc",
                "jax_version", "backend", "device_count", "config_hash"):
        assert key in m, key
    assert m["label"] == "test"
    assert m["config_hash"] == config_hash(cfg)
    assert config_hash(cfg) != config_hash(cfg.with_seed(1))


def test_save_attaches_manifest(tmp_path, monkeypatch):
    import benchmarks.common as common
    monkeypatch.setattr(common, "RESULTS_DIR", str(tmp_path))
    path = common.save("x", {"rows": [1, 2]})
    doc = json.load(open(path))
    assert doc["rows"] == [1, 2]
    assert doc["manifest"]["manifest_version"] == 1
    assert "jax_version" in doc["manifest"]


def test_timed_fences_and_warms_up():
    import benchmarks.common as common
    calls = []

    def fn(x):
        calls.append(1)
        return x * 2
    out, dt = common.timed(fn, jnp.ones(4), repeats=2, warmup=1)
    assert len(calls) == 3 and dt >= 0
    assert float(out[0]) == 2.0


def test_plan_cost_all_kinds():
    from repro.kernels.migration_kernels import build_plan
    g = generators.fem_grid2d(8)
    for executor, kinds in (("native", ("bsr",)), ("jax", ("ell", "flat"))):
        plan = build_plan(g, executor=executor)
        assert plan.kind in kinds + ("flat",)
        c = plan_cost(plan, g, k=4)
        assert c["kind"] == plan.kind
        assert c["flops"] > 0 and c["hbm_bytes"] > 0
        assert c["t_bound_s"] == max(c["t_compute_s"], c["t_memory_s"])
        assert c["dominant"] in ("compute", "memory")
    c = plan_cost(None, g, k=4)                   # no plan → flat estimate
    assert c["kind"] == "flat" and c["live_edges2"] == c["edges2"]


# ---------------------------------------------------------------------------
# Traced sessions: local smoke, disabled null path, overhead budget
# ---------------------------------------------------------------------------

LOCAL_PHASES = {"superstep", "ingest", "place", "migrate",
                "adapt", "commit"}
SHARDED_PHASES = {"superstep", "ingest", "place", "migrate", "commit",
                  "cluster/bucket", "cluster/recompile", "cluster/dispatch",
                  "cluster/host_sync", "cluster/flush", "obs/comm_probe",
                  "comm/halo_exchange", "comm/quota_collective",
                  "kernel/score"}


def test_traced_local_session(tmp_path):
    system = _session(trace=True, metrics=True)
    ev = _events(300, 200)
    for i in range(3):
        system.step(ev[i * 100:(i + 1) * 100])
    assert set(system.tracer.phase_totals()) == LOCAL_PHASES
    assert system.tracer.phase_totals()["superstep"]["count"] == 3
    p = system.tracer.write_jsonl(str(tmp_path / "local.jsonl"))
    validate_trace_file(p)
    # the metrics feed saw every superstep
    assert system.metrics.counter("events_total").values[
        (("backend", "local"),)] == 300


def test_disabled_session_is_null_path():
    system = _session(trace=False)
    system.step(_events(100, 200))
    assert system.tracer is NULL_TRACER
    assert system.metrics is None
    assert system.tracer.events == ()
    # the null tracer hands out ONE shared span object: no allocation,
    # no clock reads on the disabled hot path
    assert NULL_TRACER.span("x") is _NULL_SPAN
    assert NULL_TRACER.span("y", a=1) is _NULL_SPAN
    _NULL_SPAN.fence(jnp.ones(2))                 # no-op, takes anything


def test_null_tracer_reads_exactly_and_records_nothing():
    # the null path of the new hooks: the shared span, the bare conversion
    x = jnp.arange(4, dtype=jnp.int32).sum()
    for conv in (int, float):
        got = NULL_TRACER.host_read(conv, x, "history")
        assert type(got) is conv and got == conv(x)
    arr = NULL_TRACER.host_read(np.asarray, jnp.ones(3), "plan")
    assert isinstance(arr, np.ndarray)
    NULL_TRACER.flush_syncs()
    with NULL_TRACER.span("adapt.history") as sp:
        assert sp is _NULL_SPAN
    assert NULL_TRACER.events == ()
    assert not hasattr(NULL_TRACER, "syncs")


def _batch_session(trace: bool, backend: str = "pallas"):
    from repro.api.config import ComputeSection
    cfg = SystemConfig(partition=PartitionSection(strategy="xdgp", k=4),
                       compute=ComputeSection(backend=backend),
                       telemetry=TelemetrySection(trace=trace))
    return DynamicGraphSystem(generators.fem_grid2d(8), cfg)


@pytest.mark.parametrize("record_history", [True, False])
def test_host_syncs_count_history_and_pack_reads(record_history):
    """One ``host_syncs`` sample per adapt(): one read of every round's
    history, in one ``adapt.history`` span, with the history on, plus the
    pack's reads (edge_mask, src, dst for the CPU's ELL plan)."""
    rounds = 3
    system = _batch_session(trace=True)
    system.adapt(rounds, record_history=record_history)
    syncs = [e for e in system.tracer.events
             if e["type"] == "counter" and e["name"] == "host_syncs"]
    assert len(syncs) == 1
    want = {"plan": 3}
    if record_history:
        want["history"] = 1
    assert syncs[0]["attrs"] == want
    assert syncs[0]["value"] == sum(want.values())
    assert system.tracer.syncs == {}              # flushed
    spans = [e["name"] for e in system.tracer.events if e["type"] == "span"]
    assert spans.count("adapt.history") == (1 if record_history else 0)
    assert spans.count("plan.build") == 1 and spans[-1] == "adapt"


@pytest.mark.parametrize("executor,reads", [("jax", 3), ("interpret", 4)])
def test_pack_reads_are_counted(executor, reads):
    """The BSR pack reads edge_mask, src and dst once each, and row_ptr
    back after its upload; the ELL pack the first three."""
    from repro.kernels.migration_kernels import build_plan
    tr = Tracer()
    plan = build_plan(generators.fem_grid2d(8), executor=executor, blk=8,
                      tracer=tr)
    assert plan.kind in ("ell", "bsr")
    assert tr.syncs == {"plan": reads}


def test_converge_and_steps_flush_host_syncs():
    system = _batch_session(trace=True)
    hist = system.converge()
    by = {e["name"]: e for e in system.tracer.events}
    assert by["converge"]["attrs"]["rounds"] == hist.iterations
    # convergence reads each round back once, for its stopping rule
    assert by["host_syncs"]["attrs"] == {"plan": 3,
                                         "history": hist.iterations}
    stream = _session(trace=True)
    for i in range(2):
        stream.step(_events(200, 200, seed=i))
    syncs = [e for e in stream.tracer.events if e["name"] == "host_syncs"]
    assert len(syncs) == 2 and all(s["attrs"]["step"] >= 5 for s in syncs)
    assert not [e for e in stream.tracer.events
                if e["name"] == "migrations"]


def test_compile_time_reaches_traced_sessions_only(monkeypatch):
    import weakref
    from repro.obs import trace as trace_mod
    plain = _batch_session(trace=False)
    traced = _batch_session(trace=True)
    fresh = jax.jit(lambda x: jnp.cumsum(x * 3 + 1))
    fresh(jnp.ones((7, 13, 3))).block_until_ready()
    compiles = [e for e in traced.tracer.events
                if e["type"] == "counter" and e["name"] == "compile_s"]
    assert compiles and sum(e["value"] for e in compiles) > 0
    assert {e["attrs"]["stage"] for e in compiles} <= set(
        trace_mod.COMPILE_STAGES.values())
    assert plain.tracer.events == ()
    # with no tracer alive the listener returns before looking at anything
    monkeypatch.setattr(trace_mod, "_COMPILE_WATCHERS", weakref.WeakSet())
    trace_mod._on_compile_event(
        "/jax/core/compile/backend_compile_duration", 1.0)
    assert plain.tracer.events == ()


def test_profiler_capture_nests_program_spans(tmp_path):
    """A jax.profiler capture of a traced adapt(3) holds the program's
    spans, on the capture's clock and nested as they ran."""
    from benchmarks.chip import spans as chip_spans
    system = _batch_session(trace=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        system.adapt(3)
        jax.block_until_ready(system.labels)
    finally:
        jax.profiler.stop_trace()
    got = chip_spans.load(str(tmp_path))
    names = [s["name"] for s in got]
    assert names.count("xdgp/adapt") == 1
    assert names.count("xdgp/plan.build") == 1
    assert names.count("xdgp/adapt.history") == 1
    top = next(s for s in got if s["name"] == "xdgp/adapt")
    assert top["depth"] == 0
    for s in got:
        if s["name"] in ("xdgp/plan.build", "xdgp/adapt.history"):
            assert s["depth"] == 1 and s["track"] == top["track"]
            assert top["start_ns"] <= s["start_ns"]
            assert (s["start_ns"] + s["dur_ns"]
                    <= top["start_ns"] + top["dur_ns"])
    # the program's own record of the same spans is unchanged beside it
    assert [e["name"] for e in system.tracer.events
            if e["type"] == "span"].count("adapt.history") == 1


def test_tracing_overhead_under_3pct():
    """The §11 budget: enabled tracing costs <3% of superstep wall time.

    Two identical sessions consume the same stream; batches are timed
    interleaved and the min over rounds taken on both sides (min-of-N is
    robust to scheduler noise in a way means are not).  A small absolute
    epsilon guards the comparison on very fast hosts.
    """
    ev = _events(4000, 200, seed=3)
    plain = _session(trace=False)
    traced = _session(trace=True)
    # warmup: absorb jit compilation on both sides
    for i in range(2):
        plain.step(ev[i * 100:(i + 1) * 100])
        traced.step(ev[i * 100:(i + 1) * 100])
    best = {"plain": float("inf"), "traced": float("inf")}
    for j, r in enumerate(range(2, 18, 2)):
        batches = [ev[i * 100:(i + 1) * 100] for i in range(r, r + 2)]
        sides = [("plain", plain), ("traced", traced)]
        if j % 2:                       # alternate order: a load trend during
            sides.reverse()             # the test biases both sides equally
        for tag, system in sides:
            t0 = time.perf_counter()
            for b in batches:
                system.step(b)
            best[tag] = min(best[tag], time.perf_counter() - t0)
    best_plain, best_traced = best["plain"], best["traced"]
    assert best_traced <= best_plain * 1.03 + 1e-3, \
        f"tracing overhead {best_traced / best_plain - 1:.1%} " \
        f"(plain {best_plain * 1e3:.2f}ms, traced {best_traced * 1e3:.2f}ms)"


# ---------------------------------------------------------------------------
# Report CLI
# ---------------------------------------------------------------------------

def _write_trace(path, scale=1.0):
    tr = Tracer(meta={"label": "x"})
    with tr.span("superstep"):
        time.sleep(0.001)
        # synthetic span: exact duration, so the a-vs-b comparison below is
        # deterministic under suite load (a real sleep can overshoot 3x)
        tr.add_span("migrate", 0.002 * scale)
    tr.write_jsonl(str(path))


def test_report_cli_single_and_compare(tmp_path, capsys):
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_trace(a)
    _write_trace(b, scale=3.0)
    assert report_main([str(a)]) == 0
    out = capsys.readouterr().out
    assert "superstep" in out and "migrate" in out and "share" in out
    assert report_main([str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "ratio" in out and "vs" in out
    assert report_main([str(a), str(b), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["b"]["phases"]["migrate"]["total_s"] > \
        doc["a"]["phases"]["migrate"]["total_s"]


# ---------------------------------------------------------------------------
# Sharded traced smoke (subprocess under 8 fake devices)
# ---------------------------------------------------------------------------

def test_traced_sharded_session_names_comm_phases(tmp_path):
    out = _run(f"""
import numpy as np
from repro.api import DynamicGraphSystem, PartitionSection, SystemConfig
from repro.api.config import GraphSection, TelemetrySection
from repro.obs.schema import validate_trace_file

cfg = SystemConfig(graph=GraphSection(n_cap=256, e_cap=2048),
                   partition=PartitionSection(strategy="xdgp", k=8,
                                              adapt_iters=2),
                   telemetry=TelemetrySection(trace=True,
                                              trace_comm_probe=True))
rng = np.random.default_rng(0)
ev = np.stack([np.arange(300) // 4, rng.integers(0, 200, 300),
               rng.integers(0, 200, 300)], 1).astype(np.int64)
local = DynamicGraphSystem(None, cfg)
sharded = DynamicGraphSystem(None, cfg).distribute()
for i in range(3):
    local.step(ev[i * 100:(i + 1) * 100])
    sharded.step(ev[i * 100:(i + 1) * 100])
assert bool((local.labels == sharded.labels).all()), "parity broke"
path = sharded.tracer.write_jsonl({str(tmp_path / 'sh.jsonl')!r})
validate_trace_file(path)
print(sorted(sharded.tracer.phase_totals()))
""")
    phases = set(eval(out.strip().splitlines()[-1]))
    assert phases == SHARDED_PHASES
    # the committed deliverable's named spans, explicitly:
    for must in ("comm/halo_exchange", "comm/quota_collective",
                 "kernel/score", "cluster/host_sync"):
        assert must in phases, must


def test_telemetry_section_round_trips_new_knobs():
    cfg = SystemConfig(telemetry=TelemetrySection(
        trace=True, trace_comm_probe=True, metrics=True))
    assert SystemConfig.from_dict(cfg.to_dict()) == cfg
    with pytest.raises(ValueError, match="unknown keys.*telemetry"):
        SystemConfig.from_dict({"telemetry": {"tracing": True}})
