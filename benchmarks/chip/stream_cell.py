"""Runner of the ``stream`` mode: an open-loop edge stream into one session.

Set-up (timed as ``setup_s``): the base graph is drawn on the device from
the seed and handed to a ``DynamicGraphSystem``; the live stream and its
due times are drawn; ``warmup_supersteps`` full batches go through
``step`` (they compile, or load from the persistent cache, every program a
superstep runs), and the periodic drift check is run once so its programs
are loaded too.

Window: for ``--seconds``, each superstep takes every event due by the time
it starts (events are due at fixed offsets, whatever the session does) and
returns once its device work is done. The generator starts
``lead_seconds`` before the window opens, so a stream offered above the
session's capacity opens the window with a full queue, as it would have
in its steady state, and not with the one event due at its first instant. ``events_per_s`` is the events the
window's supersteps committed over the time until the last of them
returned; ``commit_p95_ms`` is the 95th percentile, over every event due
in the window, of due → return of the superstep that committed it. Where
the traffic asks to ``drain``, supersteps go on after the window, taking no
new events, until every event due in the window is committed.

With ``--trace 1`` the session records its phase spans (they fence each
phase, so the window's timing is not the untraced one) and, after the
window, ``profile_supersteps`` more supersteps run under the profiler; the
capture holds the same spans as ``xdgp/<name>`` on its own clock, and they
name the device's idle gaps.

Check: the plain reference replays every superstep the session ran, on the
same generated graph and the same batches, and the run is held to it.
"""
from __future__ import annotations

import gc
import math
import shutil
import tempfile
import time
from typing import Any, Dict, List

import jax
import numpy as np

from . import deployment, gen, spans, stats, xplane
from .harness import (BenchError, Cell, CompileCounter, Outcome,
                      memory_peaks)
from .reference.session import Replay


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        started: float, devices) -> Outcome:
    t_setup = {}
    tr = cell.traffic
    sess = cell.config["session"]
    g = deployment.graph_spec(cell, "kronecker")   # the live stream's too
    a_cap = sess["a_cap"]

    t = time.perf_counter()
    graph = deployment.graph(cell, seed, sess["stream_edge_slots"])
    base_edges = int(graph.edge_mask.sum())
    rate = tr["rate_share_of_knee"] * tr["knee_events_per_s"]
    warm = tr["warmup_supersteps"] * a_cap
    lead = tr["lead_seconds"]
    count = warm + math.ceil(rate * (lead + seconds + tr["tail_seconds"]))
    events = gen.stream_events(seed, count, scale=g["scale"], a=g["A"],
                               b=g["B"], c=g["C"])
    due = gen.due_offsets(tr["arrivals"], rate, count - warm, seed) - lead
    t_setup["generate"] = time.perf_counter() - t

    t = time.perf_counter()
    system = deployment.session(cell, graph, seed, trace=trace,
                                devices=devices, stream=True)
    del graph
    t_setup["session"] = time.perf_counter() - t

    t = time.perf_counter()
    batches: List[int] = []          # events handed to every step, in order
    records = []
    for i in range(tr["warmup_supersteps"]):
        batch = events[i * a_cap:(i + 1) * a_cap]
        batches.append(batch.shape[0])
        records.append(system.step(batch))
    from repro.stream.metrics import drift_check
    jax.block_until_ready(drift_check(system.tracker, system.graph,
                                      system.labels)[0])
    t_setup["warmup"] = time.perf_counter() - t

    counter = CompileCounter()
    stream = events[warm:]
    window: List[Dict[str, float]] = []
    tracer = system.tracer
    if trace:
        tracer.events.clear()
    counter.armed = True
    t0 = time.perf_counter()
    setup_s = t0 - started
    sent = 0

    def superstep(now_s: float, until: float) -> None:
        nonlocal sent
        hi = int(np.searchsorted(due, until, side="right"))
        batch = stream[sent:hi]
        sent = hi
        rec = system.step(batch)
        batches.append(batch.shape[0])
        records.append(rec)
        window.append({"start": now_s, "events": batch.shape[0],
                       "adds": rec.adds, "ret": time.perf_counter() - t0,
                       "backlog": rec.backlog_adds})

    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        superstep(now, now)
    counter.armed = False
    in_window = len(window)
    span_events = list(tracer.events) if trace else []
    backlog_end = window[-1]["backlog"] if window else 0

    profile = None
    trace_lines: Dict[str, int] = {}
    if trace:
        logdir = tempfile.mkdtemp(prefix="chip-trace-")
        try:
            jax.profiler.start_trace(logdir)
            for _ in range(tr["profile_supersteps"]):
                with jax.profiler.TraceAnnotation("bench/superstep"):
                    now = time.perf_counter() - t0
                    superstep(now, now)
            jax.profiler.stop_trace()
            captured = xplane.load(logdir, trace_lines)
            window_ns = xplane.annotated(captured)
            if window_ns:
                profile = xplane.reduce(captured, window_ns,
                                        spans=spans.load(logdir),
                                        chips=len(devices))
        finally:
            shutil.rmtree(logdir, ignore_errors=True)

    due_in_window = int(np.searchsorted(due, seconds, side="left"))
    if tr["drain"]:
        # hand over the rest of the window's events, then empty supersteps
        # until they are all committed or nothing is left queued; a minute
        # past the close at most
        while (sum(w["adds"] for w in window) < due_in_window
               and (sent < due_in_window or window[-1]["backlog"])
               and time.perf_counter() - t0 < seconds + 60):
            now = time.perf_counter() - t0
            superstep(now, min(now, np.nextafter(seconds, 0)))
    uncommitted = max(due_in_window - sum(w["adds"] for w in window), 0)

    inserted = sum(r.adds for r in records)
    if inserted > sess["stream_edge_slots"]:
        raise BenchError(f"the run inserted {inserted} edges, more than the "
                         f"{sess['stream_edge_slots']} spare edge slots")
    peaks = memory_peaks(devices)
    final = {
        "labels": np.asarray(system.labels),
        "pending": np.asarray(system.state.pending),
        "src": np.asarray(system.graph.src),
        "dst": np.asarray(system.graph.dst),
        "edge_mask": np.asarray(system.graph.edge_mask),
        "node_mask": np.asarray(system.graph.node_mask),
        "rank": np.asarray(system.program_state)[:, 0],
    }
    plan = system.scoring_plan
    del system
    gc.collect()

    t = time.perf_counter()
    checks, failed_steps = compare(cell, seed, events, batches, records,
                                   final)
    check_s = time.perf_counter() - t

    steps = window[:in_window]
    committed = sum(w["adds"] for w in steps)
    span_s = steps[-1]["ret"] if steps else float("nan")
    metrics = {"setup_s": setup_s,
               "events_per_s": committed / span_s if steps else 0.0}
    notes = [f"setup: {setup_s:.3f} s (generate {t_setup['generate']:.3f}, "
             f"session {t_setup['session']:.3f}, warm-up "
             f"{t_setup['warmup']:.3f}); base graph {base_edges} edges, "
             f"{int(final['node_mask'].sum())} live vertices at the end",
             f"window: {in_window} supersteps, {committed} events committed "
             f"in {span_s:.3f} s, backlog at end {backlog_end}; "
             f"executables built in the window {counter.built}, loaded from "
             f"the persistent cache {counter.cache_hits}; batch plan {plan}",
             f"superstep times in the window (s): "
             f"{[round(w['ret'] - w['start'], 4) for w in steps]}",
             f"memory peak per chip (bytes): {peaks}",
             f"check: reference replay of {len(batches)} supersteps took "
             f"{check_s:.3f} s"]
    if trace:
        notes.append(f"trace: device lines {trace_lines}; reduced {profile}")
    attempted = (due_in_window if tr["drain"]
                 else sum(w["events"] for w in steps))
    failed = sum(r.invalid_events + r.stale_dropped + r.dup_dropped
                 for r in records) + failed_steps
    if tr["drain"]:
        checks["uncommitted"] = {"value": uncommitted, "limit": 0}
        failed += uncommitted
        lat = stats.commit_latencies(
            due[:due_in_window - uncommitted], [w["adds"] for w in window],
            [w["ret"] for w in window])
        metrics["commit_p95_ms"] = 1e3 * stats.percentile(lat, 95)
        notes.append(f"latency: p50 {1e3 * stats.percentile(lat, 50):.3f} ms,"
                     f" p95 {metrics['commit_p95_ms']:.3f} ms over "
                     f"{lat.size} events")
    run_data = {
        "spans": span_events,
        "counters": {"backlog_end": backlog_end,
                     "supersteps": in_window},
        "trace": profile,
        "replay": {"events": events, "batches": batches},
    }
    return Outcome(metrics=metrics, run=run_data, checks=checks,
                   attempted=attempted, failed=failed,
                   memory_peak_bytes=max(peaks), notes=notes)


def _replay(cell: Cell, seed: int, low: bool) -> Replay:
    sess = cell.config["session"]
    src, dst, edge_mask, node_mask, _ = deployment.kronecker_arrays(
        cell, seed, sess["stream_edge_slots"])
    return Replay(src, dst, edge_mask, node_mask, k=sess["k"], s=sess["s"],
                  slack=sess["slack"], adapt_iters=sess["adapt_iters"],
                  a_cap=sess["a_cap"], seed=seed, low=low)


def compare(cell: Cell, seed: int, events: np.ndarray, batches: List[int],
            records, final: Dict[str, np.ndarray]):
    """Replay the session on the reference; returns (checks, supersteps
    whose tracked numbers disagreed)."""
    ref = _replay(cell, seed, low=False)
    gaps = []
    at = 0
    for size, rec in zip(batches, records):
        take, cut, live = ref.step(events[at:at + size])
        at += size
        gaps.append(abs(rec.adds - take) + abs(rec.cut_edges - int(cut))
                    + abs(rec.live_edges - int(live)))
    return checks_against(ref, final, gaps, cell.config["limits"])


def control(cell: Cell, seed: int, replay: Dict[str, Any]):
    """The comparison with the reference in bfloat16 (``low``) put in the
    session's place, on the batches a run of the session took."""
    events, batches = replay["events"], replay["batches"]
    ref = _replay(cell, seed, low=False)
    low = _replay(cell, seed, low=True)
    gaps = []
    at = 0
    for size in batches:
        got = low.step(events[at:at + size])
        want = ref.step(events[at:at + size])
        at += size
        gaps.append(sum(abs(int(a) - int(b)) for a, b in zip(got, want)))
    final = {"labels": np.asarray(low.labels),
             "pending": np.asarray(low.pending),
             "src": np.asarray(low.src), "dst": np.asarray(low.dst),
             "edge_mask": np.asarray(low.edge_mask),
             "node_mask": np.asarray(low.node_mask),
             "rank": np.asarray(low.rank)}
    return checks_against(ref, final, gaps, cell.config["limits"])[0]


def checks_against(ref: Replay, final: Dict[str, np.ndarray],
                   gaps: List[int], limits: Dict[str, float]):
    labels = np.asarray(ref.labels)
    graph_gap = int((final["src"] != np.asarray(ref.src)).sum()
                    + (final["dst"] != np.asarray(ref.dst)).sum()
                    + (final["edge_mask"] != np.asarray(ref.edge_mask)).sum()
                    + (final["node_mask"] != np.asarray(ref.node_mask)).sum()
                    + (final["pending"] != np.asarray(ref.pending)).sum())
    live = np.asarray(ref.node_mask)
    want = np.asarray(ref.rank)[live].astype(np.float64)
    got = final["rank"][live].astype(np.float64)
    rel = float(np.max(np.abs(got - want) / want)) if want.size else 0.0
    checks = {
        "label_mismatch": {"value": int((final["labels"] != labels).sum()),
                           "limit": limits["label_mismatch"]},
        "graph_mismatch": {"value": graph_gap,
                           "limit": limits["graph_mismatch"]},
        "tracked_gap": {"value": int(max(gaps) if gaps else 0),
                        "limit": limits["tracked_gap"]},
        "pagerank_rel_err": {"value": rel,
                             "limit": limits["pagerank_rel_err"]},
    }
    return checks, sum(1 for g in gaps if g)
