"""Runner of the ``adapt`` mode: batch adaptation from a hash start, again
and again for the whole window.

Set-up (timed as ``setup_s``): the configuration's graph is built (the FEM
lattice on the host, a Kronecker graph on the device from the seed) and one
``adapt(rounds)`` runs on a fresh session (it compiles, or loads from the
persistent cache, every program an adaptation runs).

Window: each repetition r builds a fresh hash-start session with session
seed ``seed + r`` off the clock, then times one ``adapt(rounds)`` up to its
final ``block_until_ready``. ``time_to_cut_s`` is the timed total over the
repetitions that completed. With ``--trace 1`` one more repetition runs
under the profiler after the window.

Check: the plain reference runs the same rounds from the same start and
seed for ``checked_repetitions`` repetitions drawn from the seed (and the
profiled one); the assignment, the deferred moves and the session's
tracked cut must match it.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time
from typing import Dict, List

import jax
import numpy as np

from . import cost, deployment, xplane
from .harness import Cell, CompileCounter, Outcome, memory_peak
from .reference import partition


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        started: float, devices) -> Outcome:
    rounds = cell.traffic["rounds"]
    graph = deployment.graph(cell, seed)

    def new_session(session_seed: int):
        return deployment.session(cell, graph, session_seed, trace=False,
                                  devices=devices, stream=False)

    system = new_session(seed)
    system.adapt(rounds)
    jax.block_until_ready(system.labels)
    plan = system.scoring_plan
    del system

    counter = CompileCounter()
    results: List[Dict] = []
    timed: List[float] = []
    resets: List[float] = []
    counter.armed = True
    t0 = time.perf_counter()
    setup_s = t0 - started

    def repetition() -> float:
        r = len(results)
        t = time.perf_counter()
        system = new_session(seed + r)
        jax.block_until_ready((system.labels, system.tracker.cut))
        resets.append(time.perf_counter() - t)
        t = time.perf_counter()
        system.adapt(rounds)
        jax.block_until_ready((system.labels, system.tracker.cut))
        took = time.perf_counter() - t
        results.append({"seed": seed + r, "labels": system.labels,
                        "pending": system.state.pending,
                        "cut": system.tracker.cut})
        return took

    while time.perf_counter() - t0 < seconds:
        timed.append(repetition())
    counter.armed = False

    profile = None
    trace_lines: Dict[str, int] = {}
    if trace:
        logdir = tempfile.mkdtemp(prefix="chip-trace-")
        try:
            jax.profiler.start_trace(logdir)
            with jax.profiler.TraceAnnotation("bench/adapt"):
                repetition()
            jax.profiler.stop_trace()
            events = xplane.load(logdir, trace_lines)
            window_ns = xplane.annotated(events)
            if window_ns:
                profile = xplane.reduce(events, window_ns,
                                        kernel=cell.config.get("kernel_op"))
        finally:
            shutil.rmtree(logdir, ignore_errors=True)

    peak = memory_peak(devices)
    # the reference takes ~2 s a repetition: hold a sample drawn from the
    # seed to it, the profiled repetition always among them
    take = min(cell.traffic["checked_repetitions"], len(results))
    picks = set(np.random.default_rng(seed).choice(len(results), take,
                                                   replace=False).tolist())
    if trace:
        picks.add(len(results) - 1)
    finals = [{k: (np.asarray(v) if k != "seed" else v) for k, v in r.items()}
              for i, r in enumerate(results) if i in picks]
    ran = len(results)
    del results
    gc.collect()

    t = time.perf_counter()
    checks, failed = compare(cell, graph, finals)
    check_s = time.perf_counter() - t

    n = graph.n_cap
    edges = int(np.asarray(graph.edge_mask).sum())
    work = cost.scoring_pass(2 * edges, n, cell.config["session"]["k"])
    notes = [f"setup: {setup_s:.3f} s; batch plan {plan}",
             f"window: {len(timed)} adaptations of {rounds} rounds, timed "
             f"total {sum(timed):.3f} s, off-clock resets total "
             f"{sum(resets[:len(timed)]):.3f} s; executables built in the "
             f"window {counter.built}, loaded from the persistent cache "
             f"{counter.cache_hits}",
             f"check: reference replay of {len(finals)} of {ran} adaptations "
             f"(sampled) took "
             f"{check_s:.3f} s",
             f"work per scoring pass: {work['bytes']} bytes, "
             f"{work['flops']} operations; plan tiles "
             f"{_tile_bytes(plan)} bytes"]
    if trace:
        least, bound = cost.least_time(work, cost.peaks(
            devices[0].device_kind))
        notes.append(f"trace: device lines {trace_lines}; reduced {profile};"
                     f" least time of a scoring pass {least:.9f} s, "
                     f"{bound}-bound")
    metrics = {"setup_s": setup_s,
               "time_to_cut_s": sum(timed) / len(timed) if timed
               else float("nan")}
    run_data = {"trace": profile, "work": work,
                "replay": {"seeds": [r["seed"] for r in finals]},
                "counters": {"rounds_profiled": rounds if trace else 0}}
    return Outcome(metrics=metrics, run=run_data, checks=checks,
                   attempted=ran, failed=failed,
                   memory_peak_bytes=peak, notes=notes)


def _tile_bytes(plan) -> int:
    if not plan or plan.get("kind") != "bsr":
        return 0
    return 4 * plan["nnzb"] * plan["blk"] * plan["blk"]


def _reference(cell: Cell, graph, seed: int, low: bool):
    s = cell.config["session"]
    k, n = s["k"], graph.n_cap
    labels, pending, _ = partition.migrate(
        graph.src, graph.dst, graph.edge_mask, graph.node_mask,
        partition.hash_start(n, k), np.full((n,), -1, np.int32),
        partition.capacity(n, k, s["slack"]), jax.random.PRNGKey(seed),
        rounds=cell.traffic["rounds"], s=s["s"], k=k, flush=False, low=low)
    cut = partition.cut_edges(graph.src, graph.dst, graph.edge_mask, labels)
    return {"seed": seed, "labels": np.asarray(labels),
            "pending": np.asarray(pending), "cut": int(cut)}


def control(cell: Cell, seed: int, replay: Dict):
    """The comparison with the reference in bfloat16 put in the session's
    place, for the repetitions' seeds of a run of ``seed``."""
    graph = deployment.graph(cell, seed)
    finals = [_reference(cell, graph, rep_seed, low=True)
              for rep_seed in replay["seeds"]]
    return compare(cell, graph, finals)[0]


def compare(cell: Cell, graph, finals: List[Dict]):
    """Hold every repetition to the reference; returns (checks, how many
    repetitions disagreed)."""
    label_gap = pending_gap = cut_gap = failed = 0
    for rep in finals:
        want = _reference(cell, graph, rep["seed"], low=False)
        labels, pending, cut = want["labels"], want["pending"], want["cut"]
        a = int((rep["labels"] != labels).sum())
        b = int((rep["pending"] != pending).sum())
        c = abs(int(rep["cut"]) - cut)
        label_gap += a
        pending_gap += b
        cut_gap = max(cut_gap, c)
        failed += bool(a or b or c)
    limits = cell.config["limits"]
    return {"label_mismatch": {"value": label_gap,
                               "limit": limits["label_mismatch"]},
            "pending_mismatch": {"value": pending_gap,
                                 "limit": limits["pending_mismatch"]},
            "cut_gap": {"value": cut_gap, "limit": limits["cut_gap"]}}, failed
