"""Each round reports its own quality, and the batch drivers' history is
built from those reports.

``migrate_step`` returns the cut edges and the live occupancy's max and sum
of the assignment it committed, taken from the scorer's own counts (cut =
E − ½·Σ_v counts[v, label(v)]) and the quota's occupancy. These must equal
``cut_edges``/``occupancy`` of the state it returns, on every backend and
plan. The drivers' history must equal, round by round, the definition it
had when every round read ``cut_ratio`` and ``imbalance`` back: for steps
that report their quality (xdgp) and for steps that do not (spinner).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import initial_partition, make_state, occupancy
from repro.core import repartitioner
from repro.core.migration import MigrationStats, migrate_step
from repro.core.partition_state import imbalance
from repro.core.repartitioner import adapt_rounds, run_to_convergence
from repro.core.spinner import spinner_step
from repro.graph import generators
from repro.graph.structure import (Graph, GraphDelta, apply_delta, cut_edges,
                                   cut_ratio, from_edges)
from repro.kernels.migration_kernels import build_plan
from repro.obs.trace import Tracer


def _stream_graph() -> Graph:
    """A graph as a stream leaves it: dead node and edge slots, deleted
    vertices, and appended edges with a duplicate and a self-loop."""
    rng = np.random.default_rng(7)
    n = 90
    g = from_edges(rng.integers(0, n, 260), rng.integers(0, n, 260), n,
                   n_cap=n + 14, e_cap=340)
    add = np.array([[3, 91], [91, 92], [5, 6], [5, 6], [40, 40], [92, 10]],
                   np.int32)
    a_cap, d_cap = 10, 6
    src = np.full(a_cap, -1, np.int32)
    dst = np.full(a_cap, -1, np.int32)
    src[:len(add)], dst[:len(add)] = add[:, 0], add[:, 1]
    dels = np.full(d_cap, -1, np.int32)
    dels[:3] = [0, 17, 63]
    delta = GraphDelta(add_src=jnp.asarray(src), add_dst=jnp.asarray(dst),
                       add_mask=jnp.asarray(src >= 0),
                       del_nodes=jnp.asarray(dels),
                       del_mask=jnp.asarray(dels >= 0))
    return apply_delta(g, delta)


_GRAPHS = {
    "fem3d": lambda: generators.fem_cube(5),
    "mesh2d": lambda: generators.fem_grid2d(9),
    "stream": _stream_graph,
}
# (graph, backend, executor, plan kind)
_CASES = [
    ("fem3d", "ref", None, None),
    ("fem3d", "pallas", "jax", "flat"),
    ("fem3d", "pallas", "jax", "ell"),
    ("fem3d", "pallas", "interpret", "bsr"),
    ("mesh2d", "ref", None, None),
    ("mesh2d", "pallas", "interpret", "bsr"),
    ("stream", "ref", None, None),
    ("stream", "pallas", "jax", "flat"),
    ("stream", "pallas", "jax", "ell"),
    ("stream", "pallas", "interpret", "bsr"),
]


def _plan(graph, executor, kind):
    if kind in (None, "flat"):
        return None
    plan = build_plan(graph, executor=executor, blk=8)
    assert plan.kind == kind
    return plan


@pytest.mark.parametrize("name,backend,executor,kind", _CASES)
def test_round_reports_quality_of_its_committed_state(name, backend,
                                                      executor, kind):
    g = _GRAPHS[name]()
    k = 5
    lab = initial_partition(g, k, "hsh")
    state = make_state(g, lab, k, slack=0.2, seed=11)
    plan = _plan(g, executor, kind)
    committed_rounds = 0
    for _ in range(4):
        state, stats = migrate_step(state, g, plan, s=0.5, backend=backend,
                                    executor=executor)
        occ = np.asarray(occupancy(state, g.node_mask))
        assert int(stats.cut_edges) == int(cut_edges(g, state.assignment))
        assert int(stats.occupancy_max) == int(occ.max())
        assert int(stats.occupancy_sum) == int(occ.sum())
        committed_rounds += int(stats.committed) > 0
    # rounds after the first commit the moves deferred by the one before
    assert committed_rounds >= 2


def _spans(tracer):
    return [e["name"] for e in tracer.events if e["type"] == "span"]


def _by_hand(g, state, iters, step):
    """The history as it was defined before: each round's state read back
    through ``cut_ratio`` and ``imbalance``."""
    hist = {"cut_ratio": [], "migrations": [], "willing": [],
            "imbalance": []}
    for _ in range(iters):
        state, stats = step(state)
        hist["cut_ratio"].append(float(cut_ratio(g, state.assignment)))
        hist["migrations"].append(int(stats.committed))
        hist["willing"].append(int(stats.willing))
        hist["imbalance"].append(float(imbalance(state, g.node_mask)))
    return state, hist


_STEPS = {
    "xdgp": lambda g, plan: lambda st: migrate_step(st, g, plan, s=0.5,
                                                    backend="pallas",
                                                    executor="interpret"),
    "spinner": lambda g, plan: lambda st: spinner_step(st, g, None, s=0.5),
}


@pytest.mark.parametrize("strategy", ["xdgp", "spinner"])
def test_history_equals_the_old_definition(strategy):
    g = _stream_graph()
    k = 4
    state = make_state(g, initial_partition(g, k, "hsh"), k, slack=0.2,
                       seed=5)
    step = _STEPS[strategy](g, build_plan(g, executor="interpret", blk=8))
    iters = 6
    want_state, want = _by_hand(g, state, iters, step)
    tr = Tracer()
    got_state, hist = adapt_rounds(g, state, iters, step_fn=step, tracer=tr)
    assert hist.as_dict() == want
    assert tr.syncs == {"history": 1}
    assert _spans(tr) == ["adapt.history"]
    np.testing.assert_array_equal(np.asarray(got_state.assignment),
                                  np.asarray(want_state.assignment))
    # the convergence driver: one read a round, the same history
    tr = Tracer()
    _, conv = run_to_convergence(g, state, patience=3, max_iters=12,
                                 step_fn=step, tracer=tr)
    _, want = _by_hand(g, state, conv.iterations, step)
    assert conv.as_dict() == want
    assert tr.syncs == {"history": conv.iterations}


def test_adapt_without_history_reads_nothing(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("read or computed a history nobody asked for")

    monkeypatch.setattr(repartitioner, "read_rounds", refuse)
    monkeypatch.setattr(repartitioner, "round_row", refuse)
    g = generators.fem_grid2d(6)
    state = make_state(g, initial_partition(g, 3, "hsh"), 3, seed=2)
    tr = Tracer()
    for step in (None, _STEPS["spinner"](g, None)):
        _, hist = adapt_rounds(g, state, 3, record_history=False,
                               step_fn=step, tracer=tr)
        assert hist.iterations == 0
    assert tr.syncs == {} and _spans(tr) == []


def test_adapt_waits_for_the_round_ahead_of_its_bound():
    """No read a round, yet the host may not queue rounds without end:
    after dispatching round t it waits for round t − _AHEAD."""
    log = []

    class Round:                    # stands in for a round's device scalar
        def __init__(self, t):
            self.t = t

        def block_until_ready(self):
            log.append(("wait", self.t))
            return self

    def step(st):
        t = sum(kind == "step" for kind, _ in log)
        log.append(("step", t))
        return st, MigrationStats(Round(t), 0, 0)

    g = generators.fem_grid2d(4)
    state = make_state(g, initial_partition(g, 2, "hsh"), 2, seed=1)
    iters, ahead = 20, repartitioner._AHEAD
    adapt_rounds(g, state, iters, record_history=False, step_fn=step)
    want = []
    for t in range(iters):
        want.append(("step", t))
        if t >= ahead:
            want.append(("wait", t - ahead))
    assert log == want
