"""The comparison that decides ``correct``, on tiny cells on the CPU.

Each cell comes out correct as it is; its control (the reference in
bfloat16 in the session's place) and every fault the cell can have,
planted under the timed path, come out not correct.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from chip import harness
from chip.tests.cpu import cells, run_tiny, tiny_cell

CELLS = cells()
MODE = {w: harness.load_cell(w).traffic["mode"] for w in CELLS}


@pytest.mark.parametrize("workload", CELLS)
def test_cell_is_correct(workload):
    out = run_tiny(workload)
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted > 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_refused(workload):
    out = run_tiny(workload)
    checks = harness.runner(MODE[workload]).control(tiny_cell(workload), 7,
                                                    out.run["replay"])
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def _state_unchanged(monkeypatch):
    from repro.api.strategy import XdgpAdaptive
    monkeypatch.setattr(XdgpAdaptive, "adapt",
                        lambda self, graph, state, ctx: state)
    real = XdgpAdaptive.adapt_rounds
    monkeypatch.setattr(
        XdgpAdaptive, "adapt_rounds",
        lambda self, graph, state, iters, ctx:
        (state, real(self, graph, state, 0, ctx)[1]))


def _answer_altered(monkeypatch):
    from repro.api.strategy import XdgpAdaptive
    real_adapt, real_rounds = XdgpAdaptive.adapt, XdgpAdaptive.adapt_rounds

    def flip(state):
        lab = state.assignment
        return dataclasses.replace(state, assignment=lab.at[3].set(
            (lab[3] + 1) % state.k))

    monkeypatch.setattr(XdgpAdaptive, "adapt",
                        lambda self, g, st, ctx: flip(real_adapt(self, g, st,
                                                                 ctx)))
    monkeypatch.setattr(
        XdgpAdaptive, "adapt_rounds",
        lambda self, g, st, it, ctx: (lambda r: (flip(r[0]), r[1]))(
            real_rounds(self, g, st, it, ctx)))


def _half_batch(monkeypatch):
    from repro.stream.ingest import WindowIngestor
    real = WindowIngestor.ingest
    monkeypatch.setattr(WindowIngestor, "ingest",
                        lambda self, events, now: real(
                            self, np.asarray(events)[::2], now))


def _half_graph(monkeypatch):
    """FEM: half of the edges left out of the scoring."""
    import repro.core.repartitioner as rep
    real = rep.migrate_step

    def half(state, graph, plan=None, **kw):
        mask = graph.edge_mask & (jnp.arange(graph.e_cap) % 2 == 0)
        return real(state, dataclasses.replace(graph, edge_mask=mask), None,
                    **kw)

    monkeypatch.setattr(rep, "migrate_step", half)


# the faults a cell of each mode can have: a stream has batches to halve,
# a batch adaptation a graph to score only half of
MODE_FAULTS = {"stream": (_state_unchanged, _half_batch, _answer_altered),
               "adapt": (_state_unchanged, _half_graph, _answer_altered)}
FAULTS = [(w, f) for w in CELLS for f in MODE_FAULTS[MODE[w]]]


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w}-{f.__name__.strip('_')}"
                              for w, f in FAULTS])
def test_fault_is_refused(workload, fault, monkeypatch):
    fault(monkeypatch)
    out = run_tiny(workload)
    assert not out.correct, out.checks
