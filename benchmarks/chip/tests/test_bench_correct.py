"""The comparison that decides ``correct``, on tiny cells on the CPU.

Each cell comes out correct as it is; its control (the reference in
bfloat16 in the session's place) and every fault the cell can have,
planted under the timed path of the cell's backend, come out not correct.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip import harness
from chip.tests.cpu import (cells, four_chip_stream, on_chips, run_tiny,
                            tiny_cell)

CELLS = cells()
LOADED = {w: harness.load_cell(w) for w in CELLS}
MODE = {w: LOADED[w].traffic["mode"] for w in CELLS}


@pytest.mark.parametrize("workload", CELLS)
@on_chips()
def test_cell_is_correct(workload):
    out = run_tiny(workload)
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted > 0


@pytest.mark.parametrize("workload", CELLS)
@on_chips()
def test_control_is_refused(workload):
    out = run_tiny(workload)
    checks = harness.runner(MODE[workload]).control(tiny_cell(workload), 7,
                                                    out.run["replay"])
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def _state_unchanged(monkeypatch):
    """The local strategy's step, and the sharded backend's cluster step,
    hand back the state they were given."""
    from repro.api.backend import ShardedBackend
    from repro.api.strategy import XdgpAdaptive
    monkeypatch.setattr(XdgpAdaptive, "adapt",
                        lambda self, graph, state, ctx: state)
    real = XdgpAdaptive.adapt_rounds
    monkeypatch.setattr(
        XdgpAdaptive, "adapt_rounds",
        lambda self, graph, state, iters, ctx:
        (state, real(self, graph, state, 0, ctx)[1]))
    real_step_fn = ShardedBackend._step_fn

    def unchanged_step_fn(self, *args, **kwargs):
        step = real_step_fn(self, *args, **kwargs)
        return lambda state: (state, step(state)[1])

    monkeypatch.setattr(ShardedBackend, "_step_fn", unchanged_step_fn)


def _answer_altered(monkeypatch):
    from repro.api.backend import ShardedBackend
    from repro.api.strategy import XdgpAdaptive
    real_adapt, real_rounds = XdgpAdaptive.adapt, XdgpAdaptive.adapt_rounds
    real_sharded = ShardedBackend.adapt

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
    monkeypatch.setattr(
        ShardedBackend, "adapt",
        lambda self, strategy, g, st, ctx: flip(real_sharded(
            self, strategy, g, st, ctx)))


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


def _exchange_left_out(monkeypatch):
    """Sharded: the halo exchange between chips left out, so each chip
    reads the boundary labels of the others as zeros."""
    import repro.core.distributed as dist

    def local_only(local_feat, dg_local, axis=dist.AXIS):
        bnd = local_feat[dg_local.boundary[0]]
        bnd = jnp.where(dg_local.boundary_ok[0][:, None], bnd, 0)
        halo = jnp.zeros((jax.lax.axis_size(axis) * bnd.shape[0],
                          bnd.shape[1]), bnd.dtype)
        return jax.lax.dynamic_update_slice(
            halo, bnd, (jax.lax.axis_index(axis) * bnd.shape[0], 0))

    monkeypatch.setattr(dist, "_halo_exchange", local_only)


# the faults a cell of each mode can have: a stream has batches to halve,
# a batch adaptation a graph to score only half of; a cell on the sharded
# backend has an exchange between its chips to leave out
MODE_FAULTS = {"stream": (_state_unchanged, _half_batch, _answer_altered),
               "adapt": (_state_unchanged, _half_graph, _answer_altered)}
BACKEND_FAULTS = {"local": (), "sharded": (_exchange_left_out,)}
FAULTS = [(w, f) for w in CELLS
          for f in MODE_FAULTS[MODE[w]] + BACKEND_FAULTS[
              LOADED[w].config["session"]["cluster_backend"]]]


# a stream on four chips, the sharded backend: every fault a stream can have,
# planted under the cluster step, and the exchange between chips left out
FOUR_CHIP_FAULTS = MODE_FAULTS["stream"] + BACKEND_FAULTS["sharded"]


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w}-{f.__name__.strip('_')}"
                              for w, f in FAULTS])
@on_chips()
def test_fault_is_refused(workload, fault, monkeypatch):
    fault(monkeypatch)
    out = run_tiny(workload)
    assert not out.correct, out.checks


@on_chips(lambda: four_chip_stream().chips)
def test_four_chip_stream_is_correct_and_its_control_refused():
    cell = four_chip_stream()
    out = run_tiny(cell)
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted > 0
    checks = harness.runner("stream").control(cell, 7, out.run["replay"])
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


@pytest.mark.parametrize("fault", FOUR_CHIP_FAULTS,
                         ids=[f.__name__.strip("_") for f in FOUR_CHIP_FAULTS])
@on_chips(lambda **_: four_chip_stream().chips)
def test_fault_is_refused_on_four_chips(fault, monkeypatch):
    fault(monkeypatch)
    out = run_tiny(four_chip_stream())
    assert not out.correct, out.checks
