"""Each cell of ``BENCHMARK.json`` is built from its files alone: a runner
for its traffic's mode, a reader for each per-layer metric listed for it,
and a graph and a session from its configuration, in which every key is
honoured or the run is refused."""
import dataclasses
import glob
import os

import jax
import pytest

from chip import deployment, harness, xplane
from chip.tests.cpu import (benchmark, cell_chips, cells, four_chip_stream,
                            on_chips, run_tiny, tiny, tiny_cell)

CELLS = cells()
LOADED = {w: harness.load_cell(w) for w in CELLS}
STREAM = [w for w in CELLS if LOADED[w].traffic["mode"] == "stream"]
CONFIGS = sorted(glob.glob(os.path.join(harness.HERE, "configs", "*.json")))


def _with(cell: harness.Cell, **config) -> harness.Cell:
    return dataclasses.replace(cell,
                               config=harness._merge(cell.config, config))


def _config_user(path):
    """The first cell that runs the configuration file ``path``."""
    bench = benchmark()
    names = [c["name"] for c in bench["configs"]
             if os.path.join(harness.ROOT, c["file"]) == path]
    assert names, f"{path} is no configuration of BENCHMARK.json"
    users = [w["name"] for w in bench["workloads"] if w["config"] == names[0]]
    assert users, f"no cell runs {names[0]}"
    return users[0]


@pytest.mark.parametrize("workload", CELLS)
@on_chips()
def test_cell_has_a_runner_and_a_reader_for_each_metric(workload):
    cell = LOADED[workload]
    devices = jax.devices()[:cell.chips]
    mod = harness.runner(cell.traffic["mode"])
    assert callable(mod.run) and callable(mod.control)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.reader(m["name"]))
        assert m["moves"] in e2e
    line = harness.result_line(tiny(cell), run_tiny(workload), trace=False,
                               devices=devices)
    assert set(line["metrics"]) == e2e
    assert all(m["value"] > 0 for m in line["metrics"].values())
    # the CPU has no device trace; every other reader finds its number
    program = {m["name"] for m in cell.per_layer
               if m["source"] != "device_trace"}
    if program:
        traced = harness.result_line(tiny(cell),
                                     run_tiny(workload, trace=True),
                                     trace=True, devices=devices)
        assert set(traced["metrics"]) == program


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
@on_chips(lambda path: cell_chips(_config_user(path)))
def test_every_config_file_runs_at_its_tiny_size(path):
    out = run_tiny(_config_user(path))
    assert out.correct, out.checks


def test_a_kronecker_graph_adapts_in_batch():
    """The batch runner builds a Kronecker graph from the same generator as
    the stream's, and holds the adaptation on it to the reference."""
    kron = next(LOADED[w] for w in STREAM
                if LOADED[w].config["graph"]["generator"] == "kronecker")
    batch = next(LOADED[w] for w in CELLS
                 if LOADED[w].traffic["mode"] == "adapt")
    session = {k: v for k, v in kron.config["session"].items()
               if k in deployment.BATCH_KEYS}
    cell = tiny(dataclasses.replace(
        kron, traffic=batch.traffic,
        config=dict(kron.config, session=session,
                    limits=batch.config["limits"])))
    out = run_tiny(cell)
    assert out.correct and out.attempted > 0, out.checks
    checks = harness.runner("adapt").control(cell, 7, out.run["replay"])
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("change,says", [
    ({"graph": {"generator": "rmat"}}, "generator 'rmat'"),
    ({"graph": {"noise": 0.1}}, "noise"),
    ({"session": {"placement_passes": 3}}, "placement_passes"),
    ({"session": {"tie_break": "stay"}}, "tie_break 'stay'"),
], ids=["generator", "graph-key", "session-key", "tie-rule"])
@on_chips()
def test_a_key_the_runner_does_not_honour_is_refused(workload, change, says):
    cell = _with(tiny_cell(workload), **change)
    with pytest.raises(harness.BenchError, match=says):
        run_tiny(cell)


def test_the_batch_runner_refuses_the_streams_keys():
    batch = next(w for w in CELLS if LOADED[w].traffic["mode"] == "adapt")
    with pytest.raises(harness.BenchError, match="a_cap"):
        run_tiny(_with(tiny_cell(batch), session={"a_cap": 256}))


@pytest.mark.parametrize("workload", CELLS)
@on_chips()
def test_a_sharded_backend_is_built_or_refused(workload):
    """``cluster_backend: sharded`` builds the sharded backend where the
    cell has a chip per partition, and is refused where it has not: more
    partitions than the cell's chips are refused, and a cell whose own
    backend is sharded builds at its own k on its own chips."""
    from repro.api.backend import ShardedBackend
    own = LOADED[workload].config["session"]
    cell = _with(tiny_cell(workload), session={"cluster_backend": "sharded"})
    stream = cell.traffic["mode"] == "stream"
    graph = deployment.graph(cell, 7, cell.config["session"].get(
        "stream_edge_slots", 0))
    devices = jax.devices()[:cell.chips]
    k = len(devices) + 1
    with pytest.raises(harness.BenchError, match=f"needs {k} devices"):
        deployment.session(_with(cell, session={"k": k}), graph, 7,
                           trace=False, devices=devices, stream=stream)
    built = own["k"] if own["cluster_backend"] == "sharded" else cell.chips
    system = deployment.session(_with(cell, session={"k": built}), graph, 7,
                                trace=False, devices=devices, stream=stream)
    assert isinstance(system.backend, ShardedBackend)


@on_chips(lambda: four_chip_stream().chips)
def test_a_four_chip_stream_runs_on_four_host_devices():
    """The harness holds a cell on four chips: the Kronecker stream on the
    sharded backend, one partition a chip, runs on four host devices, is
    held correct against the reference, and its traced window holds the
    cluster step's spans."""
    cell = four_chip_stream()
    out = run_tiny(cell, trace=True)
    assert out.correct and out.failed == 0, out.checks
    names = {e["name"] for e in out.run["spans"] if e.get("type") == "span"}
    assert {"cluster/bucket", "cluster/dispatch"} <= names
    line = harness.result_line(cell, out, trace=True,
                               devices=jax.devices()[:cell.chips])
    assert line["device"]["count"] == 4


@pytest.mark.parametrize("workload", STREAM)
@on_chips()
def test_a_traced_stream_names_its_gaps_by_the_captures_spans(
        workload, monkeypatch):
    """The program's phase spans reach the trace reduction as the capture
    holds them (``xdgp/<name>``, nested by containment)."""
    seen = {}
    real = xplane.reduce

    def spy(events, window, **kw):
        seen.update(kw)
        return real(events, window, **kw)

    monkeypatch.setattr(xplane, "reduce", spy)
    out = run_tiny(workload, trace=True)
    assert out.correct, out.checks
    depth = {}
    for s in seen["spans"]:
        depth.setdefault(s["name"], set()).add(s["depth"])
    assert depth["xdgp/superstep"] == {0}
    for phase in ("ingest", "place", "migrate", "compute"):
        assert depth["xdgp/" + phase] == {1}


@pytest.mark.parametrize("workload", [
    w for w in STREAM if LOADED[w].traffic["rate_share_of_knee"] > 1])
@on_chips()
def test_a_saturated_stream_opens_the_window_with_a_full_batch(workload):
    """Above capacity the queue never empties in steady state, so the
    window's first superstep takes a full batch (``lead_seconds``), as
    every later one does on the chip, and the rate does not hang on how
    many supersteps fit in the window."""
    cell = tiny_cell(workload)
    out = run_tiny(cell)
    # events handed to the first superstep; it commits up to a_cap of them
    first = out.run["replay"]["batches"][cell.traffic["warmup_supersteps"]]
    assert first >= cell.config["session"]["a_cap"]
