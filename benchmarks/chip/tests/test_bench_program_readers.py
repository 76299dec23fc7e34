"""The readers of the program's spans and counters, on hand-made runs, and
the depth the capture's program spans get from containment."""
import pytest

from chip import harness, spans


def _span(name, dur_us, depth=0):
    return {"type": "span", "name": name, "ts_us": 0.0, "dur_us": dur_us,
            "depth": depth}


def _counter(name, value, **attrs):
    return {"type": "counter", "name": name, "ts_us": 0.0, "value": value,
            "attrs": attrs}


RUN = {
    "trace": {"idle_gaps": [["xdgp/adapt.history", 0.9],
                            ["xdgp/plan.build", 0.25],
                            ["bench/adapt", 0.1], ["xdgp/adapt", 0.05]]},
    "spans": [_span("plan.build", 300_000.0, 1),
              _span("adapt.history", 1_000.0, 1),
              _span("adapt", 4_000_000.0),
              _counter("host_syncs", 360, history=356, plan=4)],
    "setup_spans": [_counter("compile_s", 3.0, stage="backend_compile"),
                    _counter("compile_s", 1.5, stage="cache_retrieval"),
                    _counter("compile_s", 2.0, stage="backend_compile")],
}


def test_plan_build_ms():
    assert harness.reader("plan_build_ms.adapt")(RUN) == pytest.approx(300.0)


def test_history_idle_ms_per_adaptation():
    assert harness.reader("history_idle_ms.adapt")(RUN) == pytest.approx(
        900.0)
    two = dict(RUN, spans=RUN["spans"] + [_span("adapt", 1.0)])
    assert harness.reader("history_idle_ms.adapt")(two) == pytest.approx(
        450.0)


def test_history_idle_needs_program_spans_in_the_trace():
    # a capture without the program's spans names gaps by the benchmark's
    # annotation only: nothing to read
    bare = dict(RUN, trace={"idle_gaps": [["bench/adapt", 1.44]]})
    assert harness.reader("history_idle_ms.adapt")(bare) is None
    assert harness.reader("history_idle_ms.adapt")({}) is None


def test_host_syncs_per_adaptation_and_superstep():
    assert harness.reader("host_syncs.adapt")(RUN) == 360
    steps = {"spans": [_counter("host_syncs", 8, step=7, place=1),
                       _counter("host_syncs", 6, step=6),
                       _counter("compile_s", 2.0, stage="backend_compile")]}
    assert harness.reader("host_syncs.tail")(steps) == pytest.approx(7.0)


def test_compile_s_sums_backend_compiles_of_the_setup():
    # cache loads are part of a backend compile's time: not added twice
    assert harness.reader("compile_s.adapt")(RUN) == pytest.approx(5.0)


@pytest.mark.parametrize("metric", ["plan_build_ms.adapt",
                                    "history_idle_ms.adapt",
                                    "host_syncs.adapt", "host_syncs.tail",
                                    "compile_s.adapt"])
def test_program_readers_find_nothing_in_an_untraced_run(metric):
    # what a program without these spans and counters gives them
    assert harness.reader(metric)({"trace": {"idle_gaps": []},
                                   "spans": [], "counters": {}}) is None


def test_nest_takes_depth_from_containment():
    evs = spans.nest([
        {"name": "xdgp/adapt.history", "start_ns": 30.0, "dur_ns": 5.0},
        {"name": "xdgp/adapt", "start_ns": 0.0, "dur_ns": 100.0},
        {"name": "xdgp/plan.build", "start_ns": 0.0, "dur_ns": 20.0},
        {"name": "xdgp/adapt.history", "start_ns": 35.0, "dur_ns": 5.0},
        {"name": "xdgp/adapt", "start_ns": 100.0, "dur_ns": 10.0}])
    assert [(e["name"], e["depth"]) for e in evs] == [
        ("xdgp/adapt", 0), ("xdgp/plan.build", 1),
        ("xdgp/adapt.history", 1), ("xdgp/adapt.history", 1),
        ("xdgp/adapt", 0)]
