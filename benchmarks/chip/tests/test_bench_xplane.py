"""The trace reduction: busy union, kernel events and idle gaps."""
import pytest

from chip import xplane


def _op(name, a, b, track="/device:TPU:0"):
    return {"kind": "op", "track": track, "name": name, "start_ns": a,
            "dur_ns": b - a}


def test_hand_made_trace():
    events = [_op("fusion.1", 0, 10), _op("fusion.2", 5, 15),
              _op("fused_kernel.1", 20, 30), _op("copy", 45, 50),
              {"kind": "host", "track": "python", "name": "bench/superstep",
               "start_ns": 0, "dur_ns": 40}]
    spans = [{"name": "ingest", "depth": 1, "start_ns": 14, "dur_ns": 7},
             {"name": "commit", "depth": 1, "start_ns": 29, "dur_ns": 11}]
    r = xplane.reduce(events, (0, 40), kernel="fused_kernel", spans=spans)
    assert r["window_s"] == pytest.approx(40e-9)
    assert r["busy_s"] == pytest.approx(25e-9)          # [0,15] + [20,30]
    assert r["kernel_s"] == pytest.approx(10e-9) and r["kernel_events"] == 1
    assert dict((k, v) for k, v in r["idle_gaps"]) == pytest.approx(
        {"ingest": 5e-9, "commit": 10e-9})
    assert r["device_ops"][0][0] in ("fusion.1", "fused_kernel.1")


def test_busy_is_averaged_over_chips():
    events = [_op("a", 0, 10, "/device:TPU:0"), _op("a", 0, 30,
                                                    "/device:TPU:1")]
    r = xplane.reduce(events, (0, 40))
    assert r["chips"] == 2 and r["busy_s"] == pytest.approx(20e-9)


def test_op_names_from_a_chip_trace():
    """Op names as a v5e trace writes them (fem64-adapt, one chip): the
    kernel is matched on the op's own name, not on an operand's."""
    kernel = ("%pallas_score_select.1 = (f32[4096,9,64]{2,1,0:T(8,128)}, "
              "s32[4096,1,64]{2,1,0:T(1,128)S(1)}) custom-call(s32[4097]"
              "{0:T(1024)S(1)} %copy-done.11), custom_call_target="
              "\"tpu_custom_call\"")
    user = ("%get-tuple-element.9 = f32[4096,9,64]{2,1,0:T(8,128)} "
            "get-tuple-element(%pallas_score_select.1), index=0")
    scatter = ("%fusion.80 = s32[2097153]{0:T(1024)S(1)} fusion(s32[14680064]"
               "{0:T(1024)} %bitcast.65), kind=kCustom")
    assert xplane.op_name(kernel) == "pallas_score_select.1 (f32[4096,9,64]"
    assert xplane.op_name(scatter) == "fusion.80 s32[2097153]"
    events = [_op(xplane.op_name(kernel), 0, 10),
              _op(xplane.op_name(user), 10, 12),
              _op(xplane.op_name(scatter), 12, 20)]
    r = xplane.reduce(events, (0, 20), kernel="pallas_score_select")
    assert r["kernel_events"] == 1 and r["kernel_s"] == pytest.approx(1e-8)


def test_idle_gap_inside_a_program_phase_is_named_by_it():
    """The capture's program spans (``spans.load``: ``xdgp/<name>``, depth
    from containment) name a gap by the innermost phase running in it."""
    from chip import spans
    events = [_op("fusion.1", 0, 10), _op("fusion.2", 30, 60),
              _op("fusion.3", 70, 100),
              {"kind": "host", "track": "python", "name": "bench/superstep",
               "start_ns": 0, "dur_ns": 100}]
    program = spans.nest([
        {"track": "python", "name": "xdgp/superstep", "start_ns": 0,
         "dur_ns": 100},
        {"track": "python", "name": "xdgp/ingest", "start_ns": 0,
         "dur_ns": 12},
        {"track": "python", "name": "xdgp/place", "start_ns": 12,
         "dur_ns": 50},
        {"track": "python", "name": "xdgp/migrate", "start_ns": 62,
         "dur_ns": 38}])
    r = xplane.reduce(events, xplane.annotated(events), spans=program)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"xdgp/place": 20e-9, "xdgp/migrate": 10e-9})
