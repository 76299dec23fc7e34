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


def test_a_chip_with_no_op_counts_as_idle():
    """Four chips, one of them with no op in the window: busy and idle
    are averaged over all four, and the idle chip's whole window is a gap."""
    events = [_op("a", 0, 40, f"/device:TPU:{i}") for i in range(3)]
    events.append({"kind": "host", "track": "python",
                   "name": "bench/superstep", "start_ns": 0, "dur_ns": 40})
    r = xplane.reduce(events, (0, 40), chips=4)
    assert r["chips"] == 4
    assert r["busy_s"] == pytest.approx(30e-9)            # 3 x 40 ns / 4
    assert dict(r["idle_gaps"]) == pytest.approx({"bench/superstep": 10e-9})


def test_collective_time_is_summed_per_chip():
    """Collective ops, known by their opcode as a v5e trace writes them
    (async halves included, names taken from a JAX primitive too), are
    summed on each chip and averaged over the chips; other ops are not
    counted."""
    gather = ("%all-gather.8 = s32[163840,1]{0,1:T(1,128)S(1)} all-gather("
              "%copy-done.25), channel_id=1, replica_groups={{0,1,2,3}}, "
              "frontend_attributes={async_collective_name="
              "\"all-gather-start\"}")
    psum = ("%psum_invariant.24 = s32[4]{0:T(128)} all-reduce(%bitcast.44), "
            "channel_id=1, to_apply=%region_9.10")
    tuple_sum = ("%all-reduce.6 = (s32[262144]{0:T(1024)S(1)}, s32[]{:T(128)})"
                 " all-reduce(%broadcast_select_fusion.1, "
                 "%get-tuple-element.128), channel_id=2")
    start = ("%collective-permute-start.2 = (s32[8]{0}, s32[8]{0}) "
             "collective-permute-start(%p.1), channel_id=3")
    user = ("%fusion.3 = s32[4096]{0:T(1024)} fusion(s32[163840,1]"
            "{0,1:T(1,128)S(1)} %all-gather.8), kind=kLoop")
    sort = "%sort.1 = s32[4096]{0:T(1024)} sort(%fusion.3), dimensions={0}"
    for hlo in (gather, psum, tuple_sum, start):
        assert xplane.is_collective(hlo), hlo
    for hlo in (user, sort):
        assert not xplane.is_collective(hlo), hlo

    def op(hlo, a, b, track):
        return dict(_op(xplane.op_name(hlo), a, b, track),
                    collective=xplane.is_collective(hlo))

    events = [op(gather, 0, 10, "/device:TPU:0"),
              op(psum, 10, 12, "/device:TPU:0"),
              op(tuple_sum, 12, 14, "/device:TPU:0"),
              op(user, 14, 30, "/device:TPU:0"),
              op(gather, 0, 6, "/device:TPU:1"),
              op(sort, 6, 20, "/device:TPU:1")]
    r = xplane.reduce(events, (0, 40), chips=2)
    assert r["collective_events"] == 4
    assert r["collective_s"] == pytest.approx((14e-9 + 6e-9) / 2)


def test_a_one_track_capture_reads_as_before():
    """One chip and one track: the count of chips changes nothing."""
    events = [_op("fusion.1", 0, 10), _op("fusion.2", 30, 60),
              {"kind": "host", "track": "python", "name": "bench/superstep",
               "start_ns": 0, "dur_ns": 100}]
    r = xplane.reduce(events, (0, 100), chips=1)
    assert r == xplane.reduce(events, (0, 100))
    assert r["chips"] == 1 and r["busy_s"] == pytest.approx(40e-9)
    assert dict(r["idle_gaps"]) == pytest.approx({"bench/superstep": 60e-9})
    assert r["collective_events"] == 0 and r["collective_s"] == 0.0
