"""The trace reduction against a hand-built trace: device busy union,
kernel time and bytes, idle gaps named by the innermost host span, and
the program's monotonic spans put on the profiler's clock by the
marker."""

import pytest

from bench import trace

MS = 1_000_000


def _norm():
    # traced window: the marker, [100 ms, 200 ms) on the profiler clock
    dev = [
        ["fusion.1", 100 * MS, 10 * MS, {}, "XLA Ops"],
        ["fusion.2", 105 * MS, 10 * MS, {}, "XLA Ops"],   # overlaps .1
        ["%sorted_probe_pallas = s32[8,1]{1,0} custom-call(s32[8,1]{1,0} "
         "%a, s32[1,128]{1,0} %b), custom_call_target=\"tpu_custom_call\", "
         "operand_layout_constraints={s32[8,1]{1,0}, s32[1,128]{1,0}}",
         130 * MS, 20 * MS, {}, "XLA Ops"],
        ["fusion.3", 190 * MS, 20 * MS, {}, "XLA Ops"],   # cut at 200
        ["fusion.0", 80 * MS, 10 * MS, {}, "XLA Ops"],    # before window
    ]
    host = [["bench.window", 100 * MS, 100 * MS],
            ["bench.parse", 150 * MS, 45 * MS]]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_busy_is_the_union_of_device_ops_inside_the_window():
    red = trace.reduce(_norm(), {"hash_probe": r"^%sorted_probe"})
    # [100,115) + [130,150) + [190,200) = 45 ms of 100 ms
    assert red["busy_s"] == pytest.approx(0.045)
    assert red["window_s"] == pytest.approx(0.100)


def test_kernel_time_and_bytes():
    red = trace.reduce(_norm(), {"hash_probe": r"^%sorted_probe",
                                 "segment_sum": r"^%segment_sum"})
    k = red["kernels"]["hash_probe"]
    assert k["time_ns"] == 20 * MS and k["events"] == 1
    # result and operands once; the layout constraints repeat them
    assert k["bytes"] == 8 * 4 * 2 + 128 * 4
    assert red["kernels"]["segment_sum"]["time_ns"] == 0


def test_idle_gaps_are_named_by_the_innermost_covering_span():
    marker_mono = 5000.0                      # monotonic s at 100 ms
    program = [{"name": "apply.g0", "t0": 5000.010, "dur": 0.025},
               {"name": "intake.draw", "t0": 5000.0, "dur": 0.100}]
    spans = trace.to_trace_clock(program, marker_mono, 100 * MS)
    assert spans[0] == ("apply.g0", 110 * MS, 135 * MS)
    red = trace.reduce(_norm(), {}, spans)
    # gaps: [115,130) 15 ms inside apply.g0 (innermost over intake.draw),
    #       [150,190) 40 ms inside bench.parse
    assert red["idle_gaps"] == [["bench.parse", pytest.approx(0.040)],
                                ["apply.g0", pytest.approx(0.015)]]


def test_top_device_ops_by_time():
    red = trace.reduce(_norm(), {})
    assert red["device_ops"][0][0].startswith("%sorted_probe_pallas")
    assert red["device_ops"][0][1] == pytest.approx(0.020)
    assert ["fusion.0", pytest.approx(0.0)] not in red["device_ops"]


def test_union_merges_touching_and_nested_intervals():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4), (10, 11),
                        (10, 10)]) == [(0, 4), (5, 6), (10, 11)]


def test_no_marker_is_an_error():
    n = _norm()
    n["host"] = n["host"][1:]
    with pytest.raises(ValueError):
        trace.reduce(n, {})
