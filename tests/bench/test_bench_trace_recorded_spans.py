"""The program's phase spans in a trace recorded on one TPU v5e in a
traced ``q6_backlog`` run with ``bench/run.py --dump``, cut to half a
second.  Besides what ``bench.trace.load`` keeps, the file holds the
program's own annotations as the profile's host lines recorded them
(``program``: name, start ns, duration ns, host line) and the window's
program counters (``window``).  The benchmark's reduction, unchanged,
names every idle gap by a phase of the worker, the intake or the store,
and the phases nest under their ``apply.<group>`` on one thread line."""

import os

import pytest

from bench import run, trace

PATH = os.path.join(os.path.dirname(__file__), "recorded",
                    "q6_backlog_spans_trace.json.gz")


@pytest.fixture(scope="module")
def rec():
    norm = trace.read(PATH)
    lo, hi = trace.window(norm)
    spans = trace.to_trace_clock(norm["program_spans"], norm["marker_mono"],
                                 lo)
    return norm, spans, trace.reduce(norm, run.KERNELS, spans)


def test_no_idle_gap_is_left_to_the_whole_apply(rec):
    _, _, red = rec
    names = [n for n, _ in red["idle_gaps"]]
    assert names
    assert not any(n.startswith("apply.") or n == "no span" for n in names)
    assert "compute.parse" in names
    assert red["kernels"]["segment_sum"]["time_ns"] > 0


def test_the_phases_nest_under_apply_on_one_thread_line(rec):
    norm, _, _ = rec
    prog = norm["program"]
    applies = [p for p in prog if p[0].startswith("apply.")]
    phases = [p for p in prog if p[0].startswith("compute.")]
    assert applies and {p[0] for p in phases} >= {
        "compute.parse", "compute.upload", "compute.h2d", "compute.state",
        "compute.execute", "compute.d2h"}
    worker = {p[3] for p in applies}
    assert len(worker) == 1 and {p[3] for p in phases} == worker
    # an annotation open when the profiler started or stopped is not
    # recorded, so only phases between the first and last apply count
    first = min(a[1] for a in applies)
    last = max(a[1] + a[2] for a in applies)
    inside = [p for p in phases if first <= p[1] and p[1] + p[2] <= last]
    assert inside
    for _, s, d, _ in inside:
        assert any(a[1] <= s and s + d <= a[1] + a[2] for a in applies)
    lines = {p[0]: p[3] for p in prog}
    assert lines["intake.draw"] not in worker
    assert lines["store.append"] not in worker
    assert lines["store.append"] != lines["intake.draw"]


def test_ring_phases_name_their_apply_and_fit_inside_it(rec):
    norm, _, _ = rec
    ring = norm["program_spans"]
    applies = {s["id"]: s for s in ring if s["name"].startswith("apply.")}
    children = [s for s in ring if s.get("parent") in applies]
    assert children
    for s in children:
        a = applies[s["parent"]]
        assert a["t0"] <= s["t0"]
        assert s["t0"] + s["dur"] <= a["t0"] + a["dur"] + 1e-6
    for aid, a in applies.items():
        assert sum(c["dur"] for c in children if c["parent"] == aid) \
            <= a["dur"]


def test_window_counters_are_consistent(rec):
    norm, _, _ = rec
    w = norm["window"]
    # a frame fills inside its draw; parse runs for part of its wall time
    assert 0 < w["intake_fill_s"] <= w["intake_draw_s"]
    assert 0 < w["parse_cpu_s"] < w["parse_s"]
    assert w["wait_input_s"] < w["parse_s"]
