"""The trace reduction on a trace recorded on one TPU v5e in a traced
``q6_backlog`` run, cut to a few batches: the same structure
``bench.trace.load`` makes of a profiler dump, with the program's own
spans in monotonic seconds and the marker's monotonic start.  Each
number the reduction gives is recomputed here another way."""

import os

import numpy as np
import pytest

from bench import run, trace

PATH = os.path.join(os.path.dirname(__file__), "recorded",
                    "q6_backlog_trace.json.gz")


@pytest.fixture(scope="module")
def rec():
    norm = trace.read(PATH)
    lo, hi = trace.window(norm)
    spans = trace.to_trace_clock(norm["program_spans"], norm["marker_mono"],
                                 lo)
    return norm, lo, hi, spans, trace.reduce(norm, run.KERNELS, spans)


def _ops(norm, lo, hi):
    (ops,) = norm["devices"].values()
    return [(max(s, lo), min(s + d, hi), n, st) for n, s, d, st, _ in ops
            if min(s + d, hi) > max(s, lo)]


def test_busy_matches_a_microsecond_bitmap(rec):
    norm, lo, hi, _, red = rec
    us = np.zeros((hi - lo) // 1000 + 1, bool)
    for a, b, _, _ in _ops(norm, lo, hi):
        us[(a - lo) // 1000:(b - lo + 999) // 1000] = True
    assert red["busy_s"] == pytest.approx(us.sum() * 1e-6, rel=0.02)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)


def test_kernel_time_is_the_sum_of_its_events(rec):
    norm, lo, hi, _, red = rec
    import re
    for k, pat in run.KERNELS.items():
        p = re.compile(pat)
        want = sum(b - a for a, b, n, st in _ops(norm, lo, hi)
                   if p.search(n + " " + st.get("long_name", "")))
        assert red["kernels"][k]["time_ns"] == want
    assert red["kernels"]["segment_sum"]["time_ns"] > 0
    assert red["kernels"]["segment_sum"]["bytes"] > 0


def test_marker_puts_program_spans_inside_the_traced_window(rec):
    norm, lo, hi, spans, _ = rec
    applies = [(s, e) for n, s, e in spans if n.startswith("apply.")]
    assert applies
    # the device works inside the spans of the computing job
    busy_in = busy_out = 0
    for a, b, _, _ in _ops(norm, lo, hi):
        mid = (a + b) // 2
        if any(s <= mid < e for s, e in applies):
            busy_in += b - a
        else:
            busy_out += b - a
    assert busy_in > 4 * busy_out


def test_idle_gaps_are_named_and_sorted(rec):
    norm, lo, hi, spans, red = rec
    gaps = red["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert [g for _, g in gaps] == sorted((g for _, g in gaps),
                                          reverse=True)
    names = {n for n, _, _ in spans} | {h[0] for h in norm["host"]}
    assert all(n in names or n == "no span" for n, _ in gaps)
    assert red["busy_s"] + sum(g for _, g in gaps) <= red["window_s"] + 1e-9
