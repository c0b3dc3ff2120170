"""The span API (``FeedObs.span``) and what it measures on a running feed:
a span and the counter at its boundary come from the same clock reads;
untraced, a span leaves nothing behind; the worker's phases nest under
``apply.<group>`` without changing the journey profile; the intake's
draw starts where the draw starts and visible latency counts a frame's
fill."""

import socket
import threading
import time

import pytest

from repro.core import (FeedManager, RefStore, SocketAdapter,
                        SyntheticAdapter, pipeline)
from repro.core.enrich import queries as Q
from repro.core.obs import FeedObs, JourneyProfiler, TraceSpec
from repro.core.records import SyntheticTweets


def make_manager(scale=0.002):
    store = RefStore()
    Q.make_reference_tables(store, scale=scale, seed=7)
    return FeedManager(store)


# ---------------------------------------------------------------------------
# the span API
# ---------------------------------------------------------------------------

def test_span_and_counter_agree_exactly():
    obs = FeedObs(TraceSpec())
    counter = 0.0
    with obs.span("compute.parse", (7,), parent=3, cpu=True,
                  rows=5) as sp:
        sum(i * i for i in range(20000))
        time.sleep(0.01)
    counter += sp.dur
    (span,) = obs.drain_trace()
    assert span["dur"] == counter == sp.dur
    assert span["name"] == "compute.parse"
    assert span["spans"] == [7] and span["parent"] == 3
    assert span["rows"] == 5 and span["id"] == sp.id > 0
    assert 0.0 < span["cpu"] == sp.cpu <= span["dur"]
    assert span["dur"] >= 0.01


def test_span_cpu_leaves_out_time_not_running():
    obs = FeedObs(TraceSpec())
    with obs.span("worker.wait_input") as sp:
        time.sleep(0.05)
    assert sp.dur >= 0.05
    assert sp.cpu < 0.5 * sp.dur


def test_untraced_span_measures_but_emits_nothing():
    obs = FeedObs()
    with obs.span("compute.parse", cpu=True) as sp:
        sum(i * i for i in range(20000))
        time.sleep(0.005)
    assert sp.dur > 0.0 and 0.0 < sp.cpu <= sp.dur
    assert sp.id == 0
    with obs.span("compute.state") as sp2:
        pass
    assert sp2.cpu == 0.0                    # asked for no CPU reading
    assert obs.drain_trace() == []
    assert obs.tracer is None


def test_span_is_recorded_when_the_block_raises():
    obs = FeedObs(TraceSpec())
    with pytest.raises(ValueError):
        with obs.span("apply.g"):
            raise ValueError("boom")
    assert [s["name"] for s in obs.drain_trace()] == ["apply.g"]


def test_child_spans_leave_the_journey_profile_unchanged():
    def span(name, ids, t0, dur, **kw):
        return dict(name=name, spans=list(ids), t0=t0, dur=dur, **kw)

    hops = [span("intake.draw", [1], 0.0, 1.0, id=10),
            span("apply.g", [1], 2.0, 3.0, id=11),
            span("store.append", [1], 5.0, 1.0, id=12),
            span("store.flush", [1], 6.5, 0.5, id=13)]
    children = [span("compute.parse", [1], 2.0, 2.0, parent=11),
                span("compute.execute", [1], 4.0, 0.5, parent=11),
                span("compute.d2h", (), 4.5, 0.5, parent=11)]
    plain, nested = JourneyProfiler(), JourneyProfiler()
    assert plain.ingest(hops) == 4
    assert nested.ingest(hops[:2] + children + hops[2:]) == 4
    assert nested.report().to_dict() == plain.report().to_dict()
    assert len(nested.recent_spans()) == 7


# ---------------------------------------------------------------------------
# the worker's phases on a running feed
# ---------------------------------------------------------------------------

def _traced_feed(name, total=600):
    mgr = make_manager()
    plan = (pipeline(SyntheticAdapter(total=total, frame_size=50, seed=3),
                     name)
            .parse(batch_size=50)
            .options(num_partitions=1, coalesce_rows=0,
                     trace={"capacity": 100000})
            .enrich(Q.Q2)
            .store())
    h = mgr.submit(plan)
    stats = h.join(timeout=120)
    return h, stats


def test_compute_spans_nest_under_apply_and_sum_to_the_counters():
    h, stats = _traced_feed("span-phases")
    assert stats.stored == 600
    spans = h.drain_trace()
    applies = {s["id"]: s for s in spans if s["name"].startswith("apply.")}
    phases = [s for s in spans if s["name"].startswith("compute.")]
    assert {s["name"] for s in phases} >= {
        "compute.parse", "compute.upload", "compute.h2d", "compute.state",
        "compute.execute", "compute.d2h"}
    for s in phases:
        parent = applies[s["parent"]]
        assert s["thread"] == parent["thread"]
        assert parent["t0"] <= s["t0"]
        assert s["t0"] + s["dur"] <= parent["t0"] + parent["dur"] + 1e-6
    (runner,) = h.runners
    st = runner.stats

    def total(name):
        return sum(s["dur"] for s in spans if s["name"] == name)

    assert total("compute.parse") == pytest.approx(st.parse_s, rel=1e-12)
    assert sum(s["cpu"] for s in spans if s["name"] == "compute.parse") \
        == pytest.approx(st.parse_cpu_s, rel=1e-12)
    assert total("compute.state") == pytest.approx(st.state_s, rel=1e-12)
    assert total("compute.execute") == pytest.approx(st.apply_s, rel=1e-12)
    assert total("compute.h2d") + total("compute.d2h") == pytest.approx(
        st.convert_s, rel=1e-12)
    assert total("worker.wait_input") == pytest.approx(st.wait_input_s,
                                                       rel=1e-12)
    assert total("worker.wait_output") == pytest.approx(st.wait_output_s,
                                                        rel=1e-12)
    assert 0.0 < st.parse_cpu_s <= st.parse_s * 1.05
    intake = h.intake
    assert total("intake.draw") == pytest.approx(intake.draw_s, rel=1e-12)
    assert total("intake.wait_output") == pytest.approx(
        intake.wait_output_s, rel=1e-12)
    assert total("intake.fill") == pytest.approx(intake.fill_s, rel=1e-9)
    m = h.metrics()
    for k in ("computing_parse_cpu_s", "worker_wait_input_s",
              "worker_wait_output_s", "intake_draw_s", "intake_fill_s",
              "intake_wait_output_s"):
        assert m[k] > 0.0, k
    assert m["computing_parse_cpu_s"] == st.parse_cpu_s
    assert m["intake_draw_s"] == intake.draw_s


def test_untraced_feed_keeps_the_phase_counters():
    mgr = make_manager()
    plan = (pipeline(SyntheticAdapter(total=300, frame_size=50, seed=3),
                     "span-off")
            .parse(batch_size=50)
            .options(num_partitions=1)
            .enrich(Q.Q1)
            .store())
    h = mgr.submit(plan)
    assert h.join(timeout=120).stored == 300
    assert h.drain_trace() == []
    st = h.runners[0].stats
    assert 0.0 < st.parse_cpu_s and st.wait_input_s > 0.0
    assert h.intake.draw_s > 0.0 and h.intake.fill_s >= 0.0


def _profile_lines(profile_dir):
    """Host events of the newest profile under ``profile_dir``, as
    (name, start ns, end ns, line) with one line per host thread."""
    import glob
    import os
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, i)
                        for e in line.events]
    return out


def test_traced_spans_land_in_the_profile_on_their_threads(tmp_path):
    import jax
    mgr = make_manager()
    plan = (pipeline(SyntheticAdapter(total=400, frame_size=50, seed=4),
                     "span-profile")
            .parse(batch_size=50)
            .options(num_partitions=1, coalesce_rows=0, trace=True)
            .enrich(Q.Q1)
            .store())
    jax.profiler.start_trace(str(tmp_path))
    try:
        h = mgr.submit(plan)
        assert h.join(timeout=120).stored == 400
    finally:
        jax.profiler.stop_trace()
    ev = _profile_lines(str(tmp_path))
    applies = [e for e in ev if e[0] == "apply.q1_safety_level"]
    phases = [e for e in ev if e[0].startswith("compute.")]
    assert applies and {e[0] for e in phases} >= {
        "compute.parse", "compute.upload", "compute.h2d",
        "compute.execute", "compute.d2h"}
    for name, s, e, line in phases:
        # on the worker's host line, inside one of its applies
        assert any(a[3] == line and a[1] <= s and e <= a[2]
                   for a in applies), name
    lines = {e[0]: e[3] for e in ev}
    assert lines["worker.wait_input"] == lines["compute.parse"]
    assert len({lines["compute.parse"], lines["intake.draw"],
                lines["store.append"]}) == 3
    # a span that the ring holds is the same stretch of the same work
    ring = [s for s in h.drain_trace() if s["name"] == "compute.parse"]
    prof = [e for e in phases if e[0] == "compute.parse"]
    assert len(ring) == len(prof) == 8
    assert sum(s["dur"] for s in ring) == pytest.approx(
        sum(e - s for _, s, e, _ in prof) * 1e-9, rel=0.05, abs=2e-3)


# ---------------------------------------------------------------------------
# intake stamps
# ---------------------------------------------------------------------------

def _send(port, chunks, pause):
    with socket.create_connection(("127.0.0.1", port)) as c:
        for i, chunk in enumerate(chunks):
            if i:
                time.sleep(pause)
            c.sendall(b"".join(line + b"\n" for line in chunk))


def test_draw_starts_before_the_intake_stamp_and_latency_counts_the_fill():
    frame, stall = 40, 0.4
    lines = SyntheticTweets(seed=9).raw_lines(2 * frame)
    # frame 1 arrives whole; frame 2 stalls halfway through its assembly
    chunks = [lines[:frame + frame // 2], lines[frame + frame // 2:]]
    adapter = SocketAdapter("127.0.0.1", 0, frame_size=frame)
    mgr = make_manager()
    plan = (pipeline(adapter, "span-intake")
            .parse(batch_size=frame)
            .options(num_partitions=1, coalesce_rows=0, trace=True)
            .enrich(Q.Q1)
            .store())
    h = mgr.submit(plan)
    pushed = []
    holder = h.holders[0]
    push = holder.push

    def keep(frame, *a, **kw):
        pushed.append(frame)
        return push(frame, *a, **kw)

    holder.push = keep
    sender = threading.Thread(target=_send,
                              args=(adapter.address[1], chunks, stall))
    sender.start()
    stats = h.join(timeout=120)
    sender.join()
    assert stats.stored == 2 * frame
    spans = h.drain_trace()
    # the draw that finds the end of the stream carries no frame's ids
    draws = sorted((s for s in spans
                    if s["name"] == "intake.draw" and s["spans"]),
                   key=lambda s: s["t0"])
    fills = sorted((s for s in spans if s["name"] == "intake.fill"),
                   key=lambda s: s["t0"])
    assert len(draws) == len(fills) == 2
    for d, f in zip(draws, fills):
        # the fill lies inside its draw, and the draw ends where the
        # frame was stamped
        assert d["t0"] <= f["t0"] + 1e-6
        assert f["t0"] + f["dur"] <= d["t0"] + d["dur"] + 1e-3
    frames = [f for f in pushed if getattr(f, "span_ids", ())]
    assert [f.span_ids for f in frames] == [tuple(d["spans"])
                                            for d in draws]
    for d, f in zip(draws, frames):
        # the draw starts before the frame's first line and ends at its
        # intake stamp
        assert d["t0"] <= f.t_open < f.t_intake
        assert d["t0"] + d["dur"] == pytest.approx(f.t_intake, abs=0.01)
    # the second frame's fill holds the planted stall
    assert fills[1]["dur"] >= stall
    lat = h.metrics()["ingest_visible_latency_s"]
    assert lat.count == 2
    assert lat.percentile(1.0) >= stall
    assert h.intake.fill_s >= stall
