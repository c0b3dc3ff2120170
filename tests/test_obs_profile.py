"""feedscope tests: journey reconstruction + critical-path attribution
(core/obs/profile.py), the SLO health model (core/obs/health.py), the
live ops endpoint (core/obs/server.py), the fused chain's even stage
split, and the empty-histogram nan pins.

Deliberately hypothesis-free: CI runs this module in the minimal
plan-api container, so the feedscope surface is pinned even where the
property-test extras are not installed.
"""

import json
import math
import threading
import time

import pytest

from repro.core import (ComputingRunner, ComputingSpec, FeedManager,
                        MetricsRegistry, RefStore, SyntheticAdapter,
                        pipeline)
from repro.core.enrich import queries as Q
from repro.core.obs import (FeedHealthModel, HealthSpec, JourneyProfiler,
                            ProfileSpec, http_get)
from repro.core.records import SyntheticTweets


def make_manager(scale=0.002):
    store = RefStore()
    Q.make_reference_tables(store, scale=scale, seed=7)
    return FeedManager(store)


def span(name, ids, t0, dur=0.0):
    return {"name": name, "spans": list(ids), "t0": t0, "dur": dur}


# ---------------------------------------------------------------------------
# journey profiler: golden fractions, queue vs service, id unification
# ---------------------------------------------------------------------------

def test_profiler_golden_fractions_and_bottleneck_verdict():
    prof = JourneyProfiler()
    prof.ingest([
        span("intake.draw", [1], 0.0, 1.0),
        span("apply.q1", [1], 2.0, 3.0),      # 1s queue gap before it
        span("store.append", [1], 5.0, 1.0),
        span("store.flush", [1], 6.0, 2.0),
    ])
    rep = prof.report()
    assert rep.journeys == 1
    assert rep.complete == 1
    # service: draw 1, apply 3, append 1, flush 2; queue: 1s waiting for
    # apply -> total attributed 8s
    assert rep.hops["intake.draw"].frac == pytest.approx(1 / 8)
    assert rep.hops["apply.q1"].service_s == pytest.approx(3.0)
    assert rep.hops["apply.q1"].queue_s == pytest.approx(1.0)
    assert rep.hops["apply.q1"].frac == pytest.approx(4 / 8)
    assert rep.hops["store.flush"].frac == pytest.approx(2 / 8)
    assert rep.bottleneck == "apply.q1"
    assert rep.ranked[0] == ("apply.q1", pytest.approx(0.5))
    # visible latency: intake start 0.0 -> last hop end 8.0
    assert rep.visible_p95_s == pytest.approx(8.0)


def test_profiler_decomposes_queue_vs_service_time():
    prof = JourneyProfiler()
    # back-to-back hops: no queue time anywhere
    prof.ingest([span("intake.draw", [1], 0.0, 1.0),
                 span("store.append", [1], 1.0, 1.0)])
    # gapped hops on a second journey: 5s spent waiting for the store
    prof.ingest([span("intake.draw", [2], 10.0, 1.0),
                 span("store.append", [2], 16.0, 1.0)])
    rep = prof.report()
    sa = rep.hops["store.append"]
    assert sa.service_s == pytest.approx(2.0)
    assert sa.queue_s == pytest.approx(5.0)
    assert sa.queue_p95 == pytest.approx(5.0)
    assert sa.service_p50 == pytest.approx(1.0)
    # the wait dominates: the verdict blames the hop that was waited FOR
    assert rep.bottleneck == "store.append"


def test_profiler_unions_ids_across_coalesce_and_flush():
    prof = JourneyProfiler()
    prof.ingest([
        span("intake.draw", [1], 0.0, 0.1),
        span("intake.draw", [2], 0.2, 0.1),
        span("coalesce", [1, 2], 0.4),            # merges both draws
        span("apply.g", [1, 2], 0.5, 0.5),
        span("store.flush", [1, 2], 1.1, 0.2),
    ])
    rep = prof.report()
    assert rep.journeys == 1                      # one connected component
    assert rep.complete == 1
    assert rep.hops["intake.draw"].count == 2


def test_profiler_window_evicts_oldest_journeys():
    prof = JourneyProfiler(ProfileSpec(window=2))
    for i in range(1, 6):
        prof.ingest([span("intake.draw", [i], float(i), 0.1)])
    rep = prof.report()
    assert rep.journeys == 2
    # a late span for an evicted journey resurfaces as a fresh journey
    # (never corrupting a live one) and the window re-trims to bound
    prof.ingest([span("store.append", [1], 99.0, 0.1)])
    assert prof.report().journeys == 2


def test_profile_spec_validation():
    with pytest.raises(ValueError, match="window"):
        ProfileSpec(window=0)
    with pytest.raises(ValueError, match="trace_keep"):
        ProfileSpec(trace_keep=-1)


# ---------------------------------------------------------------------------
# end-to-end: a two-stage-group traced plan reconstructs a full journey
# ---------------------------------------------------------------------------

def test_two_stage_group_plan_reconstructs_complete_journeys(tmp_path):
    mgr = make_manager()
    plan = (pipeline(SyntheticAdapter(total=400, frame_size=50, seed=5),
                     "prof-groups")
            .parse(batch_size=50)
            .options(num_partitions=1, profile=True)
            .enrich(Q.Q1, partitions=1)
            .enrich(Q.Q2, partitions=1)      # second stage group
            .store(spill_dir=str(tmp_path), segment_rows=100))
    h = mgr.submit(plan)
    stats = h.join(timeout=120)
    assert stats.stored == 400
    rep = h.profile()
    assert rep is not None and rep.journeys > 0
    names = set(rep.hops)
    assert "intake.draw" in names
    # BOTH groups' apply hops joined the same journeys (the stamps now
    # survive _push_downstream — the old multi-group known limit)
    assert sum(1 for n in names if n.startswith("apply.")) == 2
    assert "store.append" in names
    # segment flushes carry the span ids buffered per storage partition,
    # closing journeys intake.draw -> ... -> store.flush
    assert "store.flush" in names
    assert rep.complete > 0
    assert rep.visible_p95_s > 0.0
    assert rep.bottleneck is not None
    # the verdict also lands as gauges for /metrics scrapes
    m = h.metrics()
    assert any(k.startswith("bottleneck_") and k.endswith("_frac")
               for k in m)


# ---------------------------------------------------------------------------
# health model: SLO rules and state transitions under an injected clock
# ---------------------------------------------------------------------------

def _snap(visible=None, wal=None, repair=None, **scalars):
    reg = MetricsRegistry()
    for name, vals in (("ingest_visible_latency_s", visible),
                       ("wal_fsync_s", wal), ("repair_currency_s", repair)):
        h = reg.histogram(name)
        for v in vals or ():
            h.observe(v)
    for k, v in scalars.items():
        reg.gauge(k).set(float(v))
    return reg.snapshot()


def test_health_ok_with_empty_signals():
    model = FeedHealthModel()
    rep = model.evaluate(_snap())
    assert rep.state == "ok" and rep.code == 0
    assert rep.reasons == []
    assert set(rep.rules) == {"visible_latency", "wal_fsync",
                              "repair_currency", "worker_errors",
                              "backlog_growth", "stalled"}


def test_health_degrades_on_latency_errors_and_repair_lag():
    spec = HealthSpec(visible_p95_s=0.5, wal_fsync_p95_s=0.1)
    model = FeedHealthModel(spec, max_lag_s=1.0)   # budget 2.0s w/ slack
    rep = model.evaluate(_snap(visible=[2.0] * 10, wal=[0.5] * 10,
                               repair=[5.0] * 10, worker_errors=2))
    assert rep.state == "degraded" and rep.code == 1
    assert rep.rules["visible_latency"] == "degraded"
    assert rep.rules["wal_fsync"] == "degraded"
    assert rep.rules["repair_currency"] == "degraded"
    assert rep.rules["worker_errors"] == "degraded"
    assert len(rep.reasons) == 4


def test_health_backlog_growth_needs_monotone_run():
    t = [0.0]
    model = FeedHealthModel(HealthSpec(backlog_growth_evals=3),
                            clock=lambda: t[0])
    for rows in (10, 20, 15):                 # not monotone
        assert model.evaluate(_snap(backlog_rows_now=rows,
                                    feed_stored=rows)
                              ).rules["backlog_growth"] == "ok"
    for i, rows in enumerate((30, 40, 50)):   # monotone x3 -> trips
        rep = model.evaluate(_snap(backlog_rows_now=rows,
                                   feed_stored=100 + i))
    assert rep.rules["backlog_growth"] == "degraded"
    assert rep.state == "degraded"


def test_health_stall_transition_and_recovery_with_injected_clock():
    t = [0.0]
    model = FeedHealthModel(HealthSpec(stall_after_s=5.0),
                            clock=lambda: t[0])
    base = dict(backlog_rows_now=100, feed_stored=7, sink_lm_batches=3)
    assert model.evaluate(_snap(**base)).state == "ok"     # anchors
    t[0] = 4.0
    assert model.evaluate(_snap(**base)).state == "ok"     # within budget
    t[0] = 6.0
    rep = model.evaluate(_snap(**base))                    # frozen > 5s
    assert rep.state == "stalled" and rep.code == 2
    assert rep.rules["stalled"] == "stalled"
    # ANY progress counter moving re-anchors (tee pulls count too)
    t[0] = 12.0
    moved = dict(base, sink_lm_batches=4)
    assert model.evaluate(_snap(**moved)).state == "ok"
    # so does an empty backlog, stalled-for however long
    t[0] = 50.0
    assert model.evaluate(_snap(backlog_rows_now=0,
                                feed_stored=8)).state == "ok"


def test_health_spec_validation():
    with pytest.raises(ValueError, match="backlog_growth_evals"):
        HealthSpec(backlog_growth_evals=1)
    with pytest.raises(ValueError, match="stall_after_s"):
        HealthSpec(stall_after_s=0.0)


# ---------------------------------------------------------------------------
# live ops endpoint: /metrics, /health, /profile, /trace over a real feed
# ---------------------------------------------------------------------------

def test_obs_server_smoke_and_health_flip_on_induced_stall():
    mgr = make_manager()
    gate = threading.Event()
    seen = []

    def blocked_sink(batch):
        gate.wait(timeout=60)
        seen.append(batch)

    plan = (pipeline(SyntheticAdapter(total=400, frame_size=50, seed=9),
                     "ops-feed")
            .parse(batch_size=50)
            .options(num_partitions=1, coalesce_rows=0, profile=True,
                     health={"stall_after_s": 0.3})
            .enrich(Q.Q1)
            .tee(blocked_sink, name="lm"))
    h = mgr.submit(plan)
    srv = mgr.serve_obs(port=0)
    assert mgr.serve_obs() is srv            # idempotent
    try:
        url = srv.url
        code, idx = http_get(url + "/")
        assert code == 200
        assert "/metrics" in json.loads(idx)["endpoints"]

        # the tee consumer is gated shut: backlog accumulates with zero
        # progress, so /health must flip to stalled (503) within the SLO
        status = None
        deadline = time.time() + 30
        while time.time() < deadline:
            status, body = http_get(url + "/health")
            if status == 503:
                break
            time.sleep(0.1)
        assert status == 503
        payload = json.loads(body)
        assert payload["stalled"] is True
        assert payload["feeds"]["ops-feed"]["state"] == "stalled"

        code, text = http_get(url + "/metrics")
        assert code == 200
        assert "# TYPE feed_stored counter" in text
        assert "feed_health" in text
        assert "backlog_rows_now" in text

        code, prof = http_get(url + "/profile")
        assert code == 200
        assert "ops-feed" in json.loads(prof)["feeds"]

        code, tr = http_get(url + "/trace")
        assert code == 200
        spans = json.loads(tr)["feeds"]["ops-feed"]
        assert any(s["name"] == "intake.draw" for s in spans)

        code, _ = http_get(url + "/nope")
        assert code == 404

        gate.set()                            # unblock: the feed drains
        stats = h.join(timeout=120)
        assert stats.sink_batches["lm"] == len(seen) > 0
        code, body = http_get(url + "/health")
        assert code == 200                    # feed gone or recovered
    finally:
        gate.set()
        mgr.stop_obs()
        mgr.stop_obs()                        # no-op when already stopped


# ---------------------------------------------------------------------------
# fused chains: one dispatch a batch, apply wall split evenly
# ---------------------------------------------------------------------------

def test_fused_chain_even_split_and_no_compile_after_first_batch():
    store = RefStore()
    Q.make_reference_tables(store, scale=0.002, seed=7)
    udf = Q.chain("q1q2_even", Q.Q1, Q.Q2)
    runner = ComputingRunner(ComputingSpec(udf, batch_size=50),
                             store, None)
    frames = list(SyntheticTweets(seed=2).batches(70 * 50, 50))
    assert len(frames) == 70
    runner.run(frames[0])
    compiled = runner.cache.compiles
    for f in frames[1:]:
        runner.run(f)
    # a long fused feed compiles nothing after its first batch
    assert runner.cache.compiles == compiled
    st = runner.stats
    assert st.invocations == 70
    assert st.calibrations == 0
    a, b = (st.per_stage[u.name].apply_s for u in udf.stages)
    assert a == pytest.approx(b)             # even split
    assert a + b == pytest.approx(st.apply_s, rel=1e-6)


# ---------------------------------------------------------------------------
# empty-histogram pins: percentiles are nan, exposition stays valid
# ---------------------------------------------------------------------------

def test_empty_histogram_percentile_is_nan_everywhere():
    reg = MetricsRegistry()
    h = reg.histogram("quiet_s")
    assert math.isnan(h.percentile(0.5))
    snap = reg.snapshot()["quiet_s"]
    assert snap.count == 0
    assert math.isnan(snap.percentile(0.95))
    assert snap.mean == 0.0                  # mean keeps its 0.0 default


def test_empty_histogram_renders_valid_exposition():
    reg = MetricsRegistry()
    reg.histogram("quiet_s", bounds=(0.1, 1.0))
    assert reg.exposition() == (
        "# TYPE quiet_s histogram\n"
        'quiet_s_bucket{le="0.1"} 0\n'
        'quiet_s_bucket{le="1"} 0\n'
        'quiet_s_bucket{le="+Inf"} 0\n'
        "quiet_s_sum 0\n"
        "quiet_s_count 0\n")
