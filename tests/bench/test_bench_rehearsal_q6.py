"""CPU rehearsal of the idea_q6 cells at a tiny size, with the Pallas
kernels in interpret mode: the reference agrees with what the feed
stored, and the control one precision step lower does not."""

import pytest

from bench import run
from repro.kernels import dispatch_mode

SEED = 2**33 + 5


@pytest.fixture
def interpret():
    with dispatch_mode("pallas"):
        yield


def test_q6_backlog_kernels_in_interpret_mode_match_reference(tiny,
                                                              interpret):
    res = run.run_cell(tiny("q6_backlog", table_div=1000), SEED, 1.0,
                       trace=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["enriched_bad"]["value"] == 0
    assert res["metrics"]["ingest_records_per_s"]["value"] > 0


def test_q6_control_in_bfloat16_fails(tiny):
    res = run.run_cell(tiny("q6_backlog"), SEED, 1.0, trace=False,
                       control="bfloat16")
    assert not res["correct"]
    assert res["checks"]["enriched_bad"]["value"] > 0
    assert res["checks"]["lost"]["value"] == 0


def test_q6_steady_reports_visible_latency_over_all_tweets(tiny):
    res = run.run_cell(tiny("q6_steady", rate=1200), SEED, 1.5,
                       trace=False)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert set(m) == {"visible_p50_s", "setup_s"}
    assert 0 < m["visible_p50_s"]["value"] < 5
