"""CPU rehearsal of the idea_q1 cells at a tiny size: a whole run through
the generator, the socket feed and the store, judged as on the chip;
then the control and each fault the cell can have, which must come out
not correct."""

import dataclasses

import numpy as np
import pytest

from bench import run

SEED = 2**31 + 12345        # the driver's seeds exceed 32 signed bits


def test_q1_backlog_run_is_correct(tiny):
    res = run.run_cell(tiny("q1_backlog"), SEED, 1.5, trace=False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["attempted"] > 2 * 256
    assert res["metrics"]["ingest_records_per_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"lost", "duplicated", "raw_bad",
                                  "enriched_bad"}


def test_q1_traced_run_reports_per_layer_metrics(tiny):
    res = run.run_cell(tiny("q1_backlog"), SEED + 1, 1.5, trace=True)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    for name in ("parse_ms_per_batch.tput", "transfer_ms_per_batch.tput",
                 "apply_ms_per_batch.tput",
                 "store_append_ms_per_batch.tput"):
        assert m[name]["value"] > 0 and m[name]["unit"] == "ms"
    # a CPU trace holds no TPU: no device metric is read from it
    assert "device_idle_share.tput" not in m
    assert "hash_probe_roofline.tput" not in m
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] > 0


def test_q1_control_breaks_exactly_once(tiny):
    res = run.run_cell(tiny("q1_backlog"), SEED, 1.0, trace=False,
                       control="duplicate_frame")
    assert not res["correct"]
    assert res["checks"]["duplicated"]["value"] == 256


def test_q1_fault_half_of_each_batch_left_out(tiny, monkeypatch):
    from repro.core import records
    parse = records.parse_json_lines

    def half(lines):
        out = parse(lines)
        out["valid"][1::2] = False
        return out

    monkeypatch.setattr(records, "parse_json_lines", half)
    res = run.run_cell(tiny("q1_backlog"), SEED, 1.0, trace=False)
    assert not res["correct"]
    assert res["checks"]["lost"]["value"] >= res["attempted"] // 2 - 1


def test_q1_fault_answer_altered_where_produced(tiny, monkeypatch):
    from repro.core.enrich import queries as Q
    q1 = Q.SHORT_NAMES["q1"]

    def altered(batch, state, refs):
        out = q1.apply_fn(batch, state, refs)
        return {"safety_level": out["safety_level"] + 1}

    monkeypatch.setitem(Q.SHORT_NAMES, "q1",
                        dataclasses.replace(q1, apply_fn=altered))
    res = run.run_cell(tiny("q1_backlog"), SEED, 1.0, trace=False)
    assert not res["correct"]
    assert res["checks"]["enriched_bad"]["value"] == res["attempted"]
    assert res["checks"]["raw_bad"]["value"] == 0


def test_run_refuses_a_host_without_a_tpu(capsys):
    rc = run.main(["--workload", "q1_backlog", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs 1 TPU chip" in out.err


@pytest.mark.parametrize("offered", [0, 5])
def test_visible_times_take_the_first_store_write(offered):
    ids_a = np.arange(offered)
    vis = run._visible([(1.0, ids_a), (2.0, ids_a)], offered + 2)
    assert np.all(vis[:offered] == 1.0)
    assert np.isnan(vis[offered:]).all()
