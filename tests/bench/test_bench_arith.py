"""The benchmark's arithmetic against hand counts: rates over the whole
window, tails over every sample, the generator's schedule and lateness,
and each kernel's roofline bytes."""

import json
import socket
import subprocess
import sys
import threading

import numpy as np
import pytest

from bench import data, peaks, schedule, spec, stats, trace


def test_rate_counts_every_item_of_the_whole_window():
    stamps = [9.9, 10.0, 10.5, 11.0, 12.0, 12.0001, 30.0]
    # 10.0, 10.5, 11.0 and 12.0 fall in [10, 12]: 4 items over 2 s
    assert stats.rate(np.array(stamps), 10.0, 2.0) == 2.0


@pytest.mark.parametrize("q,want", [(0.5, 5.0), (0.95, 10.0), (0.9, 9.0),
                                    (0.1, 1.0), (1.0, 10.0)])
def test_percentile_is_nearest_rank_over_all_samples(q, want):
    values = [7, 3, 10, 1, 5, 2, 9, 4, 8, 6]
    assert stats.percentile(values, q) == want


POISSON = {"kind": "poisson", "warmup_batches": 2, "rate": 1000.0,
           "gap_seed": 11}


def test_poisson_schedule_is_the_same_load_in_another_order():
    a = schedule.make(POISSON, 1, 5.0, 256)
    b = schedule.make(POISSON, 2**31 + 3, 5.0, 256)
    assert a.warm == b.warm == 512
    assert a.planned == b.planned == (int(np.ceil(5000 / 256)) + 1) * 256
    ga, gb = np.diff(a.offsets, prepend=0), np.diff(b.offsets, prepend=0)
    np.testing.assert_allclose(np.sort(ga), np.sort(gb))
    assert not np.allclose(ga, gb)
    assert a.offsets[-1] == pytest.approx(b.offsets[-1])
    for s in (a, b):
        assert np.all(np.diff(s.offsets) >= 0)
        n_in = s.due_in_window(5.0)
        assert s.offsets[n_in - 1] < 5.0 <= s.offsets[n_in]
        # the frame of the window's last tweet is filled by the tail
        assert (n_in // 256 + 1) * 256 <= s.planned


def test_backlog_schedule_has_no_due_times():
    s = schedule.make({"kind": "backlog", "warmup_batches": 1,
                       "head_records_per_s": 100}, 5, 3.0, 64)
    assert (s.warm, s.planned, s.offsets, s.head) == (64, 0, None, 300)


def _serve(n_lines_box):
    srv = socket.create_server(("127.0.0.1", 0))

    def take():
        conn, _ = srv.accept()
        with conn, conn.makefile("rb") as f:
            n_lines_box.append(sum(1 for _ in f))
        srv.close()
    threading.Thread(target=take, daemon=True).start()
    return srv.getsockname()[1]


def _generator(tmp_path, traffic, seconds):
    path = tmp_path / "traffic.json"
    path.write_text(json.dumps(traffic))
    got = []
    port = _serve(got)
    p = subprocess.Popen(
        [sys.executable, f"{spec.BENCH}/gen.py", "--traffic", str(path),
         "--seed", "77", "--seconds", str(seconds), "--batch", "64"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    assert p.stdout.readline().strip() == "ready"
    p.stdin.write(f"connect {port}\n")
    p.stdin.flush()
    assert p.stdout.readline().split() == ["warm", str(2 * 64)]
    import time
    t0 = time.monotonic() + 0.01
    p.stdin.write(f"go {t0!r}\n")
    p.stdin.flush()
    time.sleep(seconds)
    p.stdin.write("stop\n")
    p.stdin.flush()
    report = json.loads(p.stdout.readline().split(" ", 1)[1])
    p.stdin.close()
    p.wait(timeout=30)
    deadline = time.monotonic() + 10
    while not got and time.monotonic() < deadline:
        time.sleep(0.01)
    return report, got[0]


def test_poisson_generator_sends_its_schedule_and_reports_lateness(
        tmp_path):
    traffic = dict(POISSON, rate=500.0)
    report, lines = _generator(tmp_path, traffic, 1.0)
    sched = schedule.make(traffic, 77, 1.0, 64)
    assert report["sent"] == sched.planned
    assert lines == report["warm"] + report["sent"]
    assert 0 <= report["late_p50_s"] <= report["late_p99_s"] \
        <= report["late_max_s"] < 0.5


def test_backlog_generator_counts_whole_tweets(tmp_path):
    traffic = {"kind": "backlog", "warmup_batches": 2,
               "head_records_per_s": 2000}
    report, lines = _generator(tmp_path, traffic, 0.5)
    assert report["sent"] % 1024 == 0 and report["sent"] > 0
    assert lines == report["warm"] + report["sent"]


def test_fnv63_of_known_strings():
    basis = 14695981039346656037 & 0x7FFFFFFFFFFFFFFF
    h = 14695981039346656037
    for b in b"user42":
        h = (h ^ b) * 1099511628211 & 0x7FFFFFFFFFFFFFFF
    assert data.fnv63(["", "user42"]).tolist() == [basis, h]


def test_json_lines_parse_to_the_stated_columns():
    t = data.tweets(2**31 + 9, 8000, 8400)   # crosses a block boundary
    cols = data.parsed_columns(t)
    for i, line in enumerate(data.json_lines(t)):
        rec = json.loads(line)
        assert rec["id"] == 8000 + i == cols["id"][i]
        assert np.float32(rec["lat"]) == cols["lat"][i]
        assert np.float32(rec["lon"]) == cols["lon"][i]
        words = rec["text"].split()
        assert 4 <= len(words) <= 15
        assert data.fnv63(words).tolist() == \
            cols["text_tokens"][i, :len(words)].tolist()
        assert data.fnv63([rec["user"]])[0] == cols["user_name_hash"][i]


def test_hlo_bytes_counts_operands_and_result_at_the_call_shapes():
    text = ("%sorted_probe_pallas = s32[8192,1]{1,0:T(8,128)S(1)} "
            "custom-call(s32[8192,1]{1,0:T(8,128)S(1)} %copy, "
            "s32[8192,1]{1,0:T(8,128)S(1)} %copy.1, "
            "s32[1,51200]{1,0:T(1,128)S(1)} %bitcast.5, "
            "s32[1,51200]{1,0:T(1,128)S(1)} %bitcast.4), "
            "custom_call_target=\"tpu_custom_call\", "
            "operand_layout_constraints={s32[8192,1]{1,0}, s32[8192,1]{1,0},"
            " s32[1,51200]{1,0}, s32[1,51200]{1,0}}")
    # result and two probe halves: 3 x 8192 x 4; two key halves 2 x 51200 x 4
    assert trace.hlo_bytes(text) == 3 * 8192 * 4 + 2 * 51200 * 4
    assert trace.hlo_bytes("f32[2,3] bf16[4] pred[]") == 24 + 8 + 1


def test_roofline_share_of_a_bytes_bound_kernel():
    # 819e6 bytes at 819 GB/s take 1 ms; measured 4 ms -> 25%
    assert peaks.roofline_share(4e-3, 819e6, 0.0, "TPU v5 lite") == \
        pytest.approx(25.0)
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


@pytest.mark.parametrize("fifths,correct,held", [
    ([0.239, 0.242, 0.192, 0.192, 0.199], True, True),     # flat
    ([0.207, 0.208, 0.214, 0.264, 0.213], True, True),     # one stall
    ([0.298, 0.312, 0.312, 0.335, 0.359], True, False),    # queue grows
    ([0.200, 0.200, 0.200, 0.200, 0.215], True, True),     # within 10%
    ([0.200, 0.200, 0.200, 0.200, 0.230], True, False),
    ([0.200, 0.200, 0.200, 0.200, 0.200], False, False),   # not correct
])
def test_sweep_holds_a_rate_only_where_latency_does_not_grow(
        fifths, correct, held):
    from bench import sweep
    assert sweep.sustained({"correct": correct}, fifths) is held
    assert sweep.sustained(None, fifths) is False
