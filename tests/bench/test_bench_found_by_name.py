"""What a new configuration brings as new files and entries alone: a
metric over any counter the program keeps, a roofline for a kernel no
reader named before, and the device time of each stage of a fused chain.
Nothing in ``bench/run.py``, ``bench/trace.py`` or ``bench/spec.py``
names any of them."""

import json
import os
import shutil
import types

import pytest

from bench import layer, run, spec, trace

ROOT = spec.ROOT
MS = 1_000_000


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

def _handle(scale=1.0):
    """A feed handle as ``_counters`` sees it: two runners, one of them
    with two fused stages, the intake, the store and the registry."""
    from repro.core.computing import ComputingStats

    def stats(k):
        s = ComputingStats(invocations=int(10 * k), records=int(6720 * k),
                           parse_s=0.8 * k, parse_cpu_s=0.7 * k,
                           upload_s=0.1 * k, convert_s=0.05 * k,
                           state_s=0.2 * k, apply_s=0.3 * k,
                           wait_input_s=0.04 * k, wait_output_s=0.01 * k,
                           calibrations=int(k))
        s.stage("q4").apply_s = 0.1 * k
        s.stage("q4").invocations = int(10 * k)
        s.stage("q6").state_s = 0.2 * k
        return s
    h = types.SimpleNamespace()
    h.runners = [types.SimpleNamespace(stats=stats(scale)),
                 types.SimpleNamespace(stats=stats(2 * scale))]
    h.intake = types.SimpleNamespace(draw_s=1.0 * scale, fill_s=0.9 * scale,
                                     wait_output_s=0.1 * scale,
                                     frames_in=int(12 * scale))
    h.storage = types.SimpleNamespace(write_s=0.5 * scale,
                                      batches=int(11 * scale))
    hist = types.SimpleNamespace(count=3)
    h.metrics = lambda: {"computing_calibrations": int(3 * scale),
                         "intake_draw_s": 1.0 * scale,
                         "parse_s": -1.0, "holder_backlog_age_s": hist,
                         "feed_health": 0.0}
    return h


def _old_counters(h):
    """``_counters`` as the first readers were written against it."""
    out = {"parse_s": 0.0, "upload_s": 0.0, "convert_s": 0.0,
           "state_s": 0.0, "apply_s": 0.0, "invocations": 0}
    for r in h.runners:
        for k in out:
            out[k] += getattr(r.stats, k)
    out["store_write_s"] = h.storage.write_s
    out["store_batches"] = h.storage.batches
    return out


def test_counters_keep_the_first_eight_and_find_the_rest():
    h = _handle()
    got = run._counters(h)
    old = _old_counters(h)
    assert {k: got[k] for k in old} == old
    assert got["parse_cpu_s"] == pytest.approx(2.1)
    assert got["wait_input_s"] == pytest.approx(0.12)
    assert got["calibrations"] == 3 and got["records"] == 3 * 6720
    assert got["stage.q4.apply_s"] == pytest.approx(0.3)
    assert got["stage.q4.invocations"] == 30
    assert got["stage.q6.state_s"] == pytest.approx(0.6)
    assert got["intake.draw_s"] == 1.0 and got["intake.frames_in"] == 12
    assert got["computing_calibrations"] == 3
    # a registry name never displaces one above; histograms are left out
    assert got["parse_s"] == old["parse_s"]
    assert "holder_backlog_age_s" not in got
    assert "per_stage" not in got


def test_a_program_without_an_intake_keeps_no_intake_counter():
    h = _handle()
    h.intake = None
    got = run._counters(h)
    assert not any(k.startswith("intake.") for k in got)
    ctx = _ctx(got, batches=10)
    assert layer.per_frame_ms(ctx, "intake.draw_s") is None


def _ctx(window, batches, red=None, cell=None):
    return run.Ctx(cell, "TPU v5 lite", window, batches, None, red)


def _window(c0, c1):
    return {k: c1.get(k, 0) - c0.get(k, 0) for k in {**c0, **c1}}


def test_a_new_metric_reads_a_new_counter_by_name(tmp_path):
    """Mirrors ``test_a_new_cell_is_found_by_name_from_new_files_only``:
    a metric over a counter no reader used before is one new file and
    one entry."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    (tmp_path / "bench" / "metrics" / "q6_state_ms_per_batch.tput.py"
     ).write_text("from bench.layer import per_batch_ms\n\n\n"
                  "def read(ctx):\n"
                  "    return per_batch_ms(ctx, 'stage.q6.state_s')\n")
    bench["per_layer"].append({"name": "q6_state_ms_per_batch.tput",
                               "unit": "ms", "better": "lower",
                               "source": "program_counter",
                               "layer": "computing job",
                               "moves": "ingest_records_per_s",
                               "workloads": ["q6_backlog"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("q6_backlog", str(tmp_path / "BENCHMARK.json"))
    assert "q6_state_ms_per_batch.tput" in [m["name"]
                                            for m in cell.per_layer]
    win = _window(run._counters(_handle(1.0)), run._counters(_handle(3.0)))
    ctx = _ctx(win, int(win["invocations"]), cell=cell)
    new = [m for m in cell.per_layer
           if m["name"] == "q6_state_ms_per_batch.tput"]
    got = spec.read_metrics(new, ctx)
    # stage q6 built state for 0.6 s more over 60 more batches
    assert got["q6_state_ms_per_batch.tput"]["value"] == pytest.approx(
        1000.0 * (0.6 * 3 - 0.6) / 60)


# the window counters of a traced q6_backlog run on one TPU v5e (the
# recorded spans trace), under the names ``_counters`` gives them
RECORDED_WINDOW = os.path.join(os.path.dirname(__file__), "recorded",
                               "q6_backlog_spans_trace.json.gz")
RENAMED = {"intake_draw_s": "intake.draw_s", "intake_fill_s":
           "intake.fill_s", "intake_wait_output_s": "intake.wait_output_s",
           "intake_frames": "intake.frames_in"}


@pytest.fixture(scope="module")
def chip_window():
    w = trace.read(RECORDED_WINDOW)["window"]
    return {RENAMED.get(k, k): v for k, v in w.items()}


@pytest.mark.parametrize("metric,want", [
    ("parse_wait_ms_per_batch.tput",
     lambda w: 1000 * (w["parse_s"] - w["parse_cpu_s"]) / w["invocations"]),
    ("worker_wait_ms_per_batch.tput",
     lambda w: 1000 * (w["wait_input_s"] + w["wait_output_s"])
     / w["invocations"]),
    ("intake_draw_ms_per_batch.tput",
     lambda w: 1000 * w["intake.draw_s"] / w["intake.frames_in"]),
    ("frame_fill_ms.lat",
     lambda w: 1000 * w["intake.fill_s"] / w["intake.frames_in"]),
])
def test_the_four_counter_metrics_on_a_chip_window(chip_window, metric,
                                                   want):
    ctx = _ctx(chip_window, int(chip_window["invocations"]))
    got = spec.reader(metric)(ctx)
    assert got == pytest.approx(want(chip_window))
    assert got > 0
    # without the program's counter the metric is left out, not 0
    bare = {k: v for k, v in chip_window.items()
            if k in ("parse_s", "upload_s", "convert_s", "state_s",
                     "apply_s", "invocations", "store_write_s",
                     "store_batches")}
    assert spec.reader(metric)(_ctx(bare, ctx.batches)) is None


def test_the_four_counter_metrics_read_as_on_the_chip(chip_window):
    ctx = _ctx(chip_window, int(chip_window["invocations"]))
    assert 7.0 < spec.reader("parse_wait_ms_per_batch.tput")(ctx) < 8.6
    assert 0.6 < spec.reader("worker_wait_ms_per_batch.tput")(ctx) < 0.8
    assert 100 < spec.reader("intake_draw_ms_per_batch.tput")(ctx) < 105


# ---------------------------------------------------------------------------
# kernels and stages in the trace
# ---------------------------------------------------------------------------

RADIUS = ("%radius_join_pallas.3 = (f32[8192,8]{1,0}, s32[8192,8]{1,0}) "
          "custom-call(f32[8192,1]{1,0} %a, f32[1,50176]{1,0} %b), "
          "custom_call_target=\"tpu_custom_call\"")
TOPK = ("%segment_topk_pallas = s32[512,3]{1,0} custom-call(s32[1,50176]"
        "{1,0} %v, s32[1,50176]{1,0} %s), custom_call_target=\"x\"")
PROBE = ("%sorted_probe_pallas = s32[8,1]{1,0} custom-call(s32[8,1]{1,0} "
         "%a, s32[1,128]{1,0} %b), custom_call_target=\"tpu_custom_call\"")


def _fused_norm():
    def op(name, start, dur, scope=None):
        st = {"tf_op": f"jit(apply)/jit(main)/{scope}/pallas_call"} \
            if scope else {}
        return [name, start * MS, dur * MS, st, "XLA Ops"]
    dev = [
        op(PROBE, 100, 2, "q1_safety_level"),
        op(RADIUS, 110, 20, "q4_nearby_monuments"),
        op("%fusion.7 = f32[8192]{0} fusion(...)", 125, 10,
           "q4_nearby_monuments"),
        op(RADIUS.replace(".3 =", ".4 ="), 140, 30, "q5_suspicious_names"),
        op(TOPK, 175, 5, "q3_largest_religions"),
        op("%copy.1 = f32[512]{0} copy(...)", 185, 4),
        op("%fusion.9 = f32[8]{0} fusion(...)", 195, 10,
           "q7_worrisome_tweets"),
    ]
    host = [["bench.window", 100 * MS, 100 * MS]]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_a_kernel_no_reader_named_is_found_by_its_instruction():
    red = trace.reduce(_fused_norm(), run.KERNELS)
    k = red["kernels"]
    assert k["radius_join"]["events"] == 2
    assert k["radius_join"]["time_ns"] == 50 * MS
    assert k["radius_join"]["bytes"] == 2 * (8192 * 8 * 8 + 8192 * 4
                                             + 50176 * 4)
    assert k["segment_topk"]["time_ns"] == 5 * MS
    # a kernel the caller names stays under its name, and only there
    assert k["hash_probe"]["time_ns"] == 2 * MS
    assert "sorted_probe" not in k


def test_a_new_roofline_is_one_file(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench" / "metrics" / "radius_join_roofline.tput.py"
     ).write_text("from bench.layer import roofline\n\n\n"
                  "def read(ctx):\n"
                  "    return roofline(ctx, 'radius_join')\n")
    red = trace.reduce(_fused_norm(), run.KERNELS)
    ctx = _ctx({}, 1, red)
    got = spec.reader("radius_join_roofline.tput", str(tmp_path))(ctx)
    nbytes = red["kernels"]["radius_join"]["bytes"]
    assert got == pytest.approx(100 * nbytes / 819e9 / 0.050)
    assert 0 < got < 100


def test_scopes_group_device_time_by_the_first_named_scope():
    red = trace.reduce(_fused_norm(), run.KERNELS)
    sc = red["scopes"]
    assert sc["q4_nearby_monuments"] == {"time_ns": 30 * MS, "events": 2}
    assert sc["q5_suspicious_names"]["time_ns"] == 30 * MS
    assert sc["q1_safety_level"]["time_ns"] == 2 * MS
    # cut at the window's end; an operation with no scope is in none
    assert sc["q7_worrisome_tweets"]["time_ns"] == 5 * MS
    assert sum(s["time_ns"] for s in sc.values()) == 72 * MS


def test_a_single_udf_trace_has_no_scopes():
    norm = trace.read(os.path.join(os.path.dirname(__file__), "recorded",
                                   "q6_backlog_trace.json.gz"))
    assert trace.reduce(norm, run.KERNELS)["scopes"] == {}


def test_stage_device_time_per_batch():
    norm = _fused_norm()
    lo = 100 * MS
    spans = [("compute.execute", lo + 5 * MS, lo + 50 * MS),
             ("compute.execute", lo + 60 * MS, lo + 95 * MS),
             ("compute.execute", lo + 98 * MS, lo + 140 * MS),
             ("compute.parse", lo, lo + 5 * MS)]
    red = trace.reduce(norm, run.KERNELS, spans)
    # two runs have their middle inside the window, the third past it
    assert red["spans"]["compute.execute"] == 2
    ctx = _ctx({}, 1, red)
    assert layer.stage_device_ms_per_batch(ctx, "q4_nearby_monuments") \
        == pytest.approx(15.0)
    assert layer.stage_device_ms_per_batch(ctx, "q2_no_such") is None
    assert layer.stage_device_ms_per_batch(_ctx({}, 1, None), "q4") is None


# the two kernels' numbers as the reduction gave them before it found
# kernels by name (recorded q6_backlog traces, one TPU v5e)
@pytest.mark.parametrize("name,want", [
    ("q6_backlog_trace", {"time_ns": 12489268, "events": 2,
                          "bytes": 9015296}),
    ("q6_backlog_spans_trace", {"time_ns": 49957072, "events": 8,
                                "bytes": 36061184}),
])
def test_recorded_kernels_read_as_before(name, want):
    norm = trace.read(os.path.join(os.path.dirname(__file__), "recorded",
                                   name + ".json.gz"))
    red = trace.reduce(norm, run.KERNELS)
    assert red["kernels"]["segment_sum"] == want
    assert red["kernels"]["hash_probe"] == {"time_ns": 0, "events": 0,
                                            "bytes": 0}


XSPACE = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 20000000000 }
    events { metadata_id: 2 offset_ps: 30000000000 duration_ps: 5000000000 }
    events { metadata_id: 1 offset_ps: 40000000000 duration_ps: 10000000000 }
  }
  lines { id: 2 name: "Framework Name Scope" timestamp_ns: 1000000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 60000000000 } }
  event_metadata { key: 1 value { id: 1
    name: "%radius_join_pallas.1 = f32[8,1]{1,0} custom-call(f32[8,1]{1,0} %a)"
    stats { metadata_id: 1
            str_value: "jit(apply)/q4_nearby_monuments/jit(topk)/pallas_call" }
  } }
  event_metadata { key: 2 value { id: 2 name: "%add.3 = f32[8]{0} add()"
    stats { metadata_id: 2 ref_value: 3 } } }
  event_metadata { key: 3 value { id: 3 name: "jit(apply)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "jit(apply)/while/body/add" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
'''


def test_load_keeps_each_operations_name_stack_from_its_metadata(tmp_path):
    """On a TPU an operation's events carry no ``tf_op``; its metadata
    does, as a string or as a reference to a stat's name."""
    from jax.profiler import ProfileData
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    norm = trace.load(str(tmp_path))
    (ops,) = norm["devices"].values()
    assert [o[3].get("tf_op") for o in ops] == [
        "jit(apply)/q4_nearby_monuments/jit(topk)/pallas_call",
        "jit(apply)/while/body/add",
        "jit(apply)/q4_nearby_monuments/jit(topk)/pallas_call"]
    red = trace.reduce(norm, run.KERNELS)
    # the scope line is not an operation: busy is the ops' union alone
    assert red["busy_s"] == pytest.approx(0.035)
    assert red["scopes"] == {"q4_nearby_monuments": {"time_ns": 30 * MS,
                                                     "events": 2}}
    assert red["kernels"]["radius_join"]["time_ns"] == 30 * MS


@pytest.mark.parametrize("stack,scope", [
    ("jit(apply)/q4_nearby_monuments/jit(topk)/pallas_call",
     "q4_nearby_monuments"),
    ("jit(apply_fn)/jit(main)/q6_tweet_context/while/body/add",
     "q6_tweet_context"),
    ("jit(apply)/while/body/cond/branch_1_fun/select_n", None),
    ("jit(apply)/dot_general", None),
    ("", None),
])
def test_scope_of_a_name_stack(stack, scope):
    assert trace.scope_of(stack) == scope


@pytest.fixture(scope="module")
def fused_op_names():
    """The name stack of every instruction of the program's enrichment
    executable, as the compiler keeps it (``op_name``) and a TPU profile
    gives it (``tf_op``): for the paper's eight UDFs fused, and for Q6
    alone.  Compiled on the CPU, at a tiny size."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import data
    from repro.core import RefStore
    from repro.core.computing import ComputingRunner, ComputingSpec
    from repro.core.enrich import queries as Q
    cards = {k: max(8, v // 1000) for k, v in Q.PAPER_CARDINALITIES.items()}
    tables = data.reference_tables(cards, 5)
    store = RefStore()
    for name, t in tables.items():
        rt = store.create(name, t["key"].shape[0] + 64, data.SCHEMAS[name])
        rt.upsert(t["key"], **{c: v for c, v in t.items() if c != "key"})
    out = {}
    for plan in (("q6",), tuple(STAGES)):
        udfs = [Q.get_udf(u) for u in plan]
        udf = udfs[0] if len(udfs) == 1 else Q.chain("all", *udfs)
        r = ComputingRunner(ComputingSpec(udf, 64), store)
        tw = data.parsed_columns(data.tweets(5, 0, 64))
        batch = r.parse(dict(tw, valid=np.ones(64, bool)))
        snaps = store.snapshot(udf.ref_tables)
        refs = r._refs_to_device(snaps)
        if udf.stages:
            state = r._get_staged_state(refs, snaps)
        else:
            state = r._get_state(refs, tuple(s.version
                                             for s in snaps.values()))
        dev = {k: jnp.asarray(v) for k, v in batch.items()}
        text = jax.jit(udf.apply_fn).lower(dev, state, refs).compile(
        ).as_text()
        out[len(plan)] = (udf, set(re.findall(r'op_name="([^"]*)"', text)))
    return out


STAGES = {"udf2": "udf2_tweet_safety_check", "q1": "q1_safety_level",
          "q2": "q2_religious_population", "q3": "q3_largest_religions",
          "q4": "q4_nearby_monuments", "q5": "q5_suspicious_names",
          "q6": "q6_tweet_context", "q7": "q7_worrisome_tweets"}


def test_every_fused_stage_is_a_scope_and_a_single_udf_has_none(
        fused_op_names):
    udf, names = fused_op_names[len(STAGES)]
    assert [s.name for s in udf.stages] == list(STAGES.values())
    assert {trace.scope_of(n) for n in names} - {None} == set(
        STAGES.values())
    _, names = fused_op_names[1]
    assert {trace.scope_of(n) for n in names} == {None}


def test_a_trace_whose_metadata_cannot_be_read_gives_no_name_stacks():
    from jax.profiler import ProfileData
    assert trace.metadata_stat(b"\x0a\x05ab\xff\xff", "tf_op") == {}
    good = ProfileData.text_proto_to_serialized_xspace(XSPACE)
    assert trace.metadata_stat(good, "no_such_stat") == {
        "/device:TPU:0": {}}
