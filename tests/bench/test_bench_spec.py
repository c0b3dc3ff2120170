"""The benchmark is data: each cell loads from its files alone, and a new
cell, configuration, traffic mix or per-layer metric is new files plus
an entry in ``BENCHMARK.json``."""

import json
import os
import re
import shutil

import pytest

from bench import schedule, spec

ROOT = spec.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_each_cell_loads_from_its_files(name):
    cell = spec.load_cell(name)
    cfg = cell.config
    for key in ("name", "source", "plan", "batch_size", "model", "refresh",
                "tables", "guarantees", "precision", "control", "assumed"):
        assert key in cfg, key
    sched = schedule.make(cell.traffic, 2**31 + 1, BENCH["run_seconds"],
                          cfg["batch_size"])
    assert sched.warm == cell.traffic["warmup_batches"] * cfg["batch_size"]
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names
        assert callable(spec.reader(m["name"]))


def test_benchmark_file_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            cfg = json.load(f)
        assert all(k in cfg for k in c["reduced"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        e2e = {e["name"]: e for e in BENCH["end_to_end"]}
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in CELLS
            assert w in moved.get("workloads", CELLS)
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))


def test_a_new_cell_is_found_by_name_from_new_files_only(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    cfg = json.loads((tmp_path / "bench" / "configs" / "idea_q1.json")
                     .read_text())
    cfg["name"] = "idea_q1_b420"
    cfg["batch_size"] = 420
    (tmp_path / "bench" / "configs" / "idea_q1_b420.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "trickle.json").write_text(json.dumps(
        {"kind": "poisson", "warmup_batches": 1, "rate": 500,
         "gap_seed": 7}))
    (tmp_path / "bench" / "metrics" / "batches_seen.lat.py").write_text(
        "def read(ctx):\n    return float(ctx.batches)\n")
    bench["configs"].append({"name": "idea_q1_b420", "source": "x",
                             "file": "bench/configs/idea_q1_b420.json",
                             "reduced": ["nodes", "batch_size"],
                             "why": "x"})
    bench["workloads"].append({"name": "q1b420_trickle",
                               "config": "idea_q1_b420",
                               "traffic": "trickle", "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"].startswith("visible"):
            m["workloads"].append("q1b420_trickle")
    bench["per_layer"].append({"name": "batches_seen.lat", "unit": "1",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "computing job",
                               "moves": "visible_p50_s",
                               "workloads": ["q1b420_trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("q1b420_trickle", str(tmp_path / "BENCHMARK.json"))
    assert cell.config["batch_size"] == 420
    assert cell.traffic["rate"] == 500
    assert {m["name"] for m in cell.end_to_end} == {"visible_p50_s",
                                                    "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["batches_seen.lat"]

    class Ctx:
        batches = 3
    Ctx.cell = cell
    got = spec.read_metrics(cell.per_layer, Ctx)
    assert got == {"batches_seen.lat": {"value": 3.0, "unit": "1"}}


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        spec.load_cell("no_such_cell")
