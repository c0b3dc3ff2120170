"""Dry-run machinery integration test: one cheap cell end-to-end in a
subprocess (512 placeholder devices never touch this process)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.slow
def test_dryrun_cell_produces_roofline_artifact(tmp_path):
    env = {**os.environ, "PYTHONPATH": "src"}
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "mamba2-130m", "--shape", "decode_32k",
         "--mesh", "single", "--tag", "testrun"],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    art = (REPO / "launch_artifacts" / "dryrun"
           / "mamba2-130m__decode_32k__single@testrun.json")
    with open(art) as f:
        r = json.load(f)
    assert r["status"] == "ok"
    assert r["chips"] == 256
    rf = r["roofline"]
    assert rf["flops_per_dev"] > 0
    assert rf["memory_s"] > 0
    assert rf["dominant"] in ("compute", "memory", "collective")
    assert not r["f64_leaks"]
    # decode is memory-bound on any sane reading of the hardware
    assert rf["dominant"] != "compute"
    os.remove(art)
    art.with_suffix(".hlo.zst").unlink(missing_ok=True)
