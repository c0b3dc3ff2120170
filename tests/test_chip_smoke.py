"""chip_smoke.py rehearsed on the CPU: its phases at a tiny size, with the
kernels forced through interpret mode, and its refusal to run anywhere
but on a TPU.  The compile-cache placement it relies on is checked too."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache
from repro.kernels import dispatch_mode

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_check_refuses_cpu(smoke):
    assert jax.default_backend() != "tpu"
    with pytest.raises(SystemExit, match="no TPU"):
        smoke.require_tpu()


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_script_fails_without_chip_or_program(where, tmp_path):
    """Off the chip, or copied away from the package, the script exits
    non-zero and never prints a result."""
    if where == "checkout":
        cwd = REPO
    else:
        cwd = tmp_path
        shutil.copy(REPO / "chip_smoke.py", cwd)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_phases_pass_on_cpu_in_interpret_mode(smoke):
    from repro.core.enrich import dispatch
    dispatch.reset_bucket_stats()
    dispatch.reset_path_stats()
    with dispatch_mode("pallas"):
        store = smoke.build_refs(scale=0.002, seed=0)
        h, stats = smoke.run_feed(store, total=3 * 256, batch=256, seed=0)
        assert stats.stored == 3 * 256
        assert stats.computing.state_builds > 0
        assert smoke.check_query(h)["groups"] > 0
        checked = smoke.check_kernels(store, probe_rows=256, topk_rows=4096,
                                      topk_segments=64, topk_k=4, seed=0)
        assert set(checked) == {"hash_probe", "spatial_join",
                                "segment_sum", "segment_topk"}
        counts = smoke.check_routing()
    assert all(n > 0 for n in counts.values())


def test_routing_check_fails_when_a_kernel_never_ran(smoke):
    from repro.core.enrich import dispatch
    dispatch.reset_bucket_stats()
    dispatch.reset_path_stats()
    with pytest.raises(AssertionError, match="no kernel-path dispatch"):
        smoke.check_routing()


def test_compile_cache_placement(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and is left to JAX; otherwise the
    cache sits at a fixed, git-ignored directory of the checkout."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    prev = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == \
            prev["jax_compilation_cache_dir"]
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
