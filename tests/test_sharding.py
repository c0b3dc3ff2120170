"""Sharding-rule + elastic-remesh tests (multi-device via subprocess so the
session's single-device jax stays untouched)."""

import os
import subprocess
import sys

import jax
import numpy as np

from repro.models.sharding import DEFAULT_RULES, spec_for


def test_spec_for_no_mesh_is_unconstrained():
    assert spec_for((8, 16), ("batch", "embed"), mesh=None) == \
        jax.sharding.PartitionSpec()


def _run(code: str) -> str:
    out = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                         env={**os.environ, "PYTHONPATH": "src"},
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_spec_for_divisibility_fallback_and_axis_reuse():
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import repro
from jax.sharding import PartitionSpec as P
from repro.models.sharding import sharding_ctx, spec_for, \
    recorded_fallbacks
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
with sharding_ctx(mesh):
    # divisible: sharded
    assert spec_for((16, 64), ("batch", "ffn")) == P("data", "model"), \
        spec_for((16, 64), ("batch", "ffn"))
    # 42 heads not divisible by 4 -> fallback to replication, recorded
    s = spec_for((8, 42), ("batch", "heads"))
    assert s == P("data",), s
    assert recorded_fallbacks(), "fallback not recorded"
    # same mesh axis cannot appear twice: second use dropped
    s = spec_for((64, 64), ("ffn", "vocab"))
    assert s == P("model",), s
print("OK")
"""
    assert "OK" in _run(code)


def test_elastic_remesh_reshard_roundtrip(tmp_path):
    """Checkpoint on a 2x4 mesh, restore resharded onto 8x1 and 4x2 —
    values identical, shardings actually applied."""
    code = rf"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
import repro
from repro.ckpt import save, restore
from repro.runtime.elastic import build_mesh, remesh_shardings

state = {{"w": jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32),
          "b": jnp.ones((32,), jnp.float32)}}
axes = {{"w": ("embed", "ffn"), "b": ("ffn",)}}
save(r"{tmp_path}", 7, state)

for mp in (1, 2, 4):
    mesh = build_mesh(model_parallel=mp)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    sh = remesh_shardings(shapes, axes, mesh)
    back = restore(r"{tmp_path}", state, shardings=sh)
    np.testing.assert_array_equal(np.asarray(back["w"]),
                                  np.asarray(state["w"]))
    assert back["w"].sharding.is_equivalent_to(
        jax.tree.leaves(sh)[1] if False else sh["w"], 2)
print("OK")
"""
    assert "OK" in _run(code)
