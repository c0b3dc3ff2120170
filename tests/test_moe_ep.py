"""Expert-parallel MoE (shard_map + all_to_all) correctness: must match the
single-device reference routing exactly when capacity is ample (8 fake
devices, subprocess)."""

import os
import subprocess
import sys

import pytest


def _run(code: str) -> str:
    out = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                         env={**os.environ, "PYTHONPATH": "src"},
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_moe_ep_matches_reference():
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
import repro
from repro.configs import smoke_config
from repro.models import moe as M
from repro.models import moe_ep as MEP
from repro.models.params import init_tree
from repro.models.sharding import sharding_ctx

# 2 (data) x 4 (model) mesh; 8 experts -> 2 per model shard
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
cfg = smoke_config("olmoe-1b-7b").replace(
    num_experts=8, experts_per_token=2, capacity_factor=8.0,
    dtype="float32", param_dtype="float32")
p = init_tree(M.moe_specs(cfg), jax.random.key(0), "float32")
x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model),
                      jnp.float32) * 0.3

y_ref, aux_ref = M.moe_ffn(cfg, p, x)        # no-mesh reference

with sharding_ctx(mesh):
    y_ep, aux_ep = jax.jit(
        lambda p, x: MEP.moe_ffn_ep(cfg, p, x))(p, x)

np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                           rtol=2e-4, atol=2e-5)
# aux is a load-balance heuristic: per-device pmean vs global mean differ
# at the percent level by construction
np.testing.assert_allclose(float(aux_ep), float(aux_ref), rtol=0.05)
print("OK exact-match")

# and through the full train loss of the moe family
from repro.models import api
cfg2 = smoke_config("olmoe-1b-7b").replace(
    capacity_factor=8.0, moe_ep=True, dtype="float32")
params = api.init_params(cfg2, jax.random.key(0))
batch = {"tokens": jnp.zeros((4, 16), jnp.int32),
         "targets": jnp.zeros((4, 16), jnp.int32)}
with sharding_ctx(mesh):
    l_ep, _ = jax.jit(lambda p, b: api.loss(cfg2, p, b))(params, batch)
l_ref, _ = api.loss(cfg2.replace(moe_ep=False), params, batch)
np.testing.assert_allclose(float(l_ep), float(l_ref), rtol=1e-4)
print("OK loss-match", float(l_ep), float(l_ref))
"""
    out = _run(code)
    assert "OK exact-match" in out and "OK loss-match" in out
