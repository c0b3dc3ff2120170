"""Validate the trip-count-aware HLO cost analyzer against programs with
hand-computable FLOPs — including the scan case where XLA's own
cost_analysis undercounts (the reason hlocost exists)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlocost


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_single_matmul_flops_exact():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    txt = _compile_text(lambda a, b: a @ b, x, w)
    cost = hlocost.analyze_text(txt)
    assert cost.flops == 2 * 128 * 256 * 512
    # traffic >= read A + read B + write C
    assert cost.hbm_bytes >= 4 * (128 * 256 + 256 * 512 + 128 * 512)


def test_scan_flops_scale_with_trip_count():
    """The whole point: 10-layer scan must cost 10x one layer."""
    def body(c, w):
        return jnp.tanh(c @ w), None

    def f(x, ws):
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    one_mm = 2 * 64 * 128 * 128
    for trips in (2, 10):
        ws = jax.ShapeDtypeStruct((trips, 128, 128), jnp.float32)
        txt = _compile_text(f, x, ws)
        cost = hlocost.analyze_text(txt)
        assert cost.flops == trips * one_mm, (trips, cost.flops)
        # XLA's own analysis reports one body only — document the delta
        xla = hlocost.xla_cost_analysis(
            jax.jit(f).lower(x, ws).compile())["flops"]
        assert xla < 1.01 * one_mm     # body counted once, not x trips


def test_nested_scan_weights_multiply():
    def inner(c, w):
        return c @ w, None

    def outer(c, ws):
        y, _ = jax.lax.scan(inner, c, ws)
        return y, None

    def f(x, ws):
        y, _ = jax.lax.scan(outer, x, ws)
        return y

    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 5, 64, 64), jnp.float32)  # 3 x 5 loops
    txt = _compile_text(f, x, ws)
    cost = hlocost.analyze_text(txt)
    assert cost.flops == 15 * 2 * 32 * 64 * 64


def test_grad_scan_counts_fwd_plus_bwd():
    def body(c, w):
        return jnp.tanh(c @ w), None

    def f(x, ws):
        y, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(y)

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)
    txt = _compile_text(jax.grad(f, argnums=1), x, ws)
    cost = hlocost.analyze_text(txt)
    one_mm = 2 * 64 * 128 * 128
    # fwd (1 mm) + bwd (2 mms) per layer = 30 matmuls; XLA may add a
    # cotangent-epilogue matmul outside the loop
    assert 30 * one_mm <= cost.flops <= 33 * one_mm, \
        cost.flops / one_mm


def test_collectives_trip_weighted():
    """A psum inside a scan must count trip-many times."""
    import os
    import subprocess
    import sys
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
import repro
from repro.launch import hlocost
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("model",))
def body(c, w):
    return c @ w, None                      # w sharded on contracting dim
def f(x, ws):
    y, _ = jax.lax.scan(body, x, ws)
    return y
x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
ws = jax.ShapeDtypeStruct((7, 64, 64), jnp.float32)
with mesh:
    txt = jax.jit(f, in_shardings=(
        NamedSharding(mesh, P(None, "model")),
        NamedSharding(mesh, P(None, "model", None)))).lower(x, ws)\
        .compile().as_text()
cost = hlocost.analyze_text(txt)
n = cost.coll_counts.get("all-reduce", 0) + \
    cost.coll_counts.get("reduce-scatter", 0)
assert n >= 7, (n, cost.coll_counts)
print("OK", cost.coll_counts)
"""
    out = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                         env={**os.environ, "PYTHONPATH": "src"},
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_dus_traffic_counts_slice_not_buffer():
    """Donated buffers update in place (the decode KV-cache pattern):
    traffic ~ slice size, NOT the 64 MB buffer.  Without donation XLA
    must copy — and the analyzer should report that too."""
    def f(buf, upd):
        return jax.lax.dynamic_update_slice(buf, upd, (0, 0))

    buf = jax.ShapeDtypeStruct((4096, 4096), jnp.float32)   # 64 MB
    upd = jax.ShapeDtypeStruct((8, 8), jnp.float32)         # 256 B
    txt_inplace = jax.jit(f, donate_argnums=0).lower(buf, upd)\
        .compile().as_text()
    cost = hlocost.analyze_text(txt_inplace)
    assert cost.hbm_bytes < 4096 * 4096 * 4 / 4, cost.hbm_bytes
    txt_copy = _compile_text(f, buf, upd)
    cost_copy = hlocost.analyze_text(txt_copy)
    assert cost_copy.hbm_bytes >= 4096 * 4096 * 4    # the copy is real
