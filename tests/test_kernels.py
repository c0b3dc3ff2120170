"""Pallas kernel validation: interpret=True on CPU, swept over shapes and
dtypes, assert_allclose against the pure-jnp oracles (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.refdata import KEY_SENTINEL
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.hash_probe import ref as hp_ref
from repro.kernels.hash_probe.kernel import sorted_probe_pallas
from repro.kernels.segment_reduce import ref as sr_ref
from repro.kernels.segment_reduce.kernel import segment_sum_pallas
from repro.kernels.spatial_join import ref as sj_ref
from repro.kernels.spatial_join.kernel import (band_plan, latitude_order,
                                               radius_join_pallas)


# ---------------------------------------------------------------------------
# segment_reduce
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("r,s", [(100, 7), (2048, 128), (5000, 300),
                                 (1, 1), (4097, 129)])
def test_segment_sum_kernel(dtype, r, s):
    rng = np.random.default_rng(r + s)
    vals = jnp.asarray(rng.integers(0, 100, r).astype(dtype))
    seg = jnp.asarray(rng.integers(0, s, r).astype(np.int32))
    got = segment_sum_pallas(vals, seg, s, block_r=512, interpret=True)
    want = sr_ref.segment_sum(vals, seg, s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6)


def test_segment_sum_kernel_drops_out_of_range():
    vals = jnp.asarray(np.array([1, 2, 3], np.int32))
    seg = jnp.asarray(np.array([0, 5, 0], np.int32))   # 5 >= num_segments
    got = segment_sum_pallas(vals, seg, 2, block_r=512, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), [4, 0])


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3000), st.integers(1, 200), st.integers(0, 2**31))
def test_segment_sum_kernel_property(r, s, seed):
    rng = np.random.default_rng(seed)
    vals = jnp.asarray(rng.normal(size=r).astype(np.float32))
    seg = jnp.asarray(rng.integers(0, s, r).astype(np.int32))
    got = segment_sum_pallas(vals, seg, s, block_r=256, interpret=True)
    want = sr_ref.segment_sum(vals, seg, s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# hash_probe
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,r,cap", [(10, 8, 16), (600, 3000, 4096),
                                     (1, 1, 4), (513, 2049, 2100)])
def test_sorted_probe_kernel(b, r, cap):
    rng = np.random.default_rng(b * r)
    ref_real = rng.choice(10 * r, r, replace=False).astype(np.int64)
    keys = np.full((cap,), KEY_SENTINEL, np.int64)
    keys[:r] = np.sort(ref_real)
    probe = rng.integers(0, 12 * r, b).astype(np.int64)
    probe[0] = ref_real[0]                       # at least one hit
    kj, rj = jnp.asarray(probe), jnp.asarray(keys)
    gi, gf = sorted_probe_pallas(kj, rj, block_b=128, block_r=512,
                                 interpret=True)
    wi, wf = hp_ref.sorted_probe(kj, rj)
    np.testing.assert_array_equal(np.asarray(gf), np.asarray(wf))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_sorted_probe_kernel_64bit_keys():
    """Hash keys above 2^32 exercise the (hi, lo) int32 split."""
    keys = np.sort(np.array([2**40 + 7, 2**55 + 1, 5], np.int64))
    cap = np.concatenate([keys, [KEY_SENTINEL]])
    probe = jnp.asarray(np.array([2**55 + 1, 2**40 + 7, 2**40 + 8, 5,
                                  KEY_SENTINEL], np.int64))
    gi, gf = sorted_probe_pallas(probe, jnp.asarray(cap), interpret=True)
    np.testing.assert_array_equal(np.asarray(gf),
                                  [True, True, False, True, False])
    np.testing.assert_array_equal(np.asarray(gi), [2, 1, -1, 0, -1])


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 500), st.integers(1, 1000), st.integers(0, 2**31))
def test_sorted_probe_kernel_property(b, r, seed):
    rng = np.random.default_rng(seed)
    ref_real = rng.choice(5 * r, r, replace=False).astype(np.int64)
    keys = jnp.asarray(np.sort(ref_real))
    probe = jnp.asarray(rng.integers(0, 6 * r, b).astype(np.int64))
    gi, gf = sorted_probe_pallas(probe, keys, block_b=128, block_r=256,
                                 interpret=True)
    wi, wf = hp_ref.sorted_probe(probe, keys)
    np.testing.assert_array_equal(np.asarray(gf), np.asarray(wf))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


# ---------------------------------------------------------------------------
# spatial_join
# ---------------------------------------------------------------------------

def _radius_case(layout, b, r, k):
    """(px, py, rx, ry, valid, radius, block_b, block_r) for one layout of
    probe and reference points."""
    f32 = np.float32
    rng = np.random.default_rng(b + r + k)
    if layout == "uniform":
        px, py, rx, ry = (rng.uniform(-10, 10, n).astype(f32)
                          for n in (b, b, r, r))
        return px, py, rx, ry, rng.random(r) < 0.9, 4.0, 128, 256
    if layout == "spread":          # many latitude blocks: the band prunes
        px, rx = (rng.uniform(-60, 60, n).astype(f32) for n in (b, r))
        py, ry = (rng.uniform(-20, 20, n).astype(f32) for n in (b, r))
        return px, py, rx, ry, None, 1.5, 64, 256
    if layout == "one_lat":         # one band: the plan is the whole grid
        px, rx = np.full(b, 5.0, f32), np.full(r, 5.0, f32)
        py, ry = (rng.uniform(-10, 10, n).astype(f32) for n in (b, r))
        return px, py, rx, ry, None, 2.0, 64, 128
    if layout == "dup_refs":        # equal points in different blocks
        base = rng.integers(-40, 41, (r // 4, 2)).astype(f32) / 4
        pts = np.tile(base, (4, 1))[rng.permutation(r)]
        probe = rng.integers(-40, 41, (b, 2)).astype(f32) / 4
        return (probe[:, 0], probe[:, 1], pts[:, 0], pts[:, 1], None, 3.0,
                64, 128)
    if layout == "edge":            # exactly the radius away, and one ulp
        radius = 1.5
        px = np.zeros(b, f32)
        py = np.arange(b, dtype=f32)
        beyond = np.nextafter(f32(radius), f32(np.inf))
        lat = np.concatenate([np.full(128, radius, f32),
                              np.full(128, beyond, f32),
                              np.full(128, -radius, f32),
                              np.full(128, -beyond, f32),
                              rng.uniform(5, 60, r - 512).astype(f32)])
        order = rng.permutation(r)
        rx = lat[order]
        ry = np.tile(np.arange(128, dtype=f32), r // 128 + 1)[:r][order]
        return px, py, rx, ry, None, radius, 64, 128
    if layout == "invalid":         # whole blocks and scattered rows off
        px, py, rx, ry = (rng.uniform(-10, 10, n).astype(f32)
                          for n in (b, b, r, r))
        valid = (rng.random(r) < 0.5) & (np.arange(r) // 128 % 3 != 1)
        return px, py, rx, ry, valid, 3.0, 64, 128
    if layout == "few_hits":        # k above every probe's count
        px, py, rx, ry = (rng.uniform(-30, 30, n).astype(f32)
                          for n in (b, b, r, r))
        return px, py, rx, ry, None, 1.0, 64, 128
    raise KeyError(layout)


@pytest.mark.parametrize("b,r,k,layout", [
    pytest.param(40, 60, 3, "uniform", id="40-60-3"),
    pytest.param(300, 2000, 8, "uniform", id="300-2000-8"),
    pytest.param(1, 1, 2, "uniform", id="1-1-2"),
    pytest.param(257, 1025, 1, "uniform", id="257-1025-1"),
    pytest.param(600, 3000, 4, "spread", id="spread-600-3000-4"),
    pytest.param(200, 700, 5, "one_lat", id="one_lat-200-700-5"),
    pytest.param(150, 800, 6, "dup_refs", id="dup_refs-150-800-6"),
    pytest.param(100, 1000, 8, "edge", id="edge-100-1000-8"),
    pytest.param(300, 1500, 4, "invalid", id="invalid-300-1500-4"),
    pytest.param(200, 500, 8, "few_hits", id="few_hits-200-500-8"),
])
def test_radius_join_kernel(b, r, k, layout):
    px, py, rx, ry, valid, radius, bb, br = (
        jnp.asarray(v) if isinstance(v, np.ndarray) else v
        for v in _radius_case(layout, b, r, k))
    gi, gd, gc = radius_join_pallas(px, py, rx, ry, radius, k, valid,
                                    block_b=bb, block_r=br, interpret=True)
    wi, wd, wc = sj_ref.radius_join(px, py, rx, ry, radius, k, valid)
    np.testing.assert_array_equal(np.asarray(gc), np.asarray(wc))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd),
                               rtol=1e-5, atol=1e-5)
    if layout == "edge":            # the radius counts, one ulp past not
        np.testing.assert_array_equal(np.asarray(gc)[:64], 2)
    if layout == "few_hits":
        assert np.asarray(gc).max() < k


def _q4_band_inputs():
    """The q1q7_backlog cell's Q4 call, in the kernel's order: 8,192
    probes (6,720 tweets and 1,472 padding rows at (0, 0)) against 50,176
    monument rows (50,000 drawn as the benchmark draws them, the rest
    invalid), seed 0."""
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import data
    px = np.zeros(8192, np.float32)
    px[:6720] = data.parsed_columns(data.tweets(0, 0, 6720))["lat"]
    rx = np.zeros(50_176, np.float32)
    rx[:50_000] = data.reference_tables({"monuments": 50_000}, 0
                                        )["monuments"]["lat"]
    valid = jnp.asarray(np.arange(rx.shape[0]) < 50_000)
    order = latitude_order(jnp.asarray(px), jnp.ones(px.shape, bool))
    rorder = latitude_order(jnp.asarray(rx), valid)
    return jnp.asarray(px)[order], jnp.asarray(rx)[rorder], valid[rorder]


def _visited(lo, hi):
    return int(np.sum(np.asarray(hi) - np.asarray(lo)))


def test_band_plan_visits_few_tiles_of_the_q4_call():
    px, rx, valid = _q4_band_inputs()
    lo, hi = band_plan(px, rx, valid, 1.5, 256, 1024)
    assert _visited(lo, hi) <= 0.10 * 32 * 49
    assert np.all(np.asarray(hi) > np.asarray(lo))


def test_band_plan_visits_every_tile_at_one_latitude():
    px, rx = jnp.full((512,), 7.0, jnp.float32), jnp.full((2048,), 7.0,
                                                         jnp.float32)
    lo, hi = band_plan(px, rx, jnp.ones((2048,), bool), 1.5, 64, 256)
    np.testing.assert_array_equal(np.asarray(lo), 0)
    np.testing.assert_array_equal(np.asarray(hi), 2048 // 256)


def test_band_plan_visits_a_block_exactly_the_radius_away():
    """Probes at latitude 0; reference blocks at -30, exactly 1.5, one
    float32 ulp past 1.5, and 40: float32 d2 is exactly 1.5² in the
    second, which must be met, and above it in the third, which need not."""
    radius = 1.5
    beyond = np.nextafter(np.float32(radius), np.float32(np.inf))
    assert np.float32(beyond) * np.float32(beyond) > np.float32(radius ** 2)
    rx = np.concatenate([np.full(128, v, np.float32)
                         for v in (-30.0, radius, beyond, 40.0)])
    lo, hi = band_plan(jnp.zeros((8,), jnp.float32), jnp.asarray(rx),
                       jnp.ones((512,), bool), radius, 8, 128)
    assert (int(lo[0]), int(hi[0])) == (1, 2)


def test_band_plan_skips_blocks_of_invalid_references():
    rx = jnp.zeros((256,), jnp.float32)
    valid = jnp.arange(256) < 128
    lo, hi = band_plan(jnp.zeros((8,), jnp.float32), rx, valid, 1.0, 8, 128)
    assert (int(lo[0]), int(hi[0])) == (0, 1)


def test_latitude_order_sorts_kept_points_and_puts_the_rest_last():
    """Kept points ascend to within one of the 2**18 steps that 5,000
    points leave room for; the rest, NaN included, follow."""
    rng = np.random.default_rng(3)
    x = rng.uniform(-60, 60, 5000).astype(np.float32)
    x[:20] = x[20:40]
    x[50] = np.nan
    keep = rng.random(5000) < 0.8
    keep[50] = True
    perm = np.asarray(latitude_order(jnp.asarray(x), jnp.asarray(keep)))
    assert sorted(perm.tolist()) == list(range(5000))
    n_kept = int(keep.sum()) - 1
    kept = perm[:n_kept]
    assert np.all(keep[kept])
    step = (x[kept].max() - x[kept].min()) / (2**18 - 2)
    assert np.all(np.diff(x[kept]) >= -step)
    assert set(perm[n_kept:].tolist()) == set(np.flatnonzero(~keep)) | {50}


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 200), st.integers(1, 600), st.integers(1, 6),
       st.integers(0, 2**31))
def test_radius_join_kernel_property(b, r, k, seed):
    rng = np.random.default_rng(seed)
    px = jnp.asarray(rng.uniform(-8, 8, b).astype(np.float32))
    py = jnp.asarray(rng.uniform(-8, 8, b).astype(np.float32))
    rx = jnp.asarray(rng.uniform(-8, 8, r).astype(np.float32))
    ry = jnp.asarray(rng.uniform(-8, 8, r).astype(np.float32))
    gi, gd, gc = radius_join_pallas(px, py, rx, ry, 3.0, k,
                                    block_b=64, block_r=128, interpret=True)
    wi, wd, wc = sj_ref.radius_join(px, py, rx, ry, 3.0, k)
    np.testing.assert_array_equal(np.asarray(gc), np.asarray(wc))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kv,d", [
    (1, 256, 4, 4, 64),      # MHA
    (2, 256, 8, 2, 64),      # GQA 4:1
    (1, 384, 4, 1, 128),     # MQA, odd seq blocks
    (1, 128, 4, 4, 112),     # kimi-k2 head_dim (padded to 128)
])
def test_flash_attention_kernel(dtype, b, s, h, kv, d):
    rng = np.random.default_rng(s + h + d)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32),
                    dtype=dtype)
    k = jnp.asarray(rng.normal(size=(b, s, kv, d)).astype(np.float32),
                    dtype=dtype)
    v = jnp.asarray(rng.normal(size=(b, s, kv, d)).astype(np.float32),
                    dtype=dtype)
    got = flash_attention_pallas(q, k, v, causal=True, block_q=128,
                                 block_k=128, interpret=True)
    want = fa_ref.flash_attention(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


def test_flash_attention_noncausal():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 64)).astype(np.float32))
    got = flash_attention_pallas(q, k, v, causal=False, interpret=True)
    want = fa_ref.flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_model_sdpa():
    """The kernel and the model's XLA chunked path agree — they are two
    lowerings of the same attention (layers._sdpa is the dry-run path)."""
    from repro.configs import smoke_config
    from repro.models import layers as L
    cfg = smoke_config("deepseek-coder-33b").replace(
        num_heads=4, num_kv_heads=2, head_dim=64)
    rng = np.random.default_rng(1)
    b, s, d = 1, 256, 64
    q = jnp.asarray(rng.normal(size=(b, s, 4, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, 2, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, 2, d)).astype(np.float32))
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    want = L._sdpa(cfg, q, k, v, pos, pos, None, None, causal=True)
    got = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
