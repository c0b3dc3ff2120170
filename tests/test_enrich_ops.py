"""Operator-level tests for the enrichment algebra (core/enrich/ops.py)
against brute-force numpy oracles, including hypothesis property sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.enrich import ops
from repro.core.refdata import KEY_SENTINEL


def _pad_sorted_keys(keys, capacity):
    out = np.full((capacity,), KEY_SENTINEL, np.int64)
    out[:len(keys)] = np.sort(keys)
    return out


# ---------------------------------------------------------------------------
# sorted_join
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.data())
def test_sorted_join_matches_dict(data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    nref = data.draw(st.integers(1, 64))
    nprobe = data.draw(st.integers(1, 64))
    cap = nref + data.draw(st.integers(0, 16))
    ref = rng.choice(200, nref, replace=False).astype(np.int64)
    keys = _pad_sorted_keys(ref, cap)
    probe = rng.integers(0, 220, nprobe).astype(np.int64)
    idx, found = jax.jit(ops.sorted_join)(jnp.asarray(probe),
                                          jnp.asarray(keys))
    table = {int(k): i for i, k in enumerate(keys[:nref])}
    for j in range(nprobe):
        if int(probe[j]) in table:
            assert bool(found[j])
            assert int(keys[int(idx[j])]) == int(probe[j])
        else:
            assert not bool(found[j])


def test_sorted_join_sentinel_probe_never_matches():
    keys = _pad_sorted_keys(np.array([5], np.int64), 4)
    probe = jnp.asarray(np.array([KEY_SENTINEL, 5], np.int64))
    _, found = ops.sorted_join(probe, jnp.asarray(keys))
    assert not bool(found[0]) and bool(found[1])


# ---------------------------------------------------------------------------
# segment ops
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.data())
def test_segment_sum_count(data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    n = data.draw(st.integers(1, 200))
    s = data.draw(st.integers(1, 20))
    seg = rng.integers(0, s, n)
    vals = rng.integers(0, 100, n)
    valid = rng.random(n) < 0.8
    got = np.asarray(ops.segment_sum(jnp.asarray(vals), jnp.asarray(seg), s,
                                     jnp.asarray(valid)))
    want = np.zeros(s, np.int64)
    for i in range(n):
        if valid[i]:
            want[seg[i]] += vals[i]
    np.testing.assert_array_equal(got, want)


def test_segment_topk_exact():
    seg = jnp.asarray(np.array([0, 0, 0, 1, 1, 2], np.int32))
    vals = jnp.asarray(np.array([5, 9, 7, 3, 8, 1], np.int32))
    pay = jnp.asarray(np.array([10, 11, 12, 13, 14, 15], np.int32))
    top_pay, top_val = ops.segment_topk(vals, seg, pay, 4, 2)
    np.testing.assert_array_equal(np.asarray(top_val),
                                  [[9, 7], [8, 3], [1, 0], [0, 0]])
    np.testing.assert_array_equal(np.asarray(top_pay),
                                  [[11, 12], [14, 13], [15, -1], [-1, -1]])


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_segment_topk_property(data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    n = data.draw(st.integers(1, 150))
    s = data.draw(st.integers(1, 10))
    k = data.draw(st.integers(1, 4))
    seg = rng.integers(0, s, n).astype(np.int32)
    vals = rng.integers(0, 1000, n).astype(np.int32)
    pay = np.arange(n, dtype=np.int32)
    top_pay, top_val = ops.segment_topk(
        jnp.asarray(vals), jnp.asarray(seg), jnp.asarray(pay), s, k)
    top_pay, top_val = np.asarray(top_pay), np.asarray(top_val)
    for g in range(s):
        want = sorted(vals[seg == g], reverse=True)[:k]
        got = [v for v, p in zip(top_val[g], top_pay[g]) if p >= 0]
        assert got == want, (g, got, want)
        # returned payloads actually hold the claimed values
        for v, p in zip(top_val[g], top_pay[g]):
            if p >= 0:
                assert vals[p] == v and seg[p] == g


# ---------------------------------------------------------------------------
# spatial ops
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.data())
def test_radius_ops_vs_bruteforce(data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    b = data.draw(st.integers(1, 40))
    r = data.draw(st.integers(1, 60))
    pts = rng.uniform(-10, 10, (b, 2)).astype(np.float32)
    refs = rng.uniform(-10, 10, (r, 2)).astype(np.float32)
    valid = rng.random(r) < 0.8
    radius = 4.0
    d2 = ((pts[:, None, :] - refs[None, :, :]) ** 2).sum(-1)
    want_count = ((d2 <= radius ** 2) & valid[None, :]).sum(1)

    count = np.asarray(ops.radius_count(
        jnp.asarray(pts), jnp.asarray(refs), radius, jnp.asarray(valid),
        chunk=8))
    np.testing.assert_array_equal(count, want_count)

    k = 3
    idx, dd, cnt = ops.radius_topk(jnp.asarray(pts), jnp.asarray(refs),
                                   radius, k, jnp.asarray(valid), chunk=8)
    idx, dd = np.asarray(idx), np.asarray(dd)
    np.testing.assert_array_equal(np.asarray(cnt), want_count)
    for i in range(b):
        dmask = np.where(valid, d2[i], np.inf)
        order = np.argsort(dmask)
        want = [j for j in order[:k] if dmask[j] <= radius ** 2]
        got = [j for j in idx[i] if j >= 0]
        assert got == want, (i, got, want)


def test_point_in_rect_chunked_equals_unchunked():
    rng = np.random.default_rng(0)
    pts = jnp.asarray(rng.uniform(-5, 5, (100, 2)).astype(np.float32))
    rects = jnp.asarray(
        np.stack([rng.uniform(-5, 0, 20), rng.uniform(-5, 0, 20),
                  rng.uniform(0, 5, 20), rng.uniform(0, 5, 20)],
                 axis=1).astype(np.float32))
    a_idx, a_found = ops.point_in_rect(pts, rects, chunk=16)
    b_idx, b_found = ops.point_in_rect(pts, rects, chunk=1000)
    np.testing.assert_array_equal(np.asarray(a_idx), np.asarray(b_idx))
    np.testing.assert_array_equal(np.asarray(a_found), np.asarray(b_found))


def test_pairwise_dist2_elementwise():
    rng = np.random.default_rng(1)
    a = rng.uniform(-3, 3, (17, 2)).astype(np.float32)
    b = rng.uniform(-3, 3, (23, 2)).astype(np.float32)
    got = np.asarray(ops.pairwise_dist2(jnp.asarray(a), jnp.asarray(b)))
    dx = a[:, None, 0] - b[None, :, 0]
    dy = a[:, None, 1] - b[None, :, 1]
    want = dx * dx + dy * dy                 # float32, every step
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# text / time ops
# ---------------------------------------------------------------------------

def test_contains_any():
    toks = jnp.asarray(np.array([[1, 2, 3, 0], [4, 5, 6, 0],
                                 [0, 0, 0, 0]], np.int64))
    kws = jnp.asarray(np.array([3, 9], np.int64))
    got = np.asarray(ops.contains_any(toks, kws))
    np.testing.assert_array_equal(got, [True, False, False])


def test_time_window_count():
    t = jnp.asarray(np.array([100, 200], np.int64))
    ev_t = jnp.asarray(np.array([95, 99, 150, 210], np.int64))
    ev_g = jnp.asarray(np.array([1, 1, 2, 1], np.int32))
    groups = jnp.asarray(np.array([[1, 2], [1, 2]], np.int32))
    got = np.asarray(ops.time_window_count_by_group(t, ev_t, ev_g, groups,
                                                    window=50))
    # t=100: window (50,100): events 95(g1), 99(g1) -> g1:2, g2:0
    # t=200: window (150,200): none strictly inside -> 0,0
    np.testing.assert_array_equal(got, [[2, 0], [0, 0]])
