"""The paper's full fused feed (UDF2 > Q1..Q7, configuration ``idea_q1q7``)
against the benchmark's plain numpy reference (``bench/reference.py``),
at 1/100 of the paper's table sizes on the CPU, and the spatial operators
on planted points.

The feed runs through the normal path, as ``bench/run.build_feed``
builds it: ``SocketAdapter -> parse -> enrich(Q.chain(...)) -> store``
under a ``FeedManager``, with the seeded tweets of ``bench/data.py``
sent over the socket.  Every enriched column of every stored tweet must
equal the float32 reference element for element; the reference one step
lower (bfloat16) must not.
"""

import dataclasses
import os
import socket
import sys
import threading

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import data, reference, run, spec  # noqa: E402
from repro.core import ComputingRunner, ComputingSpec, RefStore  # noqa: E402
from repro.core.enrich import ops  # noqa: E402
from repro.core.enrich import queries as Q  # noqa: E402
from repro.core.records import SyntheticTweets  # noqa: E402
from repro.kernels import dispatch_mode  # noqa: E402

SEED = 2**33 + 18
TABLE_DIV = 100


def _cell(batch: int):
    cell = spec.load_cell("q1q7_backlog")
    cfg = dict(cell.config, batch_size=batch)
    cfg["tables"] = {k: max(8, v // TABLE_DIV)
                     for k, v in cfg["tables"].items()}
    return dataclasses.replace(cell, config=cfg)


def _feed(batch: int, n: int):
    """Send ``n`` seeded tweets through the fused feed; the stored rows,
    the tweets as a correct parser makes them, the tables and the
    plan's UDFs."""
    cell = _cell(batch)
    tables, _, mgr, udf, adapter, plan = run.build_feed(cell, SEED, False)
    assert [s.name for s in udf.stages] == [
        Q.get_udf(u).name for u in cell.config["plan"]["udfs"]]
    h = mgr.submit(plan)
    lines = b"".join(data.json_lines(data.tweets(SEED, 0, n)))

    def send():
        with socket.create_connection(adapter.address[:2]) as c:
            c.sendall(lines)

    t = threading.Thread(target=send)
    t.start()
    t.join(timeout=120)
    h.join(timeout=600)
    stored = run._stored_rows(h)
    order = np.argsort(stored["id"], kind="stable")
    stored = {k: v[order] for k, v in stored.items()}
    want = data.parsed_columns(data.tweets(SEED, 0, n))
    return stored, want, tables, cell.config["plan"]["udfs"]


def _assert_equal_to_reference(stored, want, tables, udfs, n):
    np.testing.assert_array_equal(stored["id"], np.arange(n))
    ref = reference.enrich(udfs, want, tables)
    assert len(ref) == 17 and set(ref) <= set(stored)
    bad = {c: int((~(np.asarray(stored[c]) == v).reshape(n, -1).all(1))
                  .sum())
           for c, v in ref.items()}
    assert all(v == 0 for v in bad.values()), bad
    return ref


def test_fused_q1q7_feed_equals_float32_reference_element_for_element():
    n = 3 * 512 + 100                      # three full frames and a tail
    stored, want, tables, udfs = _feed(512, n)
    ref = _assert_equal_to_reference(stored, want, tables, udfs, n)
    # the radius joins found something to compare: not all -1 or 0
    assert (ref["nearby_monument_count"] > 0).any()
    assert (ref["nearby_facility_counts"] > 0).any()
    assert (ref["nearby_religious_buildings"] >= 0).any()
    # the bfloat16 control is one step lower and has to differ
    low = reference.enrich(udfs, want, tables, "bfloat16")
    assert any(not np.array_equal(low[c], v) for c, v in ref.items())


def test_fused_q1q7_feed_kernels_in_interpret_mode_equal_reference():
    n = 256                                # one small batch
    with dispatch_mode("pallas"):
        stored, want, tables, udfs = _feed(256, n)
    _assert_equal_to_reference(stored, want, tables, udfs, n)


def test_q1q7_control_in_bfloat16_differs_from_float32():
    udfs = _cell(256).config["plan"]["udfs"]
    tables = data.reference_tables(_cell(256).config["tables"], SEED)
    t = data.parsed_columns(data.tweets(SEED, 0, 2048))
    hi = reference.enrich(udfs, t, tables)
    lo = reference.enrich(udfs, t, tables, "bfloat16")
    off = {c for c, v in hi.items() if not np.array_equal(lo[c], v)}
    # the spatial columns move once positions round to bfloat16
    assert {"nearby_monuments", "nearby_facility_counts",
            "district"} <= off


# ---------------------------------------------------------------------------
# spatial operators on planted points
# ---------------------------------------------------------------------------

F32 = np.float32


def _up(x):
    return np.nextafter(F32(x), F32(np.inf))


def _down(x):
    return np.nextafter(F32(x), F32(-np.inf))


def _planted():
    """Probe points and reference rows near +-60 and +-180, with pairs at
    exactly the radius (2.5: 1.5**2 + 2**2 and 2.5**2 are exact), one
    float32 ulp inside and one outside it, and two rows at equal
    distance from one point."""
    pts = np.array([[58.5, 179.0],      # 0: rows 0-2 along x, 2.5 +- ulp
                    [-58.0, 177.5],     # 1: row 3 at (-1.5, 2), 2.5 exactly
                    [0.0, 179.0],       # 2: rows 4, 5 at equal distance
                    [-59.0, -177.5],    # 3: rows 6, 7 at the radius +- ulp
                    [59.9, -179.9]],    # 4: nothing near
                   F32)
    refs = np.array([[61.0, 179.0],                         # 0: at 2.5
                     [_down(61.0), 179.0],                  # 1: ulp inside
                     [_up(61.0), 179.0],                    # 2: ulp outside
                     [-59.5, 179.5],                        # 3: at 2.5
                     [1.0, 179.0],                          # 4: tie, 1.0
                     [-1.0, 179.0],                         # 5: tie, 1.0
                     [-59.0, _up(-175.0)],                  # 6: ulp outside
                     [-59.0, _down(-175.0)],                # 7: ulp inside
                     [10.0, 10.0]],                         # 8: far
                    F32)
    group = np.array([3, 3, 1, 0, 2, 2, 5, 5, 0], np.int32)
    return pts, refs, group


RADIUS = 2.5


def _ref_d2(pts, refs):
    """``bench/reference._d2`` over every pair, (B, R)."""
    return reference._d2(pts[:, None, 0], pts[:, None, 1],
                         refs[None, :, 0], refs[None, :, 1], F32)


def _identity_d2(pts, refs):
    """The matmul identity the program once used, in float32."""
    p, r = pts.astype(F32), refs.astype(F32)
    d2 = ((p * p).sum(1)[:, None] + (r * r).sum(1)[None, :]
          - F32(2) * (p @ r.T))
    return np.maximum(d2, F32(0))


def test_planted_points_decide_the_radius_boundary():
    """The planted pairs sit where they are meant to, and the matmul
    identity |p|^2 + |r|^2 - 2 p.r that ``pairwise_dist2`` once computed
    decides at least one of them otherwise: near |p|^2 ~ 36,000 its
    float32 rounding step is ~0.004, some 200 times the 2e-5 by which
    rows 1 and 2 lie either side of the radius.  With it, the three
    operator tests below fail on the jnp path."""
    pts, refs, _ = _planted()
    d2 = _ref_d2(pts, refs)
    r2 = F32(RADIUS) * F32(RADIUS)
    hit = d2 <= r2
    # each planted boundary case is where it is meant to be
    assert d2[0, 0] == r2 and d2[1, 3] == r2
    assert hit[0, 1] and not hit[0, 2]
    assert hit[3, 7] and not hit[3, 6]
    assert d2[2, 4] == d2[2, 5]
    assert ((_identity_d2(pts, refs) <= r2) != hit).any()


@pytest.mark.parametrize("mode", ["reference", "pallas"])
def test_planted_radius_count_equals_elementwise_reference(mode):
    pts, refs, _ = _planted()
    want = (_ref_d2(pts, refs) <= F32(RADIUS) * F32(RADIUS)).sum(1)
    with dispatch_mode(mode):
        got = ops.radius_count(jnp.asarray(pts), jnp.asarray(refs), RADIUS)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("mode", ["reference", "pallas"])
def test_planted_radius_topk_equals_elementwise_reference(mode):
    pts, refs, _ = _planted()
    k = 3
    d2 = _ref_d2(pts, refs)
    r2 = F32(RADIUS) * F32(RADIUS)
    want_idx = np.full((pts.shape[0], k), -1)
    want_d2 = np.full((pts.shape[0], k), np.inf, F32)
    for i in range(pts.shape[0]):
        order = np.lexsort((np.arange(refs.shape[0]), d2[i]))
        keep = [j for j in order if d2[i, j] <= r2][:k]
        want_idx[i, :len(keep)] = keep
        want_d2[i, :len(keep)] = d2[i, keep]
    with dispatch_mode(mode):
        idx, dd, count = ops.radius_topk(jnp.asarray(pts),
                                         jnp.asarray(refs), RADIUS, k)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_array_equal(np.asarray(dd), want_d2)
    np.testing.assert_array_equal(np.asarray(count), (d2 <= r2).sum(1))
    assert list(np.asarray(idx)[2, :2]) == [4, 5]   # tie: lower row first


def test_planted_group_count_equals_elementwise_reference():
    pts, refs, group = _planted()
    groups = 8
    hit = _ref_d2(pts, refs) <= F32(RADIUS) * F32(RADIUS)
    want = np.stack([np.bincount(group[h], minlength=groups) for h in hit])
    got = ops.group_count_within_radius(jnp.asarray(pts), jnp.asarray(refs),
                                        jnp.asarray(group), groups, RADIUS)
    np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# upload_bytes: the bytes of the trimmed columns put on the device
# ---------------------------------------------------------------------------

def _trimmed_bytes(store, tables):
    q = ComputingRunner.TRIM_QUANTUM
    total = 0
    for snap in store.snapshot(tables).values():
        n = min(snap.capacity, ((max(snap.size, 1) + q - 1) // q) * q)
        total += sum(v[:n].nbytes for v in snap.arrays.values())
    return total


@pytest.mark.parametrize("udfs", [("q1",), ("udf2", "q1", "q2", "q3", "q4",
                                            "q5", "q6", "q7")],
                         ids=["one_table", "fused_q1q7"])
def test_upload_bytes_counts_trimmed_columns(udfs):
    store = RefStore()
    Q.make_reference_tables(store, scale=0.002, seed=7)
    parts = [Q.get_udf(u) for u in udfs]
    udf = parts[0] if len(parts) == 1 else Q.chain("q1q7", *parts)
    runner = ComputingRunner(ComputingSpec(udf, batch_size=64), store, None)
    frame = next(iter(SyntheticTweets(seed=3).batches(64, 64)))
    runner.run(frame)
    want = _trimmed_bytes(store, udf.ref_tables)
    assert want > 0
    assert runner.stats.upload_bytes == want
    runner.run(frame)                       # refresh="always" re-uploads
    assert runner.stats.upload_bytes == 2 * want
