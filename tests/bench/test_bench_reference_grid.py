"""The reference's grid search for the spatial joins (Q4, Q5, Q7) and its
vectorised window count (Q7) against the brute-force search they
replaced, kept here as the oracle: every tweet against every row, each
squared distance in the stated precision.  Equal element for element, in
float32 and in the bfloat16 control, on seeded tables and on planted
points at the radius, just beyond it, at equal distances and at the
edges of the grid."""

import ml_dtypes
import numpy as np
import pytest

from bench import data, reference

FTS = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}
SEEDS = [3, 2**31 + 17]
TENTH = {"monuments": 5_000, "religious_buildings": 1_000,
         "facilities": 5_000, "attack_events": 500,
         "suspicious_names": 100_000}


# ---------------------------------------------------------------------------
# the oracle: the O(tweets x rows) search
# ---------------------------------------------------------------------------

def _d2_all(px, py, rx, ry, ft):
    dx = px.astype(ft)[:, None] - rx.astype(ft)[None, :]
    dy = py.astype(ft)[:, None] - ry.astype(ft)[None, :]
    return (dx * dx + dy * dy).astype(ft)


def brute_nearest(t, tabs, table, radius, k, ft):
    ref = tabs[table]
    n = t["lat"].shape[0]
    out = np.full((n, k), -1, np.int64)
    r2 = ft(radius) * ft(radius)
    kk = min(k, ref["lat"].shape[0])
    for lo in range(0, n, 256):
        s = slice(lo, min(lo + 256, n))
        d2 = _d2_all(t["lat"][s], t["lon"][s], ref["lat"], ref["lon"],
                     ft).astype(np.float64)
        kth = np.partition(d2, kk - 1, axis=1)[:, kk - 1:kk]
        r, c = np.nonzero((d2 <= kth) & (d2 <= r2))
        order = np.lexsort((c, d2[r, c], r))
        r, c = r[order], c[order]
        rank = np.arange(r.shape[0]) - np.searchsorted(r, r)
        keep = rank < k
        blk = out[s]
        blk[r[keep], rank[keep]] = c[keep]
        out[s] = blk
    return out


def brute_within_count(t, tabs, table, radius, ft, group="", groups=0):
    ref = tabs[table]
    n = t["lat"].shape[0]
    r2 = ft(radius) * ft(radius)
    out = np.zeros((n, max(groups, 1)), np.int32)
    for lo in range(0, n, 256):
        s = slice(lo, min(lo + 256, n))
        hit = _d2_all(t["lat"][s], t["lon"][s], ref["lat"], ref["lon"],
                      ft) <= r2
        if groups:
            r, c = np.nonzero(hit)
            b = hit.shape[0]
            out[s] = np.bincount(r * groups + ref[group][c],
                                 minlength=b * groups).reshape(b, groups)
        else:
            out[s, 0] = hit.sum(1)
    return out if groups else out[:, 0]


def loop_attack_counts(rels, ts, ev):
    order = np.lexsort((ev["time"], ev["religion"]))
    er, et = ev["religion"][order], ev["time"][order]
    counts = np.zeros(rels.shape, np.int32)
    for j in range(rels.shape[1]):
        r = rels[:, j]
        lo_r = np.searchsorted(er, r, side="left")
        hi_r = np.searchsorted(er, r, side="right")
        for i in np.nonzero(r >= 0)[0]:
            seg = et[lo_r[i]:hi_r[i]]
            counts[i, j] = (np.searchsorted(seg, ts[i], side="left")
                            - np.searchsorted(seg, ts[i]
                                              - reference.TWO_MONTHS,
                                              side="right"))
    return counts


def _both(monkeypatch, udfs, t, tabs, precision):
    got = reference.enrich(udfs, t, tabs, precision)
    with monkeypatch.context() as m:
        m.setattr(reference, "_nearest", brute_nearest)
        m.setattr(reference, "_within_count", brute_within_count)
        want = reference.enrich(udfs, t, tabs, precision)
    return got, want


def _assert_same(got, want):
    assert set(got) == set(want)
    for c, v in want.items():
        assert got[c].dtype == v.dtype, c
        np.testing.assert_array_equal(got[c], v, err_msg=c)


# ---------------------------------------------------------------------------
# seeded tables at a tenth of the paper's cardinalities
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", sorted(FTS))
@pytest.mark.parametrize("seed", SEEDS)
def test_grid_equals_brute_force_on_seeded_tables(monkeypatch, seed,
                                                  precision):
    tabs = data.reference_tables(TENTH, seed)
    t = data.parsed_columns(data.tweets(seed, 0, 1500))
    got, want = _both(monkeypatch, ["q4", "q5", "q7"], t, tabs, precision)
    _assert_same(got, want)
    # the joins found rows: not every answer is a fill value
    assert (want["nearby_monuments"] >= 0).sum() > 500
    assert want["nearby_facility_counts"].sum() > 0
    assert (want["religion_attack_counts"] > 0).any()


@pytest.mark.parametrize("seed", SEEDS)
def test_vectorised_window_count_equals_the_loop(seed):
    tabs = data.reference_tables(TENTH, seed)
    t = data.parsed_columns(data.tweets(seed, 0, 1500))
    ev = tabs["attack_events"]
    # planted: tweets at an event's time and two months after it, where
    # the window's open ends leave the event out, and a tweet just inside
    ts = t["created_at"].copy()
    ts[:3] = ev["time"][0], ev["time"][0] + reference.TWO_MONTHS, \
        ev["time"][0] + 1
    t = dict(t, created_at=ts)
    out = reference.q7(t, tabs, np.float32)
    rels = out["nearby_religions"]
    want = loop_attack_counts(rels.astype(np.int64), ts, ev)
    np.testing.assert_array_equal(out["religion_attack_counts"], want)
    assert out["religion_attack_counts"].dtype == np.int32
    assert want.sum() > 0


# ---------------------------------------------------------------------------
# planted points
# ---------------------------------------------------------------------------

def _table(lat, lon, n_groups=16):
    lat = np.asarray(lat, np.float32)
    lon = np.asarray(lon, np.float32)
    m = lat.shape[0]
    return {"key": np.arange(m, dtype=np.int64), "lat": lat, "lon": lon,
            "religion": (np.arange(m) % 7).astype(np.int32),
            "ftype": (np.arange(m) % n_groups).astype(np.int32)}


def _tweets(lat, lon):
    lat = np.asarray(lat, np.float32)
    n = lat.shape[0]
    return {"lat": lat, "lon": np.asarray(lon, np.float32),
            "created_at": np.full(n, 1_550_000_000, np.int64),
            "user_name_hash": np.arange(n, dtype=np.int64)}


def _planted_tables(rows_lat, rows_lon):
    rows = _table(rows_lat, rows_lon)
    ev = {"key": np.arange(4, dtype=np.int64),
          "time": np.array([1_549_000_000, 1_549_999_999, 1_550_000_000,
                            1_540_000_000], np.int64),
          "religion": np.array([0, 1, 1, 2], np.int32)}
    sn = {"key": np.array([1, 5], np.int64),
          "religion": np.array([3, 4], np.int32),
          "threat_level": np.array([2, 9], np.int32)}
    return {"monuments": rows, "religious_buildings": rows,
            "facilities": rows, "attack_events": ev,
            "suspicious_names": sn}


def _radius_cases():
    """Tweets at the origin and beside the grid's cells, rows at exactly
    the radius, one step of float32 beyond it, at equal distances in
    four directions, and at the corners of the rows' extent."""
    rows_lat, rows_lon = [], []
    for r in (reference.Q4_RADIUS, reference.Q5_RADIUS):
        beyond = np.nextafter(np.float32(r), np.float32(np.inf))
        rows_lat += [r, -r, 0.0, 0.0, float(beyond), 0.0]
        rows_lon += [0.0, 0.0, r, -r, 0.0, -float(beyond)]
    # a lattice of half degrees: many equal distances and exact radii
    g = np.arange(-6, 6.5, 0.5)
    rows_lat += list(np.repeat(g, g.size) + 20.0)
    rows_lon += list(np.tile(g, g.size) + 40.0)
    # the rows' extent ends here; tweets lie past it
    rows_lat += [-59.9999, 59.9999, -59.9999, 59.9999]
    rows_lon += [-179.9999, -179.9999, 179.9999, 179.9999]
    tw_lat = [0.0, 20.0, 20.25, 21.5, 23.0, 60.0, -60.0, 60.0, 1.5, 0.75]
    tw_lon = [0.0, 40.0, 40.25, 38.5, 43.0, 180.0, -180.0, -180.0, 1.5,
              0.0]
    # each side of every cell boundary of both radii, near the origin
    for r in (reference.Q4_RADIUS, reference.Q5_RADIUS):
        side = reference.GRID_SIDE * r
        for b in (side, -side, 2 * side):
            for eps in (-1e-4, 0.0, 1e-4):
                tw_lat.append(b + eps)
                tw_lon.append(eps)
    return _planted_tables(rows_lat, rows_lon), _tweets(tw_lat, tw_lon)


@pytest.mark.parametrize("precision", sorted(FTS))
def test_grid_equals_brute_force_on_planted_points(monkeypatch, precision):
    tabs, t = _radius_cases()
    got, want = _both(monkeypatch, ["q4", "q5", "q7"], t, tabs, precision)
    _assert_same(got, want)
    if precision == "float32":
        # at the origin: four rows at exactly 1.5 (one beyond is out),
        # ranked by row among equal distances
        assert want["nearby_monument_count"][0] == 4
        assert list(want["nearby_monuments"][0, :4]) == [0, 1, 2, 3]
        # the lattice point (20, 40) has 9 rows within 1.5 of it at
        # distances 0, 0.5, 1, 1.5 and more: the count finds them all
        assert want["nearby_monument_count"][1] > reference.Q4_K


@pytest.mark.parametrize("precision", sorted(FTS))
def test_grid_equals_brute_force_on_a_dense_lattice(monkeypatch, precision):
    """Rows and tweets on a quarter-degree lattice around cell corners, so
    that distances tie and fall exactly on both radii."""
    rng = np.random.default_rng(11)
    rows_lat = rng.integers(-40, 40, 3000) * 0.25
    rows_lon = rng.integers(-40, 40, 3000) * 0.25
    tabs = _planted_tables(rows_lat, rows_lon)
    t = _tweets(rng.integers(-44, 44, 600) * 0.25,
                rng.integers(-44, 44, 600) * 0.25)
    got, want = _both(monkeypatch, ["q4", "q5", "q7"], t, tabs, precision)
    _assert_same(got, want)
    assert (want["nearby_monument_count"] > reference.Q4_K).any()


@pytest.mark.parametrize("precision", sorted(FTS))
def test_tweets_beyond_the_rows_grid_find_nothing(monkeypatch, precision):
    tabs = _planted_tables([10.0, 10.5, 11.0], [20.0, 20.5, 21.0])
    t = _tweets([-50.0, 13.0, 10.0, 50.0], [-170.0, 23.0, 17.0, 170.0])
    got, want = _both(monkeypatch, ["q4", "q5"], t, tabs, precision)
    _assert_same(got, want)
    assert (got["nearby_monuments"][[0, 3]] == -1).all()
    assert got["nearby_facility_counts"][[0, 3]].sum() == 0
    assert got["nearby_facility_counts"][1].sum() == 1
