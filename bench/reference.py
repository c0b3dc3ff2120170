"""Plain numpy reference of the paper's enrichment UDFs (UDF2, Q1-Q7).

Written from the UDFs' stated semantics (An IDEA §4, §8 and appendix),
not from the program: nothing here imports it.  Every UDF reads the
benchmark's own reference tables (``bench.data.reference_tables``, rows in
ascending key order, the order a snapshot keeps them in) and the parsed
tweet columns, and returns the columns the stored record must carry.

Coordinates and distances are computed in the precision the
configuration states (``float32``).  ``precision="bfloat16"`` gives the
control: the same reference one step lower, which the comparison has to
refuse.
"""

from __future__ import annotations

from typing import Callable, Dict

import ml_dtypes
import numpy as np

from bench import data

Cols = Dict[str, np.ndarray]
FLOATS = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}

Q4_RADIUS, Q4_K = 1.5, 8
Q5_RADIUS, Q5_K = 3.0, 3
Q7_RADIUS, Q7_K = 3.0, 3
TWO_MONTHS = 62 * 24 * 3600


def _d2(px, py, rx, ry, ft) -> np.ndarray:
    """Squared distances of paired points, elementwise, every step in
    ``ft``."""
    dx = px.astype(ft) - rx.astype(ft)
    dy = py.astype(ft) - ry.astype(ft)
    return (dx * dx + dy * dy).astype(ft)


# The spatial joins look only at reference rows in the 3 x 3 grid cells
# around a tweet, the cells being squares of side GRID_SIDE x radius over
# the coordinates rounded to ``ft``.  That finds every pair the exact
# search over all rows accepts.  Let u be ``ft``'s unit roundoff (half its
# epsilon: 2**-24 for float32, 2**-8 for bfloat16) and d the exact
# difference of two rounded coordinates.  The computed dx is d rounded, so
# |d| <= |dx| (1 + u).  Rounding is monotone and the other square is not
# negative, so an accepted pair has round(dx * dx) <= d2 <= r2, hence
# dx * dx <= r2 (1 + u), with r2 = round(round(radius)**2) <= radius**2
# (1 + u)**3.  So |d| <= radius (1 + u)**3, under 1.012 radius even in
# bfloat16; a side of 1.25 radius leaves a fifth of a cell to spare, far
# more than float64's rounding of coordinate / side can take.  Two points
# less than a side apart lie in the same or in neighbouring cells.
GRID_SIDE = 1.25
PAIR_BLOCK = 1 << 22          # candidate pairs compared at a time


def _pairs(t: Cols, ref: Cols, radius: float, ft):
    """Blocks of candidate pairs (tweet index, row index, d2 in ``ft``):
    each tweet against the rows of its 3 x 3 neighbouring cells, in
    ascending tweet order; every pair within ``radius`` is among them."""
    side = GRID_SIDE * radius
    rx = ref["lat"].astype(ft).astype(np.float64)
    ry = ref["lon"].astype(ft).astype(np.float64)
    px = t["lat"].astype(ft).astype(np.float64)
    py = t["lon"].astype(ft).astype(np.float64)
    rcx, rcy = np.floor(rx / side), np.floor(ry / side)
    if rcx.size == 0 or px.size == 0:
        return
    x0, y0 = rcx.min(), rcy.min()
    nx, ny = int(rcx.max() - x0) + 1, int(rcy.max() - y0) + 1
    cell = (rcx - x0).astype(np.int64) * ny + (rcy - y0).astype(np.int64)
    rows = np.argsort(cell, kind="stable")
    offs = np.zeros(nx * ny + 1, np.int64)
    np.cumsum(np.bincount(cell, minlength=nx * ny), out=offs[1:])
    # the tweets' 9 neighbouring cells, clipped to the rows' grid
    tcx = (np.floor(px / side) - x0).astype(np.int64)
    tcy = (np.floor(py / side) - y0).astype(np.int64)
    step = np.array([-1, 0, 1])
    cx = (tcx[:, None] + step[None, :])[:, :, None]
    cy = (tcy[:, None] + step[None, :])[:, None, :]
    ok = (cx >= 0) & (cx < nx) & (cy >= 0) & (cy < ny)
    nb = np.where(ok, cx * ny + cy, 0).reshape(px.size, 9)
    start = offs[nb]
    count = np.where(ok.reshape(px.size, 9), offs[nb + 1] - start, 0)
    per_tweet = np.cumsum(count.sum(1))
    lo = 0
    while lo < px.size:
        base = per_tweet[lo - 1] if lo else 0
        hi = max(lo + 1, int(np.searchsorted(per_tweet, base + PAIR_BLOCK,
                                             side="right")))
        c, s = count[lo:hi].ravel(), start[lo:hi].ravel()
        ti = np.repeat(np.arange(lo, hi).repeat(9), c)
        first = np.cumsum(c) - c
        ri = rows[np.repeat(s - first, c) + np.arange(int(c.sum()))]
        yield ti, ri, _d2(t["lat"][ti], t["lon"][ti], ref["lat"][ri],
                          ref["lon"][ri], ft)
        lo = hi


def _nearest(t: Cols, tabs, table: str, radius: float, k: int, ft
             ) -> np.ndarray:
    """Row index of up to ``k`` nearest rows within ``radius``, nearest
    first and lower row first among equals; -1 past the last."""
    ref = tabs[table]
    out = np.full((t["lat"].shape[0], k), -1, np.int64)
    r2 = ft(radius) * ft(radius)
    for ti, ri, d2 in _pairs(t, ref, radius, ft):
        hit = d2 <= r2
        ti, ri, d2 = ti[hit], ri[hit], d2[hit].astype(np.float64)
        order = np.lexsort((ri, d2, ti))
        ti, ri = ti[order], ri[order]
        rank = np.arange(ti.shape[0]) - np.searchsorted(ti, ti)
        keep = rank < k
        out[ti[keep], rank[keep]] = ri[keep]
    return out


def _within_count(t: Cols, tabs, table: str, radius: float, ft,
                  group: str = "", groups: int = 0) -> np.ndarray:
    ref = tabs[table]
    n = t["lat"].shape[0]
    r2 = ft(radius) * ft(radius)
    g = max(groups, 1)
    out = np.zeros(n * g, np.int64)
    for ti, ri, d2 in _pairs(t, ref, radius, ft):
        hit = d2 <= r2
        ti, ri = ti[hit], ri[hit]
        key = ti * g + ref[group][ri] if groups else ti
        out += np.bincount(key, minlength=n * g)
    out = out.astype(np.int32).reshape(n, g)
    return out if groups else out[:, 0]


def _first_rect(lat, lon, rects: Cols, ft) -> np.ndarray:
    """Index of the first rectangle (row order) holding each point,
    bounds inclusive; -1 where none does."""
    x, y = lat.astype(ft), lon.astype(ft)
    xmin, ymin = rects["xmin"].astype(ft), rects["ymin"].astype(ft)
    xmax, ymax = rects["xmax"].astype(ft), rects["ymax"].astype(ft)
    out = np.full(x.shape[0], -1, np.int64)
    for lo in range(0, x.shape[0], 8192):
        s = slice(lo, min(lo + 8192, x.shape[0]))
        inside = ((x[s, None] >= xmin[None]) & (y[s, None] >= ymin[None])
                  & (x[s, None] <= xmax[None]) & (y[s, None] <= ymax[None]))
        any_ = inside.any(1)
        out[s] = np.where(any_, inside.argmax(1), -1)
    return out


def _lookup(keys: np.ndarray, probe: np.ndarray):
    """(row, found) of each probe key in an ascending key column."""
    pos = np.minimum(np.searchsorted(keys, probe), keys.shape[0] - 1)
    found = keys[pos] == probe
    return pos, found


def udf2(t: Cols, tabs, ft) -> Cols:
    """Red (1) when one of the tweet's words is a sensitive word of its
    country."""
    sw = tabs["sensitive_words"]
    pairs = set(zip(sw["country"].tolist(), sw["word"].tolist()))
    toks = t["text_tokens"]
    flag = np.zeros(toks.shape[0], np.int32)
    for i, (c, row) in enumerate(zip(t["country"].tolist(), toks.tolist())):
        flag[i] = any((c, w) in pairs for w in row if w != 0)
    return {"safety_check_flag": flag}


def q1(t: Cols, tabs, ft) -> Cols:
    sl = tabs["safety_levels"]
    pos, found = _lookup(sl["key"], t["country"].astype(np.int64))
    return {"safety_level": np.where(found, sl["safety_level"][pos],
                                     -1).astype(np.int32)}


def q2(t: Cols, tabs, ft) -> Cols:
    rp = tabs["religious_populations"]
    tot = np.zeros(data.COUNTRY_DOMAIN, np.int64)
    np.add.at(tot, rp["country"], rp["population"].astype(np.int64))
    return {"religious_population": tot[t["country"]]}


def q3(t: Cols, tabs, ft) -> Cols:
    """Per country, the religions of its three most populous rows
    (population descending, row order among equals)."""
    rp = tabs["religious_populations"]
    order = np.lexsort((np.arange(rp["country"].shape[0]),
                        -rp["population"].astype(np.int64), rp["country"]))
    top = np.full((data.COUNTRY_DOMAIN, 3), -1, np.int32)
    c_sorted = rp["country"][order]
    starts = np.searchsorted(c_sorted, np.arange(data.COUNTRY_DOMAIN))
    ends = np.searchsorted(c_sorted, np.arange(data.COUNTRY_DOMAIN),
                           side="right")
    for c in np.unique(rp["country"]):
        rows = order[starts[c]:min(ends[c], starts[c] + 3)]
        top[c, :rows.shape[0]] = rp["religion"][rows]
    return {"largest_religions": top[t["country"]]}


def q4(t: Cols, tabs, ft) -> Cols:
    idx = _nearest(t, tabs, "monuments", Q4_RADIUS, Q4_K, ft)
    keys = tabs["monuments"]["key"]
    return {"nearby_monuments": np.where(idx >= 0, keys[np.maximum(idx, 0)],
                                         -1),
            "nearby_monument_count": _within_count(
                t, tabs, "monuments", Q4_RADIUS, ft).astype(np.int32)}


def q5(t: Cols, tabs, ft) -> Cols:
    rb, sn = tabs["religious_buildings"], tabs["suspicious_names"]
    idx = _nearest(t, tabs, "religious_buildings", Q5_RADIUS, Q5_K, ft)
    safe = np.maximum(idx, 0)
    pos, found = _lookup(sn["key"], t["user_name_hash"])
    return {
        "nearby_facility_counts": _within_count(
            t, tabs, "facilities", Q5_RADIUS, ft, "ftype",
            data.NUM_FACILITY_TYPES),
        "nearby_religious_buildings": np.where(idx >= 0, rb["key"][safe], -1),
        "nearby_building_religions": np.where(idx >= 0, rb["religion"][safe],
                                              -1).astype(np.int32),
        "suspect_threat_level": np.where(found, sn["threat_level"][pos],
                                         -1).astype(np.int32),
        "suspect_religion": np.where(found, sn["religion"][pos],
                                     -1).astype(np.int32),
    }


def q6_state(tabs, ft):
    """Facilities and persons counted per district (the first district
    holding them) and type or ethnicity; income per district."""
    dst = tabs["district_areas"]
    nd = dst["key"].shape[0]
    out = {}
    for table, col, groups in (("facilities", "ftype",
                                data.NUM_FACILITY_TYPES),
                               ("persons", "ethnicity",
                                data.NUM_ETHNICITIES)):
        ref = tabs[table]
        d = _first_rect(ref["lat"], ref["lon"], dst, ft)
        ok = d >= 0
        counts = np.zeros((nd, groups), np.int32)
        np.add.at(counts, (d[ok], ref[col][ok]), 1)
        out[table] = counts
    inc = tabs["average_incomes"]
    pos, found = _lookup(inc["key"], dst["key"])
    out["income"] = np.where(found, inc["income"][pos], 0).astype(np.float32)
    return out


def q6(t: Cols, tabs, ft, state=None) -> Cols:
    st = state if state is not None else q6_state(tabs, ft)
    d = _first_rect(t["lat"], t["lon"], tabs["district_areas"], ft)
    ok = d >= 0
    safe = np.maximum(d, 0)
    return {
        "district": d.astype(np.int32),
        "area_avg_income": np.where(ok, st["income"][safe],
                                    0).astype(np.float32),
        "area_facility_counts": np.where(ok[:, None],
                                         st["facilities"][safe], 0),
        "area_ethnicity_dist": np.where(ok[:, None], st["persons"][safe], 0),
    }


def q7(t: Cols, tabs, ft) -> Cols:
    """Religions of the three nearest religious buildings, and for each
    the attacks on that religion in the two months before the tweet."""
    rb, ev = tabs["religious_buildings"], tabs["attack_events"]
    idx = _nearest(t, tabs, "religious_buildings", Q7_RADIUS, Q7_K, ft)
    rels = np.where(idx >= 0, rb["religion"][np.maximum(idx, 0)], -1)
    # one search over the events sorted by (religion, time): each key is
    # religion * span + (time - base), with base and span wide enough
    # that every tweet's window lies inside its religion's block
    ts = t["created_at"].astype(np.int64)
    et = ev["time"].astype(np.int64)
    base = min(int(et.min(initial=0)), int(ts.min(initial=0)) - TWO_MONTHS)
    span = max(int(et.max(initial=0)), int(ts.max(initial=0))) - base + 1
    keys = np.sort(ev["religion"].astype(np.int64) * span + (et - base))
    r = rels.astype(np.int64)
    at = r * span + (ts[:, None] - base)
    counts = (np.searchsorted(keys, at, side="left")
              - np.searchsorted(keys, at - TWO_MONTHS, side="right"))
    counts = np.where(rels >= 0, counts, 0).astype(np.int32)
    return {"nearby_religions": rels.astype(np.int32),
            "religion_attack_counts": counts}


UDFS: Dict[str, Callable] = {"udf2": udf2, "q1": q1, "q2": q2, "q3": q3,
                             "q4": q4, "q5": q5, "q6": q6, "q7": q7}


def enrich(udfs, t: Cols, tabs, precision: str = "float32") -> Cols:
    """Every enriched column the plan ``udfs`` adds to the tweets ``t``."""
    ft = FLOATS[precision]
    out: Cols = {}
    for name in udfs:
        out.update(UDFS[name](t, tabs, ft))
    return out
