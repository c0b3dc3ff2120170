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
CHUNK = 256


def _chunks(n: int):
    for lo in range(0, n, CHUNK):
        yield slice(lo, min(lo + CHUNK, n))


def _d2(px, py, rx, ry, ft) -> np.ndarray:
    """(b, R) squared distances, every step in ``ft``."""
    dx = px.astype(ft)[:, None] - rx.astype(ft)[None, :]
    dy = py.astype(ft)[:, None] - ry.astype(ft)[None, :]
    return (dx * dx + dy * dy).astype(ft)


def _nearest(t: Cols, tabs, table: str, radius: float, k: int, ft
             ) -> np.ndarray:
    """Row index of up to ``k`` nearest rows within ``radius``, nearest
    first and lower row first among equals; -1 past the last."""
    ref = tabs[table]
    n = t["lat"].shape[0]
    out = np.full((n, k), -1, np.int64)
    r2 = ft(radius) * ft(radius)
    kk = min(k, ref["lat"].shape[0])
    for s in _chunks(n):
        d2 = _d2(t["lat"][s], t["lon"][s], ref["lat"], ref["lon"],
                 ft).astype(np.float64)
        # rows at or below each row's k-th smallest distance, then
        # ordered by (distance, row) and cut to k
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


def _within_count(t: Cols, tabs, table: str, radius: float, ft,
                  group: str = "", groups: int = 0) -> np.ndarray:
    ref = tabs[table]
    n = t["lat"].shape[0]
    r2 = ft(radius) * ft(radius)
    out = np.zeros((n, max(groups, 1)), np.int32)
    for s in _chunks(n):
        hit = _d2(t["lat"][s], t["lon"][s], ref["lat"], ref["lon"], ft) <= r2
        if groups:
            r, c = np.nonzero(hit)
            b = hit.shape[0]
            out[s] = np.bincount(r * groups + ref[group][c],
                                 minlength=b * groups).reshape(b, groups)
        else:
            out[s, 0] = hit.sum(1)
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
    order = np.lexsort((ev["time"], ev["religion"]))
    er, et = ev["religion"][order], ev["time"][order]
    counts = np.zeros(rels.shape, np.int32)
    ts = t["created_at"]
    for j in range(rels.shape[1]):
        r = rels[:, j]
        lo_r = np.searchsorted(er, r, side="left")
        hi_r = np.searchsorted(er, r, side="right")
        for i in np.nonzero(r >= 0)[0]:
            seg = et[lo_r[i]:hi_r[i]]
            counts[i, j] = (np.searchsorted(seg, ts[i], side="left")
                            - np.searchsorted(seg, ts[i] - TWO_MONTHS,
                                              side="right"))
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
