"""What decides ``correct``: the stored records against what the
generator sent and against the plain reference (``bench/reference.py``).

Four numbers, each with its limit:

  lost          tweets sent and never stored                       limit 0
  duplicated    stored copies beyond the first of a tweet          limit 0
  raw_bad       stored tweets whose parsed columns differ from
                what was sent                                      limit 0
  enriched_bad  compared tweets with any enriched column off the
                reference                                          limit 0

Each is exact, so each limit is 0.  Every stored tweet is compared.  The
control puts the reference in the program's place, one step below the
configuration's stated precision (``"control": "bfloat16"``), or breaks
its delivery guarantee where it states no precision that the plan can
round (``"duplicate_frame"``).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import data, reference

Cols = Dict[str, np.ndarray]
RAW = ("id", "country", "lat", "lon", "created_at", "user_name_hash",
       "text_tokens")
LIMITS = {"lost": 0, "duplicated": 0, "raw_bad": 0, "enriched_bad": 0}


def _rows_equal(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    a, b = np.asarray(a), np.asarray(b)
    eq = a == b
    return eq.reshape(eq.shape[0], -1).all(1)


def compare(stored: Cols, offered: int, seed: int, config: dict,
            tables, control: Optional[str] = None
            ) -> Tuple[Dict[str, Dict[str, float]], Dict[str, int]]:
    """``stored``: every stored row (columns, one row per stored copy).
    ``offered``: tweets sent, ids [0, offered).  Returns the compared
    numbers with their limits, and mismatches per enriched column."""
    ids = np.asarray(stored["id"], np.int64)
    if control == "duplicate_frame":
        last = slice(max(0, ids.shape[0] - config["batch_size"]), None)
        stored = {k: np.concatenate([v, v[last]]) for k, v in stored.items()}
        ids = stored["id"]
    inside = (ids >= 0) & (ids < offered)
    counts = np.bincount(ids[inside], minlength=offered)
    lost = int((counts == 0).sum())
    duplicated = int(np.maximum(counts - 1, 0).sum())
    order = np.argsort(ids, kind="stable")
    first = order[np.r_[True, ids[order][1:] != ids[order][:-1]]]
    first = first[inside[first]]
    want = data.parsed_columns(data.tweets(seed, 0, offered))
    uid = ids[first]
    raw_ok = np.ones(uid.shape[0], bool)
    for c in RAW:
        raw_ok &= _rows_equal(np.asarray(stored[c])[first], want[c][uid])
    raw_bad = int((~raw_ok).sum()) + int((~inside).sum())

    rows, cids = first, uid
    t = {c: want[c][cids] for c in RAW}
    udfs = config["plan"]["udfs"]
    ref = reference.enrich(udfs, t, tables)
    if control == "bfloat16":
        got = reference.enrich(udfs, t, tables, "bfloat16")
    else:
        got = {c: np.asarray(stored[c])[rows] for c in ref}
    bad = np.zeros(cids.shape[0], bool)
    per_col: Dict[str, int] = {}
    for c, v in ref.items():
        col_bad = ~_rows_equal(got[c], v)
        per_col[c] = int(col_bad.sum())
        bad |= col_bad
    numbers = {"lost": lost, "duplicated": duplicated, "raw_bad": raw_bad,
               "enriched_bad": int(bad.sum())}
    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in numbers.items()}
    per_col["compared"] = int(cids.shape[0])
    return checks, per_col


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def lines(checks: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"check {k}: {c['value']} (limit {c['limit']})"
            for k, c in checks.items()]
