"""The benchmark's own data: tweets and the eleven reference tables, made
from a seed with numpy alone.

Kept apart from the program's generators (``repro.core.records``,
``repro.core.enrich.queries.make_reference_tables``) so that no change to
the program can move the yardstick.  Shapes follow the paper's §8
workload as the program reads it: tweets with a country code of 256,
a position in [-60, 60] x [-180, 180], a creation time, an author and
4 to 15 words; the appendix cardinalities for the tables.  Author names
and words are hashed with the same 63-bit FNV-1a that the program's
parser applies, so that joins on them find their rows.

The generator process imports this module, so it imports no JAX.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

TEXT_TOKENS = 16
NUM_COUNTRIES = 256
NUM_USERS = 1_000_000
WORDS = [f"w{i}" for i in range(4096)] + ["bomb", "alert", "match", "storm"]
BLOCK = 8192                  # tweets drawn per seeded block

COUNTRY_DOMAIN = 50_000
NUM_RELIGIONS = 64
NUM_FACILITY_TYPES = 16
NUM_ETHNICITIES = 32

_FNV_BASIS = np.uint64(14695981039346656037)
_FNV_PRIME = np.uint64(1099511628211)
_MASK63 = np.uint64(0x7FFFFFFFFFFFFFFF)


def fnv63(strings: List[str]) -> np.ndarray:
    """63-bit FNV-1a of each string, as int64, one byte position at a
    time over all strings together."""
    raw = [s.encode() for s in strings]
    n = len(raw)
    width = max((len(b) for b in raw), default=0)
    buf = np.zeros((n, max(width, 1)), np.uint8)
    lens = np.fromiter((len(b) for b in raw), np.int64, n)
    flat = np.frombuffer(b"".join(raw), np.uint8)
    rows = np.repeat(np.arange(n), lens)
    cols = np.arange(flat.shape[0]) - np.repeat(np.cumsum(lens) - lens, lens)
    buf[rows, cols] = flat
    h = np.full(n, _FNV_BASIS, np.uint64)
    with np.errstate(over="ignore"):
        for j in range(width):
            step = ((h ^ buf[:, j].astype(np.uint64)) * _FNV_PRIME) & _MASK63
            h = np.where(j < lens, step, h)
    return (h & _MASK63).astype(np.int64)


WORD_HASH = fnv63(WORDS)


# ---------------------------------------------------------------------------
# tweets
# ---------------------------------------------------------------------------

def tweet_block(seed: int, block: int) -> Dict[str, np.ndarray]:
    """The ``block``-th run of ``BLOCK`` tweets of a seed.  Positions are
    whole ten-thousandths of a degree, so the JSON text and the parsed
    float32 agree exactly.  ``words`` holds vocabulary indices, -1 past a
    tweet's last word."""
    rng = np.random.default_rng([int(seed), int(block), 1])
    n = BLOCK
    nwords = rng.integers(4, TEXT_TOKENS, n)
    words = rng.integers(0, len(WORDS), (n, TEXT_TOKENS - 1))
    words = np.where(np.arange(TEXT_TOKENS - 1)[None, :] < nwords[:, None],
                     words, -1)
    return {
        "id": np.arange(block * n, (block + 1) * n, dtype=np.int64),
        "country": rng.integers(0, NUM_COUNTRIES, n).astype(np.int32),
        "lat_e4": rng.integers(-600_000, 600_001, n),
        "lon_e4": rng.integers(-1_800_000, 1_800_001, n),
        "created_at": rng.integers(1_500_000_000, 1_600_000_000, n),
        "user": rng.integers(0, NUM_USERS, n),
        "words": words,
    }


def tweets(seed: int, lo: int, hi: int) -> Dict[str, np.ndarray]:
    """Tweets with ids in [lo, hi)."""
    if hi <= lo:
        return {k: v[:0] for k, v in tweet_block(seed, 0).items()}
    blocks = [tweet_block(seed, b)
              for b in range(lo // BLOCK, (hi - 1) // BLOCK + 1)]
    cat = {k: np.concatenate([b[k] for b in blocks]) for k in blocks[0]}
    off = lo - (lo // BLOCK) * BLOCK
    return {k: v[off:off + hi - lo] for k, v in cat.items()}


def json_lines(t: Dict[str, np.ndarray]) -> List[bytes]:
    """Newline-terminated JSON records, as the socket feed receives them."""
    out = []
    ids, ctry = t["id"].tolist(), t["country"].tolist()
    lat, lon = (t["lat_e4"] / 1e4).tolist(), (t["lon_e4"] / 1e4).tolist()
    ts, users = t["created_at"].tolist(), t["user"].tolist()
    for i, row in enumerate(t["words"].tolist()):
        text = " ".join(WORDS[w] for w in row if w >= 0)
        out.append(b'{"id":%d,"country":%d,"lat":%.4f,"lon":%.4f,'
                   b'"created_at":%d,"user":"user%d","text":"%s"}\n'
                   % (ids[i], ctry[i], lat[i], lon[i], ts[i], users[i],
                      text.encode()))
    return out


def user_hashes(users: np.ndarray) -> np.ndarray:
    return fnv63([f"user{u}" for u in users.tolist()])


def parsed_columns(t: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """What a correct parser makes of ``json_lines(t)``: the program's
    tweet schema."""
    toks = np.zeros((t["id"].shape[0], TEXT_TOKENS), np.int64)
    w = t["words"]
    toks[:, :w.shape[1]] = np.where(w >= 0, WORD_HASH[np.maximum(w, 0)], 0)
    return {
        "id": t["id"].astype(np.int64),
        "country": t["country"].astype(np.int32),
        "lat": (t["lat_e4"] / 1e4).astype(np.float32),
        "lon": (t["lon_e4"] / 1e4).astype(np.float32),
        "created_at": t["created_at"].astype(np.int64),
        "user_name_hash": user_hashes(t["user"]),
        "text_tokens": toks,
    }


# ---------------------------------------------------------------------------
# reference tables
# ---------------------------------------------------------------------------

SCHEMAS = {
    "safety_levels": {"safety_level": np.int32},
    "religious_populations": {"country": np.int32, "religion": np.int32,
                              "population": np.int32},
    "monuments": {"lat": np.float32, "lon": np.float32},
    "sensitive_words": {"country": np.int32, "word": np.int64},
    "religious_buildings": {"lat": np.float32, "lon": np.float32,
                            "religion": np.int32},
    "facilities": {"lat": np.float32, "lon": np.float32,
                   "ftype": np.int32},
    "suspicious_names": {"religion": np.int32, "threat_level": np.int32},
    "district_areas": {"xmin": np.float32, "ymin": np.float32,
                       "xmax": np.float32, "ymax": np.float32},
    "average_incomes": {"income": np.float32},
    "persons": {"lat": np.float32, "lon": np.float32,
                "ethnicity": np.int32},
    "attack_events": {"time": np.int64, "religion": np.int32},
}


def _table(name: str, m: int, rng: np.random.Generator
           ) -> Dict[str, np.ndarray]:
    def u(lo, hi):
        return rng.uniform(lo, hi, m).astype(np.float32)

    def i(lo, hi):
        return rng.integers(lo, hi, m).astype(np.int32)

    key = np.arange(m, dtype=np.int64)
    if name == "safety_levels":
        return {"key": key, "safety_level": i(0, 5)}
    if name == "religious_populations":
        return {"key": key, "country": i(0, NUM_COUNTRIES),
                "religion": i(0, NUM_RELIGIONS),
                "population": i(1_000, 10_000_000)}
    if name == "monuments":
        return {"key": key, "lat": u(-60, 60), "lon": u(-180, 180)}
    if name == "sensitive_words":
        return {"key": key, "country": i(0, NUM_COUNTRIES),
                "word": WORD_HASH[rng.integers(0, len(WORDS), m)]}
    if name == "religious_buildings":
        return {"key": key, "lat": u(-60, 60), "lon": u(-180, 180),
                "religion": i(0, NUM_RELIGIONS)}
    if name == "facilities":
        return {"key": key, "lat": u(-60, 60), "lon": u(-180, 180),
                "ftype": i(0, NUM_FACILITY_TYPES)}
    if name == "suspicious_names":
        users = rng.choice(NUM_USERS, m, replace=False)
        return {"key": user_hashes(users), "religion": i(0, NUM_RELIGIONS),
                "threat_level": i(1, 11)}
    if name == "district_areas":
        cx, cy = u(-58, 58), u(-170, 170)
        w, h = u(1.0, 8.0), u(1.0, 8.0)
        return {"key": key, "xmin": cx - w, "ymin": cy - h,
                "xmax": cx + w, "ymax": cy + h}
    if name == "average_incomes":
        return {"key": key, "income": u(20_000, 120_000)}
    if name == "persons":
        return {"key": key, "lat": u(-60, 60), "lon": u(-180, 180),
                "ethnicity": i(0, NUM_ETHNICITIES)}
    if name == "attack_events":
        return {"key": key,
                "time": rng.integers(1_500_000_000, 1_600_000_000, m
                                     ).astype(np.int64),
                "religion": i(0, NUM_RELIGIONS)}
    raise KeyError(f"no generator for reference table {name!r}")


def reference_tables(cardinalities: Dict[str, int], seed: int
                     ) -> Dict[str, Dict[str, np.ndarray]]:
    """Every table of ``cardinalities``, each from its own stream of the
    seed, rows in ascending key order."""
    out = {}
    for idx, name in enumerate(sorted(cardinalities)):
        rng = np.random.default_rng([int(seed), idx, 2])
        t = _table(name, int(cardinalities[name]), rng)
        order = np.argsort(t["key"], kind="stable")
        out[name] = {k: v[order] for k, v in t.items()}
    return out
