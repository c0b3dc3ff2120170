"""Smoke run of the enrichment feed on one TPU chip.

Drives the paper's deployment once through the entry points a user calls
(``pipeline(...)`` -> ``FeedManager.submit`` -> column store -> ``query()``)
at the paper's reference-table cardinalities (about 2.28M rows in 11
tables) and its 16X batch of 6,720 tweets, and checks every result
against a plain reference:

  feed     SyntheticAdapter -> parse -> UDF2 > Q1..Q7 fused -> store, with
           reference-table upserts mid-feed (snapshot re-upload, Model-2
           state rebuild); stored == total
  query    group-by count / sum / topk over the store == numpy over the
           stored rows
  kernels  each Pallas kernel once at deployment size == its jnp reference
  routing  every kernel took the kernel path during the feed or the query

Off a TPU it exits non-zero before any work.  The last line of stdout is
``{"ok": true, "device": {...}}``, printed only when every phase passed.
This is a smoke run, not a benchmark: its times are labelled as such.

Run from the root of a checkout:  python chip_smoke.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import (FeedManager, RefStore, SyntheticAdapter,  # noqa: E402
                        agg, pipeline)
from repro.core.computing import ComputingRunner  # noqa: E402
from repro.core.enrich import dispatch  # noqa: E402
from repro.core.enrich import queries as Q  # noqa: E402
from repro.core.refdata import KEY_SENTINEL  # noqa: E402
from repro.kernels.hash_probe import ops as hp_ops  # noqa: E402
from repro.kernels.hash_probe import ref as hp_ref  # noqa: E402
from repro.kernels.segment_reduce import ops as sr_ops  # noqa: E402
from repro.kernels.segment_reduce import ref as sr_ref  # noqa: E402
from repro.kernels.segment_topk import ops as st_ops  # noqa: E402
from repro.kernels.segment_topk import ref as st_ref  # noqa: E402
from repro.kernels.spatial_join import ops as sj_ops  # noqa: E402
from repro.kernels.spatial_join import ref as sj_ref  # noqa: E402

BATCH_16X = 6720          # the paper's largest batch (§8.2)
BATCHES = 20              # a few windows: enough to cross the upsert
SEED = 0
UDFS = (Q.UDF2, Q.Q1, Q.Q2, Q.Q3, Q.Q4, Q.Q5, Q.Q6, Q.Q7)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu() -> dict:
    """The device this run is about, or SystemExit: nothing falls back."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (backend "
                         f"{backend!r}); refusing to run")
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


# ---------------------------------------------------------------------------
# phases (tests call these at a tiny size)
# ---------------------------------------------------------------------------

def build_refs(scale: float, seed: int) -> RefStore:
    store = RefStore()
    Q.make_reference_tables(store, scale=scale, seed=seed)
    return store


def run_feed(store: RefStore, total: int, batch: int, seed: int):
    """The fused UDF2 > Q1..Q7 plan into the column store.  A tee sink
    upserts existing keys of two reference tables at the second batch, so
    later batches are enriched against new snapshot versions (re-upload +
    Model-2 state rebuild).  Returns (handle, stats)."""
    seen = [0]
    upserts = []

    def upsert_mid_feed(b):
        seen[0] += 1
        if seen[0] != 2:
            return
        for name, col in (("religious_populations", "population"),
                          ("safety_levels", "safety_level")):
            t = store[name]
            snap = t.snapshot()
            keys = snap.arrays["key"][:min(snap.size, 64)]
            vals = snap.arrays[col][:keys.shape[0]] + 1
            t.upsert(keys, **{col: vals})
            upserts.append((name, t.snapshot().version))

    p = (pipeline(SyntheticAdapter(total=total, frame_size=batch, seed=seed),
                  "chip_smoke")
         .parse(batch_size=batch)
         # one compiled batch shape: coalesced frames would pad to new
         # power-of-two buckets, each a full recompile of the fused chain
         .options(coalesce_rows=0))
    for u in UDFS:
        p = p.enrich(u)
    p = p.tee(upsert_mid_feed, name="upsert_mid_feed").store()

    h = FeedManager(store).submit(p)
    stats = h.join(timeout=1200)
    assert upserts, "the mid-feed reference upsert never ran"
    assert stats.stored == total, (stats.stored, total)
    log(f"feed: stored == total == {total} "
        f"({stats.computing.invocations} batches of {batch}; "
        f"upserts {upserts}; state builds "
        f"{stats.computing.state_builds})")
    return h, stats


def stored_rows(h, cols) -> dict:
    """Every stored row, one version per id (no repair: ids are unique)."""
    chunks = list(h.storage.scan())
    out = {c: np.concatenate([ch[c] for ch in chunks]) for c in cols}
    valid = np.concatenate([ch["valid"] for ch in chunks])
    out = {c: v[valid] for c, v in out.items()}
    ids = out["id"]
    assert np.unique(ids).shape[0] == ids.shape[0], "duplicate stored ids"
    return out


def check_query(h, k: int = 3) -> dict:
    """Group-by count / sum / topk through the query subsystem, checked
    against numpy over the stored rows.  topk ranks an int32 column so it
    can take the segment_topk kernel; ties may pick any tied row, so the
    check compares the ranked values and that each id belongs to its
    group and carries its value."""
    res = (h.query().group_by("country")
           .agg(n=agg.count(), s=agg.sum("safety_level"),
                top=agg.topk("nearby_monument_count", k=k, payload="id"))
           .execute())
    rows = stored_rows(h, ("id", "country", "safety_level",
                           "nearby_monument_count"))
    keys = np.unique(rows["country"])
    np.testing.assert_array_equal(res["country"], keys)
    by_id = {int(i): (int(c), int(v)) for i, c, v in zip(
        rows["id"], rows["country"], rows["nearby_monument_count"])}
    for gi, key in enumerate(keys):
        m = rows["country"] == key
        assert res["n"][gi] == m.sum(), (key, res["n"][gi], m.sum())
        assert res["s"][gi] == rows["safety_level"][m].astype(
            np.int64).sum(), key
        want = np.sort(rows["nearby_monument_count"][m])[::-1][:k]
        got_ids = [int(i) for i in res["top"][gi] if i >= 0]
        assert len(set(got_ids)) == len(got_ids) == want.shape[0], key
        got = [by_id[i] for i in got_ids]
        assert all(c == key for c, _ in got), key
        assert [v for _, v in got] == want.tolist(), (key, got, want)
    log(f"query: equals numpy over {rows['id'].shape[0]} stored rows "
        f"({keys.shape[0]} groups; count, sum, top-{k}; "
        f"{res.stats.agg_kernel_dispatches} kernel dispatches)")
    return {"groups": int(keys.shape[0])}


def check_kernels(store: RefStore, probe_rows: int, topk_rows: int,
                  topk_segments: int, topk_k: int, seed: int) -> dict:
    """Each kernel once at deployment size against its jnp reference."""
    rng = np.random.default_rng(seed)
    out = {}

    snap = store["suspicious_names"].snapshot()
    keys = jnp.asarray(snap.arrays["key"])
    live = snap.arrays["key"][:snap.size]
    probe = np.where(rng.random(probe_rows) < 0.5,
                     rng.choice(live, probe_rows),
                     rng.integers(0, 2**62, probe_rows)).astype(np.int64)
    probe = jnp.asarray(probe)
    got = hp_ops.sorted_probe(probe, keys, use_pallas=True)
    want = hp_ref.sorted_probe(probe, keys)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    out["hash_probe"] = f"B={probe_rows} R={keys.shape[0]}"

    snap = store["monuments"].snapshot()
    rx, ry = (jnp.asarray(snap.arrays[c]) for c in ("lat", "lon"))
    valid = jnp.asarray(snap.arrays["key"] != KEY_SENTINEL)
    px = jnp.asarray(rng.uniform(-60, 60, probe_rows).astype(np.float32))
    py = jnp.asarray(rng.uniform(-180, 180, probe_rows).astype(np.float32))
    got = sj_ops.radius_join(px, py, rx, ry, Q.Q4_RADIUS, Q.Q4_K, valid,
                             use_pallas=True)
    want = sj_ref.radius_join(px, py, rx, ry, Q.Q4_RADIUS, Q.Q4_K, valid)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    out["spatial_join"] = f"B={probe_rows} R={rx.shape[0]} k={Q.Q4_K}"

    # Q6's ethnicity group-by: persons x (district snapshot rows as the
    # computing job trims them) x ethnicities, plus the overflow segment
    per = store["persons"].snapshot()
    dst = store["district_areas"].snapshot()
    qt = ComputingRunner.TRIM_QUANTUM
    nd = min(dst.capacity, -(-dst.size // qt) * qt)
    nseg = nd * Q.NUM_ETHNICITIES + 1
    rows = dispatch.bucket_rows(per.capacity)
    seg = jnp.asarray(rng.integers(0, nseg + 1, rows).astype(np.int32))
    vals = jnp.asarray(rng.integers(-1000, 1000, rows).astype(np.int32))
    got = sr_ops.segment_sum(vals, seg, nseg, use_pallas=True)
    want = sr_ref.segment_sum(vals, seg, nseg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    out["segment_sum"] = f"R={rows} S={nseg}"

    seg = jnp.asarray(rng.integers(0, topk_segments, topk_rows
                                   ).astype(np.int32))
    vals = jnp.asarray(rng.integers(0, 1000, topk_rows).astype(np.int32))
    got = st_ops.segment_topk_idx(vals, seg, topk_segments, topk_k,
                                  use_pallas=True)
    want = st_ref.segment_topk_idx(vals, seg, topk_segments, topk_k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    out["segment_topk"] = f"R={topk_rows} S={topk_segments} k={topk_k}"

    for name, size in out.items():
        log(f"kernel {name}: equals its jnp reference at {size}")
    return out


def check_routing() -> dict:
    """Fail unless every kernel took the kernel path: bucket_stats()
    records only kernel-path calls of the join ops, path_stats() the
    route of every segment_* call."""
    buckets, paths = dispatch.bucket_stats(), dispatch.path_stats()
    log(f"dispatch bucket_stats: {sorted(buckets.items())}")
    log(f"dispatch path_stats: {sorted(paths.items())}")
    counts = {
        "sorted_join": sum(n for (op, _), n in buckets.items()
                           if op == "sorted_join"),
        "radius_topk": sum(n for (op, _), n in buckets.items()
                           if op == "radius_topk"),
        "segment_sum": paths.get(("segment_sum", "kernel"), 0),
        "segment_topk": paths.get(("segment_topk", "kernel"), 0),
    }
    missing = [op for op, n in counts.items() if n == 0]
    assert not missing, f"no kernel-path dispatch for {missing}"
    log(f"routing: kernel-path dispatches {counts}")
    return counts


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main() -> int:
    device = require_tpu()
    cache = compile_cache.enable()
    log(f"device {device}; compile cache {cache}")
    dispatch.reset_bucket_stats()
    dispatch.reset_path_stats()

    t0 = time.perf_counter()
    store = build_refs(scale=1.0, seed=SEED)
    nref = sum(len(store[n]) for n in Q.PAPER_CARDINALITIES)
    log(f"set-up: {nref} reference rows in "
        f"{time.perf_counter() - t0}s")

    total = BATCH_16X * BATCHES
    t0 = time.perf_counter()
    h, stats = run_feed(store, total, BATCH_16X, SEED)
    feed_s = time.perf_counter() - t0
    pre = h.manager.predeploy
    log(f"smoke timing (not a benchmark): feed wall {feed_s}s incl. "
        f"{pre.compiles} predeploy compiles taking "
        f"compile_s={pre.compile_s}")

    t0 = time.perf_counter()
    check_query(h)
    log(f"smoke timing (not a benchmark): query wall "
        f"{time.perf_counter() - t0}s incl. compiles")

    t0 = time.perf_counter()
    check_kernels(store, probe_rows=dispatch.bucket_rows(BATCH_16X),
                  topk_rows=1 << 18, topk_segments=2048, topk_k=16,
                  seed=SEED)
    log(f"smoke timing (not a benchmark): kernel checks wall "
        f"{time.perf_counter() - t0}s incl. compiles")

    check_routing()
    mem = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use={mem.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
