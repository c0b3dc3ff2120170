"""Relational enrichment operators, TPU-adapted (pure jnp, jit-able, static
shapes).  These are the building blocks of the paper's UDF workload:

  hash join      -> ``sorted_join``: binary-search probe of the snapshot's
                    sorted key column (no pointer-chase hash table; O(log R)
                    regular accesses, fully vectorized on the VPU)
  group-by       -> ``segment_sum`` / ``segment_count`` (optionally lowered
                    to the one-hot x matmul MXU kernel, kernels/segment_reduce)
  order-by/top-k -> ``segment_topk``: one composite-key sort, no S x R blowup
  spatial join   -> ``radius_count`` / ``radius_topk``: tiled pairwise
                    distances, dx*dx + dy*dy elementwise in float32
                    (kernels/spatial_join is the Pallas version)
  contains()     -> ``contains_any``: hashed-token membership (DESIGN.md §2)

Invalid reference rows are key-sentinel padded, so every operator is correct
on fixed-capacity snapshots regardless of fill level.

Routing: the hot-path operators (``sorted_join``, ``radius_count``,
``radius_topk``, ``segment_sum``, ``segment_count``, ``segment_topk``) are
thin wrappers over the kernel-dispatch layer (dispatch.py), which picks the
Pallas kernel or the ``_*_ref`` jnp bodies kept here.  The ``_*_ref``
functions ARE the former implementations — dispatch falls back to them for
tiny batches, CPU-only runs, or mode="reference".
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.refdata import KEY_SENTINEL

Array = jax.Array

_SPATIAL_CHUNK = 512   # probe-row block for distance tiles (see kernels/)


# ---------------------------------------------------------------------------
# joins
# ---------------------------------------------------------------------------

def sorted_join(probe: Array, ref_keys: Array) -> Tuple[Array, Array]:
    """Equi-join probe: for each probe key, the index of its match in the
    (ascending, sentinel-padded) reference key column and a found flag.
    probe: (B,) int64; ref_keys: (R,) int64 sorted.
    Returns (idx (B,) int32 [-1 when absent], found (B,) bool)."""
    from repro.core.enrich import dispatch
    return dispatch.sorted_join(probe, ref_keys)


def _sorted_join_ref(probe: Array, ref_keys: Array) -> Tuple[Array, Array]:
    idx = jnp.searchsorted(ref_keys, probe)
    idx = jnp.minimum(idx, ref_keys.shape[0] - 1)
    found = (ref_keys[idx] == probe) & (probe != KEY_SENTINEL)
    return jnp.where(found, idx, -1).astype(jnp.int32), found


def gather_col(col: Array, idx: Array, found: Array, fill=0) -> Array:
    """Payload gather for an (idx, found) join result."""
    out = jnp.take(col, idx, axis=0)
    fill_arr = jnp.asarray(fill, out.dtype)
    return jnp.where(
        found.reshape(found.shape + (1,) * (out.ndim - 1)), out, fill_arr)


# ---------------------------------------------------------------------------
# group-by aggregation
# ---------------------------------------------------------------------------

def segment_sum(values: Array, seg: Array, num_segments: int,
                valid: Optional[Array] = None) -> Array:
    from repro.core.enrich import dispatch
    return dispatch.segment_sum(values, seg, num_segments, valid)


def _segment_sum_ref(values: Array, seg: Array, num_segments: int,
                     valid: Optional[Array] = None) -> Array:
    if valid is not None:
        values = jnp.where(valid, values, 0)
    return jax.ops.segment_sum(values, seg, num_segments=num_segments)


def segment_count(seg: Array, num_segments: int,
                  valid: Optional[Array] = None) -> Array:
    from repro.core.enrich import dispatch
    return dispatch.segment_count(seg, num_segments, valid)


def segment_topk(values: Array, seg: Array, payload: Array,
                 num_segments: int, k: int,
                 valid: Optional[Array] = None) -> Tuple[Array, Array]:
    from repro.core.enrich import dispatch
    return dispatch.segment_topk(values, seg, payload, num_segments, k,
                                 valid)


def _segment_topk_ref(values: Array, seg: Array, payload: Array,
                      num_segments: int, k: int,
                      valid: Optional[Array] = None) -> Tuple[Array, Array]:
    """Per-segment top-k by ``values`` (descending), returning the payload.

    One composite-key argsort — O(R log R), never materializes (S, R).
    values: (R,) non-negative int32; seg: (R,) int32; payload: (R,) any.
    Returns (payload (S, k) with -1 fill, values (S, k) with 0 fill)."""
    r = values.shape[0]
    vmax = jnp.int64(1) << 31
    v = jnp.clip(values.astype(jnp.int64), 0, vmax - 1)
    segi = seg.astype(jnp.int64)
    if valid is not None:
        # invalid rows sort to a virtual overflow segment
        segi = jnp.where(valid, segi, num_segments)
    composite = segi * vmax + (vmax - 1 - v)   # asc seg, desc value
    order = jnp.argsort(composite)
    sseg = segi[order]
    sval = values[order]
    spay = payload[order]
    starts = jnp.searchsorted(sseg, jnp.arange(num_segments + 1,
                                               dtype=jnp.int64))
    pos = jnp.arange(r) - starts[jnp.clip(sseg, 0, num_segments)]
    keep = (pos < k) & (sseg < num_segments)
    slot = jnp.where(keep, sseg * k + pos, num_segments * k)
    pay_out = jnp.full((num_segments * k + 1,), -1, payload.dtype)
    val_out = jnp.zeros((num_segments * k + 1,), values.dtype)
    pay_out = pay_out.at[slot].set(jnp.where(keep, spay, -1), mode="drop")
    val_out = val_out.at[slot].set(jnp.where(keep, sval, 0), mode="drop")
    return (pay_out[:-1].reshape(num_segments, k),
            val_out[:-1].reshape(num_segments, k))


# ---------------------------------------------------------------------------
# text membership (the ``contains`` adaptation)
# ---------------------------------------------------------------------------

def contains_any(text_tokens: Array, keywords: Array,
                 kw_valid: Optional[Array] = None) -> Array:
    """(B, T) int64 token hashes vs (K,) keyword hashes -> (B,) bool."""
    eq = text_tokens[:, :, None] == keywords[None, None, :]
    if kw_valid is not None:
        eq &= kw_valid[None, None, :]
    eq &= text_tokens[:, :, None] != 0
    return jnp.any(eq, axis=(1, 2))


# ---------------------------------------------------------------------------
# spatial operators
# ---------------------------------------------------------------------------

def country_keyword_match(text_tokens: Array, country: Array,
                          ref_country: Array, ref_word: Array,
                          ref_valid: Optional[Array] = None,
                          chunk: int = 256) -> Array:
    """SQL++ UDF 2 (tweetSafetyCheck): EXISTS(SELECT s FROM SensitiveWords s
    WHERE t.country = s.country AND contains(t.text, s.word)).
    text_tokens: (B, T); country: (B,); ref_country/ref_word: (R,).
    Returns (B,) bool.  Chunked over probe rows like the spatial tiles."""
    def one(args):
        toks, ctry = args
        cmatch = ctry[:, None] == ref_country[None, :]           # (b, R)
        wmatch = jnp.any(
            (toks[:, :, None] == ref_word[None, None, :])
            & (toks[:, :, None] != 0), axis=1)                   # (b, R)
        hit = cmatch & wmatch
        if ref_valid is not None:
            hit &= ref_valid[None, :]
        return jnp.any(hit, axis=1)

    b = text_tokens.shape[0]
    if b <= chunk:
        return one((text_tokens, country))
    pad = (-b) % chunk
    toks = jnp.pad(text_tokens, ((0, pad), (0, 0)))
    ctry = jnp.pad(country, (0, pad))
    out = jax.lax.map(one, (toks.reshape(-1, chunk, text_tokens.shape[1]),
                            ctry.reshape(-1, chunk)))
    return out.reshape(-1)[:b]


def pairwise_dist2(points: Array, refs: Array) -> Array:
    """Squared euclidean distance matrix, elementwise in float32:
    ``dx * dx + dy * dy`` on each (point, ref) pair, the Pallas kernel's
    own arithmetic (kernels/spatial_join).  The MXU identity
    |p|^2 + |r|^2 - 2 p.r is not used: at |p|^2 ~ 36,000 it cancels away
    the low bits that decide a pair at the radius, and on a TPU the
    default matmul precision rounds p.r to bfloat16.
    points: (B, 2); refs: (R, 2) -> (B, R) float32."""
    p = points.astype(jnp.float32)
    r = refs.astype(jnp.float32)
    dx = p[:, 0:1] - r[None, :, 0]
    dy = p[:, 1:2] - r[None, :, 1]
    return dx * dx + dy * dy


def _chunk_map(fn, points: Array, chunk: int):
    """Apply ``fn`` over probe-row blocks so the (B, R) tile never exceeds
    (chunk, R) — mirrors the Pallas kernel's VMEM blocking."""
    b = points.shape[0]
    if b <= chunk:
        return fn(points)
    pad = (-b) % chunk
    pts = jnp.pad(points, ((0, pad), (0, 0)))
    blocks = pts.reshape(-1, chunk, 2)
    out = jax.lax.map(fn, blocks)
    return jax.tree.map(
        lambda x: x.reshape((-1,) + x.shape[2:])[:b], out)


def radius_count(points: Array, refs: Array, radius: float,
                 ref_valid: Optional[Array] = None,
                 chunk: int = _SPATIAL_CHUNK) -> Array:
    """#reference points within ``radius`` of each probe point. (B,) int32."""
    from repro.core.enrich import dispatch
    return dispatch.radius_count(points, refs, radius, ref_valid,
                                 chunk=chunk)


def _radius_count_ref(points: Array, refs: Array, radius: float,
                      ref_valid: Optional[Array] = None,
                      chunk: int = _SPATIAL_CHUNK) -> Array:
    r2 = jnp.float32(radius) ** 2

    def one(pts):
        d2 = pairwise_dist2(pts, refs)
        hit = d2 <= r2
        if ref_valid is not None:
            hit &= ref_valid[None, :]
        return jnp.sum(hit, axis=1).astype(jnp.int32)

    return _chunk_map(one, points, chunk)


def radius_topk(points: Array, refs: Array, radius: float, k: int,
                ref_valid: Optional[Array] = None,
                chunk: int = _SPATIAL_CHUNK
                ) -> Tuple[Array, Array, Array]:
    """k nearest reference points within ``radius``.
    Returns (idx (B,k) int32 [-1 when absent], dist2 (B,k), count (B,))."""
    from repro.core.enrich import dispatch
    return dispatch.radius_topk(points, refs, radius, k, ref_valid,
                                chunk=chunk)


def _radius_topk_ref(points: Array, refs: Array, radius: float, k: int,
                     ref_valid: Optional[Array] = None,
                     chunk: int = _SPATIAL_CHUNK
                     ) -> Tuple[Array, Array, Array]:
    r2 = jnp.float32(radius) ** 2
    kk = min(k, refs.shape[0])

    def one(pts):
        d2 = pairwise_dist2(pts, refs)
        if ref_valid is not None:
            d2 = jnp.where(ref_valid[None, :], d2, jnp.inf)
        neg, idx = jax.lax.top_k(-d2, kk)
        dd = -neg
        if kk < k:   # tiny reference table: pad result slots
            pad = [(0, 0), (0, k - kk)]
            idx = jnp.pad(idx, pad, constant_values=-1)
            dd = jnp.pad(dd, pad, constant_values=jnp.inf)
        ok = dd <= r2
        count = jnp.sum((d2 <= r2), axis=1).astype(jnp.int32)
        return (jnp.where(ok, idx, -1).astype(jnp.int32),
                jnp.where(ok, dd, jnp.inf), count)

    return _chunk_map(one, points, chunk)


def group_count_within_radius(points: Array, refs: Array, group: Array,
                              num_groups: int, radius: float,
                              ref_valid: Optional[Array] = None,
                              chunk: int = _SPATIAL_CHUNK) -> Array:
    """Per probe point: counts of in-radius reference points per group
    (Q5/Q6's 'facilities by type').  Returns (B, num_groups) int32.
    The hit x one-hot contraction is a dense GEMM — MXU-native — and
    exact at any matmul precision: both operands are 0 or 1, which
    bfloat16 holds exactly, and each count is at most R < 2**24, which
    the float32 accumulator holds exactly."""
    r2 = jnp.float32(radius) ** 2
    onehot = jax.nn.one_hot(group, num_groups, dtype=jnp.float32)
    if ref_valid is not None:
        onehot *= ref_valid[:, None]

    def one(pts):
        d2 = pairwise_dist2(pts, refs)
        hit = (d2 <= r2).astype(jnp.float32)
        if ref_valid is not None:
            hit *= ref_valid[None, :]
        return (hit @ onehot).astype(jnp.int32)

    return _chunk_map(one, points, chunk)


def point_in_rect(points: Array, rects: Array,
                  rect_valid: Optional[Array] = None,
                  chunk: int = 8192) -> Tuple[Array, Array]:
    """First containing rectangle per point (the paper's district lookup).
    points: (B, 2); rects: (R, 4) [xmin, ymin, xmax, ymax].
    Returns (rect_idx (B,) int32 [-1 when none], found (B,) bool).
    Chunked over points: Q6 pushes 1M persons through this."""
    big = jnp.int32(2**31 - 1)

    def one(pts):
        x, y = pts[:, 0:1], pts[:, 1:2]
        inside = ((x >= rects[None, :, 0]) & (y >= rects[None, :, 1])
                  & (x <= rects[None, :, 2]) & (y <= rects[None, :, 3]))
        if rect_valid is not None:
            inside &= rect_valid[None, :]
        # single min-iota reduction instead of any + argmax (§Perf: one
        # pass over the (B, R) tile instead of two)
        iota = jax.lax.broadcasted_iota(jnp.int32, inside.shape, 1)
        idx = jnp.min(jnp.where(inside, iota, big), axis=1)
        found = idx != big
        return jnp.where(found, idx, -1), found

    return _chunk_map(one, points, chunk)


def time_window_count_by_group(t: Array, event_t: Array, event_group: Array,
                               group_of_interest: Array, window: int,
                               event_valid: Optional[Array] = None) -> Array:
    """Q7: for each (probe time t_i, group g_ij): #events with
    t_i - window < event_t < t_i and event_group == g_ij.
    t: (B,); event_*: (A,); group_of_interest: (B, K). Returns (B, K)."""
    in_window = ((event_t[None, :] < t[:, None])
                 & (event_t[None, :] > (t[:, None] - window)))   # (B, A)
    if event_valid is not None:
        in_window &= event_valid[None, :]
    match = (group_of_interest[:, :, None]
             == event_group[None, None, :])                      # (B, K, A)
    return jnp.sum(match & in_window[:, None, :], axis=-1).astype(jnp.int32)
