"""Enrichment kernel-dispatch layer: route the relational operators through
the Pallas kernels with shape-bucketed jit caching.

The paper's thesis (An IDEA §6-8) only pays off if the enrichment operators
themselves are fast at scale; the stream-enrichment survey finds operator
*dispatch* cost dominates once ingestion is decoupled.  This module is that
dispatch layer:

  * **Routing** — each operator picks the Pallas kernel or the pure-jnp
    reference path per call.  Policy (repro.kernels.get_dispatch_mode):
    "pallas" forces the kernel (interpret-mode emulation off-TPU — slow,
    for equivalence tests and --dispatch pallas benchmarks), "reference"
    forces the jnp path, and "auto" uses the kernel only on TPU and only
    above ``min_pallas_rows`` (tiny batches are dominated by launch
    overhead, not compute — the reference path wins there).

  * **Shape-bucketed jit caching** — probe batches arrive at every size
    (partial frames, coalesced micro-batches, drain-protocol tails).  A
    fresh XLA compile per size would re-introduce exactly the per-statement
    compile cost the paper's predeployed jobs eliminate (§5.2.1), so probe
    dimensions are padded up to power-of-two buckets (floor
    ``bucket_min``): at most log2(max_batch) compiled variants per
    operator, ever.  Padding rows are key-sentinel / dropped-segment rows,
    inert by the same convention that already pads reference snapshots.

Row counts, padding and routing are all static at trace time, so these
functions are safe both eagerly and inside predeployed (AOT-compiled) UDFs.
The reference-table operand is NOT bucketed here: snapshots are already
shape-stable (fixed capacity, trim-quantized in computing.py) and the
kernels pad the reference block internally.

``segment_topk`` routes to the tournament-selection kernel
(kernels/segment_topk) inside its segment-count/k envelope — the
query subsystem's group-by top-k lives there — and to the composite-key
XLA sort outside it (Q3's 50K-segment state build).

Fused UDF chains (core/plan.py) trace every stage's operators into ONE
predeployed executable, so a chained Q1->Q2->Q3 plan pays one dispatch per
batch total, not one per stage; routing/bucketing decisions here happen at
trace time and are baked into that single executable.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.refdata import KEY_SENTINEL
from repro.kernels import (dispatch_mode,  # noqa: F401  (re-export: scoped
                           # mode override — plan tests force "reference"
                           # to compare fused vs sequential bit-for-bit)
                           get_dispatch_mode, resolve_use_pallas)
from repro.kernels.hash_probe import ops as hp_ops
from repro.kernels.segment_reduce import ops as sr_ops
from repro.kernels.segment_topk import ops as st_ops
from repro.kernels.spatial_join import ops as sj_ops

Array = jax.Array


@dataclasses.dataclass
class DispatchConfig:
    min_pallas_rows: int = 1024   # "auto": below this the jnp path wins
    bucket_min: int = 512         # smallest probe bucket
    bucket_max: int = 1 << 22     # cap: beyond this, chunk upstream
    # segment_topk kernel envelope: its (S_pad, k) winner tables and
    # the (S_pad, block) one-hot tile live in VMEM, and its work is
    # O(k*R) vs the reference sort's O(R log R) — route to the kernel
    # only inside these bounds (Q3's 50K-country top-3 stays on the
    # reference sort; query-layer group-bys land inside)
    topk_max_segments: int = 2048
    topk_max_k: int = 16


_config = DispatchConfig()
_stats_lock = threading.Lock()              # lock-name: dispatch-stats
_bucket_hits: Dict[Tuple[str, int], int] = {}   # guarded-by: _stats_lock
# (op, path) execution-path counters for the segment_* aggregation ops:
# "kernel" = Pallas kernel, "xla_64bit" = the EXPLICIT wide-dtype XLA
# fallback (the MXU kernel accumulates in 32 bits; a hi/lo split
# accumulator is TPU-future work — see ROADMAP), "reference" = jnp path
# via mode/size/envelope routing.  Callers that need a per-query view
# (QueryStats' kernel-vs-fallback report) use the thread-local tape.
_path_hits: Dict[Tuple[str, str], int] = {}     # guarded-by: _stats_lock
_tls = threading.local()                    # per-thread path tape


def configure(min_pallas_rows: Optional[int] = None,
              bucket_min: Optional[int] = None,
              bucket_max: Optional[int] = None,
              topk_max_segments: Optional[int] = None,
              topk_max_k: Optional[int] = None) -> DispatchConfig:
    if min_pallas_rows is not None:
        _config.min_pallas_rows = min_pallas_rows
    if bucket_min is not None:
        _config.bucket_min = bucket_min
    if bucket_max is not None:
        _config.bucket_max = bucket_max
    if topk_max_segments is not None:
        _config.topk_max_segments = topk_max_segments
    if topk_max_k is not None:
        _config.topk_max_k = topk_max_k
    return _config


def bucket_rows(n: int, minimum: Optional[int] = None) -> int:
    """Smallest power-of-two bucket >= n (floor ``minimum``).  This is the
    whole recompile-avoidance scheme: every operator pads its probe batch to
    a bucket, so the predeploy/jit caches see O(log max_batch) shapes."""
    lo = max(int(minimum) if minimum is not None else _config.bucket_min, 1)
    b = lo
    while b < n:
        b <<= 1
    return min(max(b, n), max(_config.bucket_max, n))


def bucket_stats() -> Dict[Tuple[str, int], int]:
    """(op, bucket) -> dispatch count; tests use this to pin down that
    nearby batch sizes share a compiled shape."""
    with _stats_lock:
        return dict(_bucket_hits)


def reset_bucket_stats() -> None:
    with _stats_lock:
        _bucket_hits.clear()


def _note(op: str, bucket: int) -> None:
    with _stats_lock:
        _bucket_hits[(op, bucket)] = _bucket_hits.get((op, bucket), 0) + 1


def path_stats() -> Dict[Tuple[str, str], int]:
    """(op, path) -> dispatch count for the segment_* aggregation ops;
    path is "kernel", "xla_64bit" (wide-dtype fallback, explicit by
    design), or "reference" (mode/size/envelope routing)."""
    with _stats_lock:
        return dict(_path_hits)


def reset_path_stats() -> None:
    with _stats_lock:
        _path_hits.clear()


def path_tape_start() -> None:
    """Start recording this thread's segment_* dispatch paths (the query
    layer wraps one execute() in a tape to report kernel-vs-fallback
    counts without cross-thread noise from concurrent feeds)."""
    _tls.paths = {}


def path_tape_stop() -> Dict[Tuple[str, str], int]:
    """Stop this thread's tape and return its (op, path) counts."""
    d = getattr(_tls, "paths", None) or {}
    _tls.paths = None
    return d


def _note_path(op: str, path: str) -> None:
    with _stats_lock:
        _path_hits[(op, path)] = _path_hits.get((op, path), 0) + 1
    d = getattr(_tls, "paths", None)
    if d is not None:
        d[(op, path)] = d.get((op, path), 0) + 1


def _use_pallas(rows: int) -> bool:
    # the row threshold applies only in "auto"; mode semantics stay in
    # one place (repro.kernels.resolve_use_pallas)
    if get_dispatch_mode() == "auto" and rows < _config.min_pallas_rows:
        return False
    return resolve_use_pallas(None)


# ---------------------------------------------------------------------------
# hash join probe
# ---------------------------------------------------------------------------

def sorted_join(probe: Array, ref_keys: Array) -> Tuple[Array, Array]:
    """Equi-join probe against a sorted sentinel-padded key column.
    Returns (idx (B,) int32 [-1 when absent], found (B,) bool) — the
    kernels/hash_probe/ref.py convention on both paths."""
    b = probe.shape[0]
    if not _use_pallas(b):
        from repro.core.enrich import ops
        return ops._sorted_join_ref(probe, ref_keys)
    bk = bucket_rows(b)
    _note("sorted_join", bk)
    probe_p = jnp.pad(probe, (0, bk - b), constant_values=KEY_SENTINEL)
    idx, found = hp_ops.sorted_probe(probe_p, ref_keys, use_pallas=True)
    return idx[:b], found[:b]


# ---------------------------------------------------------------------------
# spatial radius join
# ---------------------------------------------------------------------------

def _pad_points(points: Array, bk: int) -> Tuple[Array, Array]:
    b = points.shape[0]
    p = jnp.pad(points.astype(jnp.float32), ((0, bk - b), (0, 0)))
    return p[:, 0], p[:, 1]


def radius_topk(points: Array, refs: Array, radius: float, k: int,
                ref_valid: Optional[Array] = None,
                chunk: Optional[int] = None
                ) -> Tuple[Array, Array, Array]:
    """k nearest reference points within ``radius`` per probe point.
    Returns (idx (B,k) int32 [-1], dist2 (B,k) [inf], count (B,)).
    ``chunk`` only shapes the reference path's probe-row blocking (the
    kernel blocks in VMEM-sized tiles on its own)."""
    b = points.shape[0]
    if not _use_pallas(b):
        from repro.core.enrich import ops
        kw = {} if chunk is None else {"chunk": chunk}
        return ops._radius_topk_ref(points, refs, radius, k, ref_valid,
                                    **kw)
    bk = bucket_rows(b)
    _note("radius_topk", bk)
    px, py = _pad_points(points, bk)
    idx, d2, count = sj_ops.radius_join(px, py, refs[:, 0], refs[:, 1],
                                        radius, k, ref_valid,
                                        use_pallas=True)
    return idx[:b], d2[:b], count[:b]


def radius_count(points: Array, refs: Array, radius: float,
                 ref_valid: Optional[Array] = None,
                 chunk: Optional[int] = None) -> Array:
    """#reference points within ``radius`` of each probe point, (B,) int32.
    Kernel path: the radius join's count output with a minimal top-k."""
    b = points.shape[0]
    if not _use_pallas(b):
        from repro.core.enrich import ops
        kw = {} if chunk is None else {"chunk": chunk}
        return ops._radius_count_ref(points, refs, radius, ref_valid, **kw)
    bk = bucket_rows(b)
    _note("radius_count", bk)
    px, py = _pad_points(points, bk)
    _, _, count = sj_ops.radius_join(px, py, refs[:, 0], refs[:, 1],
                                     radius, 1, ref_valid, use_pallas=True)
    return count[:b]


# ---------------------------------------------------------------------------
# group-by aggregation
# ---------------------------------------------------------------------------

def _segment_64bit(values: Array) -> bool:
    # the MXU/VPU kernel accumulates in 32 bits; 64-bit inputs must take
    # the XLA path or high bits are silently lost
    return jnp.dtype(values.dtype).itemsize > 4


def segment_sum(values: Array, seg: Array, num_segments: int,
                valid: Optional[Array] = None) -> Array:
    r = values.shape[0]
    if _segment_64bit(values):
        # explicit, not silent: wide dtypes CANNOT ride the MXU kernel
        # (32-bit accumulator) in any mode — recorded as its own path so
        # QueryStats can report which dispatches fell back and why
        _note_path("segment_sum", "xla_64bit")
        from repro.core.enrich import ops
        return ops._segment_sum_ref(values, seg, num_segments, valid)
    if not _use_pallas(r):
        _note_path("segment_sum", "reference")
        from repro.core.enrich import ops
        return ops._segment_sum_ref(values, seg, num_segments, valid)
    _note_path("segment_sum", "kernel")
    rk = bucket_rows(r)
    _note("segment_sum", rk)
    seg = seg.astype(jnp.int32)
    if valid is not None:
        # invalid rows route to the dropped overflow segment
        seg = jnp.where(valid, seg, num_segments)
    values = jnp.pad(values, (0, rk - r))
    seg = jnp.pad(seg, (0, rk - r), constant_values=num_segments)
    return sr_ops.segment_sum(values, seg, num_segments, use_pallas=True)


def segment_count(seg: Array, num_segments: int,
                  valid: Optional[Array] = None) -> Array:
    ones = jnp.ones(seg.shape, jnp.int32)
    return segment_sum(ones, seg, num_segments, valid)


def segment_topk(values: Array, seg: Array, payload: Array,
                 num_segments: int, k: int,
                 valid: Optional[Array] = None) -> Tuple[Array, Array]:
    """Per-segment top-k by ``values`` desc (ties: row asc), returning
    ((S, k) payload -1-filled, (S, k) values 0-filled).  Kernel path: the
    tournament-selection kernel (kernels/segment_topk) picks winner ROW
    indices; payload/value gathers happen out here so any payload dtype
    rides along.  Falls back to the composite-key-sort reference outside
    the kernel's segment/k envelope or for 64-bit values (the winner
    table ranks in int32)."""
    r = values.shape[0]
    from repro.core.enrich import ops
    if (r == 0 or not _use_pallas(r) or num_segments < 1
            or num_segments > _config.topk_max_segments
            or k > _config.topk_max_k
            # the winner table ranks in int32: anything that does not
            # embed losslessly (64-bit, unsigned >= 2^31, floats) takes
            # the composite-sort reference
            or not jnp.issubdtype(values.dtype, jnp.signedinteger)
            or jnp.dtype(values.dtype).itemsize > 4):
        _note_path("segment_topk",
                   "xla_64bit" if jnp.dtype(values.dtype).itemsize > 4
                   else "reference")
        return ops._segment_topk_ref(values, seg, payload, num_segments,
                                     k, valid)
    _note_path("segment_topk", "kernel")
    rk = bucket_rows(r)
    _note("segment_topk", rk)
    segi = seg.astype(jnp.int32)
    if valid is not None:
        # invalid rows route to the dropped overflow segment
        segi = jnp.where(valid, segi, num_segments)
    vals_p = jnp.pad(values.astype(jnp.int32), (0, rk - r))
    seg_p = jnp.pad(segi, (0, rk - r), constant_values=num_segments)
    idx = st_ops.segment_topk_idx(vals_p, seg_p, num_segments, k,
                                  use_pallas=True)        # (S, k) rows
    found = idx >= 0
    safe = jnp.maximum(idx, 0)
    pay = jnp.where(found, jnp.take(payload, safe, axis=0),
                    jnp.asarray(-1, payload.dtype))
    val = jnp.where(found, jnp.take(values, safe, axis=0),
                    jnp.asarray(0, values.dtype))
    return pay, val


# ---------------------------------------------------------------------------
# batched-aggregation planner
# ---------------------------------------------------------------------------

def concat_rows(parts: Sequence[Dict[str, np.ndarray]]
                ) -> Tuple[Dict[str, np.ndarray], int]:
    """Concat-and-pad planner for the per-query batched aggregation path
    (core/query.py): the per-unit masked column slices of one query are
    concatenated IN SCAN ORDER into a single contiguous batch per column,
    so the whole query pays one ``segment_*`` dispatch per aggregate
    instead of one per surviving unit.  Returns ``(cols, n)`` with ``n``
    real rows; the caller pads row dimensions to ``bucket_rows(n)`` when
    it builds the segment-id vector (padding rows must route to the
    dropped overflow segment, which only the caller can number).  Scan
    order is preserved because downstream top-k tie-breaking is
    value-desc-then-scan-order — identical to the eager per-unit path and
    the naive reference.  The hit is recorded against the row bucket the
    dispatches will use, so ``bucket_stats()`` shows batched queries
    riding the same bounded jit-cache shape ladder as the write side."""
    parts = [p for p in parts if p and next(iter(p.values())).shape[0]]
    if not parts:
        return {}, 0
    if len(parts) == 1:
        cols = {k: np.asarray(v) for k, v in parts[0].items()}
    else:
        cols = {k: np.concatenate([np.asarray(p[k]) for p in parts])
                for k in parts[0]}
    n = int(next(iter(cols.values())).shape[0])
    _note("concat_rows", bucket_rows(n))
    return cols, n
