"""Per-segment top-k selection as k tournament rounds on the VPU.

GPU top-k kernels sort per segment with shared-memory bitonic networks;
the TPU has neither scatter nor per-segment shared memory, but it eats
dense compare/reduce tiles — so the selection network runs over the SAME
in-register one-hot tile the segment-sum kernel uses (DESIGN.md §2):

    round j:  for every segment s, pick argmax_r {value_r : seg_r == s,
              r not selected in rounds < j}   (ties -> lowest row)

The grid is (k, R/block): the slow dimension is the round, the fast one
streams lane-dense (1, block) value/segment rows through VMEM.  The
one-hot tile is (S_pad, block): segments on sublanes, rows on lanes.  The
(S_pad, k) winner tables (value + row index) live in the revisited output
block, round j in column j; a round reads the previous rounds' winner
columns to mask them out — the per-row "am I already taken" test reuses
the one-hot tile as a gather (``where(onehot, taken_col, -1)`` + a
sublane max), so nothing irregular ever touches memory.  Columns are read
and written through a lane mask, never a dynamic lane index.  k rounds
re-stream R rows: O(kR) work against the reference's O(R log R)
composite sort, but each pass is pure VPU compare/max on data already in
VMEM, and the k the workload cares about (paper Q3: top-3; query topk:
single digits) is tiny.

Selection order is deterministic and bit-identical to the reference
oracle's composite-key sort: values descend, ties break toward the lower
row (blocks revisit in ascending row order and merges are strictly
``>``), which is exactly stable-argsort order.

The kernel returns ROW INDICES (``(S, k)`` int32, -1-filled); the ops.py
wrapper gathers payload/value columns outside the kernel, so any payload
dtype works without touching kernel memory.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BIG = 2**31 - 1  # python int: pallas kernels cannot capture array constants


def _kernel(values_ref, seg_ref, idx_ref, val_ref, *, block_r: int):
    j = pl.program_id(0)          # selection round (slow)
    i = pl.program_id(1)          # row block (fast)
    s_pad, k = idx_ref.shape
    slot = jax.lax.broadcasted_iota(jnp.int32, (s_pad, k), 1)

    @pl.when((j == 0) & (i == 0))
    def _init():                  # every round opens with no winners
        idx_ref[...] = jnp.full_like(idx_ref, -1)
        val_ref[...] = jnp.full_like(val_ref, -1)

    def column(ref, jj):          # (s_pad, 1): round jj's winners (>= -1)
        return jnp.max(jnp.where(slot == jj, ref[...], -1), axis=1,
                       keepdims=True)

    seg = seg_ref[...]                           # (1, block_r) int32
    onehot = jax.lax.broadcasted_iota(jnp.int32, (s_pad, block_r), 0) == seg
    rows = (jax.lax.broadcasted_iota(jnp.int32, (1, block_r), 1)
            + i * block_r)

    # mask rows already selected by earlier rounds: the winner row of
    # round jj for THIS row's segment, fetched through the one-hot tile
    def mask_round(jj, taken):
        prev = column(idx_ref, jj)
        mine = jnp.max(jnp.where(onehot, prev, -1), axis=0, keepdims=True)
        return jnp.where(mine == rows, 1, taken)

    taken = jax.lax.fori_loop(0, j, mask_round,
                              jnp.zeros((1, block_r), jnp.int32))
    cand = jnp.where(taken != 0, -1, values_ref[...])    # (1, block_r)
    # per-segment argmax within the block (first max -> lowest row)
    tile = jnp.where(onehot, cand, -1)                   # (s_pad, block_r)
    bmax = jnp.max(tile, axis=1, keepdims=True)          # (s_pad, 1)
    brow = jnp.min(jnp.where(tile == bmax, rows, _BIG), axis=1,
                   keepdims=True)
    accv = column(val_ref, j)
    acci = column(idx_ref, j)
    # blocks revisit in ascending row order, so strict > keeps the
    # earliest row on value ties — stable-sort order, like the oracle
    better = bmax > accv
    here = slot == j
    val_ref[...] = jnp.where(here, jnp.where(better, bmax, accv),
                             val_ref[...])
    idx_ref[...] = jnp.where(here, jnp.where(better, brow, acci),
                             idx_ref[...])


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("num_segments", "k",
                                             "block_r", "interpret"))
def segment_topk_pallas(values: jax.Array, seg: jax.Array,
                        num_segments: int, k: int, block_r: int = 512,
                        interpret: bool = False) -> jax.Array:
    """values: (R,) int32 (negatives rank as 0, clipped here — the empty-
    winner sentinel is -1); seg: (R,) int32, rows with seg outside
    [0, num_segments) are dropped (the shared padding convention).
    Returns (num_segments, k) int32 row indices, -1 where the segment has
    fewer than k rows.  ``block_r`` is a multiple of 128 (lanes)."""
    r = values.shape[0]
    r_pad = _round_up(max(r, block_r), block_r)
    s_pad = _round_up(max(num_segments, 1), 8)
    values = jnp.pad(jnp.maximum(values.astype(jnp.int32), 0),
                     (0, r_pad - r)).reshape(1, r_pad)
    seg = jnp.pad(seg.astype(jnp.int32), (0, r_pad - r),
                  constant_values=s_pad).reshape(1, r_pad)

    # 32-bit kernel: trace it with the package's x64 mode off (Mosaic
    # cannot lower 64-bit literals and index types)
    with jax.enable_x64(False):
        idx, _ = pl.pallas_call(
            functools.partial(_kernel, block_r=block_r),
            grid=(k, r_pad // block_r),
            in_specs=[
                pl.BlockSpec((1, block_r), lambda j, i: (0, i)),
                pl.BlockSpec((1, block_r), lambda j, i: (0, i)),
            ],
            out_specs=[
                pl.BlockSpec((s_pad, k), lambda j, i: (0, 0)),
                pl.BlockSpec((s_pad, k), lambda j, i: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((s_pad, k), jnp.int32),   # winner rows
                jax.ShapeDtypeStruct((s_pad, k), jnp.int32),   # winner vals
            ],
            interpret=interpret,
        )(values, seg)
    return idx[:num_segments]
