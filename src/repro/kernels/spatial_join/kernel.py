"""Spatial radius join (the paper's dominant enrichment cost, Fig 25/26) as
a tiled distance kernel with an in-register streaming top-k.

Paper workload: "monuments within 1.5 degrees of the tweet" (Q4), "3 closest
religious buildings within 3 degrees" (Q5/Q7).  A CUDA version would bucket
by spatial grid and chase neighbor lists; the TPU adaptation (DESIGN.md §2)
computes dense (bk x rk) distance tiles — perfectly regular VPU work — and
maintains, per probe row, a running ascending top-k of (distance, index)
entirely in registers/VMEM across reference blocks:

  extract the tile's k minima one at a time (min + argmin by original
  index + mask), insert each into the sorted running list with a
  compare-shift — no sort primitive needed, so everything lowers to
  Mosaic-supported elementwise ops and reductions.

Latitude band: probes and references are first put in the order of
their first coordinate (``latitude_order``), so each probe block only has
to meet the run of reference blocks whose first-coordinate interval comes
within ``radius`` of its own (``band_plan``).  The run's bounds are scalar-prefetched; tiles outside it
compute nothing, and their reference index is clamped into the run, so
they fetch no new block.  The grid stays static.  When every point shares
one band the run is the whole grid.

Outputs are revisited across the reference grid dimension (innermost,
'arbitrary' semantics), so the counts and top-k accumulate in place.

Layout: probe coordinates enter as (B, 1) columns and reference
coordinates as (1, R) rows, so a distance tile is a broadcast of each
along the other axis and every per-probe result stays a column.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_IBIG = 2**31 - 1  # python int: pallas kernels cannot capture array constants


def _kernel(lo_ref, hi_ref, px_ref, py_ref, rx_ref, ry_ref, valid_ref,
            ri_ref, bestd_ref, besti_ref, count_ref, *,
            k: int, radius2: float):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        bestd_ref[...] = jnp.full_like(bestd_ref, jnp.inf)
        besti_ref[...] = jnp.full_like(besti_ref, -1)
        count_ref[...] = jnp.zeros_like(count_ref)

    @pl.when((lo_ref[i] <= j) & (j < hi_ref[i]))
    def _tile():
        px, py = px_ref[...], py_ref[...]                 # (bk, 1)
        rx, ry = rx_ref[...], ry_ref[...]                 # (1, rk)
        ok = valid_ref[...] != 0                          # (1, rk)
        ri = ri_ref[...]                                  # (1, rk) original

        dx = px - rx
        dy = py - ry
        d2 = jnp.where(ok, dx * dx + dy * dy, jnp.inf)    # (bk, rk)

        count_ref[...] += jnp.sum((d2 <= radius2).astype(jnp.int32), axis=1,
                                  keepdims=True)

        bd, bi = bestd_ref[...], besti_ref[...]           # (bk, k) ascending
        slot = jax.lax.broadcasted_iota(jnp.int32, bd.shape, 1)
        work = d2
        for _ in range(k):
            m = jnp.min(work, axis=1, keepdims=True)      # (bk, 1)
            sel_i = jnp.min(jnp.where(work == m, ri, _IBIG), axis=1,
                            keepdims=True)
            # remove exactly the selected entry from the tile
            work = jnp.where(ri == sel_i, jnp.inf, work)
            # sorted insert by (distance, original index): blocks arrive in
            # latitude order, so an equal distance met earlier may still
            # belong after this one
            pos = jnp.sum(((bd < m) | ((bd == m) & (bi < sel_i)))
                          .astype(jnp.int32), axis=1, keepdims=True)
            shift_d = jnp.concatenate([bd[:, :1], bd[:, :-1]], axis=1)
            shift_i = jnp.concatenate([bi[:, :1], bi[:, :-1]], axis=1)
            at = slot == pos
            before = slot < pos
            bd = jnp.where(before, bd, jnp.where(at, m, shift_d))
            bi = jnp.where(before, bi, jnp.where(at, sel_i, shift_i))
        bestd_ref[...] = bd
        besti_ref[...] = bi


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def latitude_order(x: jax.Array, keep: jax.Array) -> jax.Array:
    """A permutation (n,) int32 that takes ``x`` (n,) float32 to ascending
    order, with the entries where ``keep`` is false, or ``x`` is not
    finite, last.  ``x`` is cut into at most 2**20 equal steps of the kept
    range; within a step the order is the index order.  The key is the
    step in the high bits of an int32 and the index in the low bits, so
    keys are distinct and one unstable sort of one int32 array orders
    them: for a v5e at 51,200 rows that sort compiles in seconds, where a
    stable sort of float32 keys carrying their payload takes minutes."""
    n = x.shape[0]
    ibits = max(1, (n - 1).bit_length())
    top = (1 << min(31 - ibits, 20)) - 1          # the step of the rest
    ok = keep & jnp.isfinite(x)
    lo = jnp.min(jnp.where(ok, x, jnp.inf))
    span = jnp.max(jnp.where(ok, x, -jnp.inf)) - lo
    frac = jnp.where(span > 0, (x - lo) / span, 0.0)
    step = jnp.floor(jnp.clip(frac, 0.0, 1.0) * (top - 1))
    step = jnp.where(ok & ~jnp.isnan(step), step, top).astype(jnp.int32)
    idx = jnp.arange(n, dtype=jnp.int32)
    key = jax.lax.sort((step << ibits) | idx, is_stable=False)
    return key & ((1 << ibits) - 1)


def band_plan(px: jax.Array, rx: jax.Array, ref_valid: jax.Array,
              radius: float, block_b: int, block_r: int):
    """The run of reference blocks each probe block must visit.

    ``px`` (B_pad,) and ``rx`` (R_pad,) are float32 first coordinates in
    the kernel's order (``latitude_order``), padded to whole blocks, and
    ``ref_valid`` (R_pad,) marks the references the kernel counts.
    Returns (lo, hi), (B_pad / block_b,) int32: probe block i meets
    reference blocks [lo[i], hi[i]), and none when lo == hi.

    A pair of blocks is skipped only when ``fl(gap) * fl(gap) > fl(r²)``,
    where ``gap`` is the float32 difference of the blocks' facing edges
    (their least and greatest first coordinate) and ``fl(r²)`` is the
    float32 radius² the kernel compares with.  That never drops a pair
    the kernel would count as ``d2 <= fl(r²)``: for a probe p and
    reference q in those blocks the exact |p - q| is at least the exact
    edge difference, and float32 rounding is monotone, so
    ``|fl(p - q)| >= fl(gap)`` and ``fl(dx * dx) >= fl(gap * gap)``; and
    ``d2 = fl(fl(dx * dx) + fl(dy * dy)) >= fl(dx * dx)``, because
    ``fl(a + b) >= fl(a) = a`` for a float a and b >= 0 (with a fused
    multiply-add, ``fl(dx² + b) >= fl(dx²)`` all the same).  A NaN edge
    compares false, so its blocks are visited; a block of no valid
    reference is skipped.  In latitude order the blocks to visit are one
    run, up to blocks that share a step; the first and the last visited
    block bound it in any case.
    """
    nb, nr = px.shape[0] // block_b, rx.shape[0] // block_r
    pb = px.reshape(nb, block_b)
    rb = rx.reshape(nr, block_r)
    ok = ref_valid.reshape(nr, block_r) != 0
    pmin = jnp.min(pb, axis=1, keepdims=True)             # (nb, 1)
    pmax = jnp.max(pb, axis=1, keepdims=True)
    qmin = jnp.min(jnp.where(ok, rb, jnp.inf), axis=1)[None, :]   # (1, nr)
    qmax = jnp.max(jnp.where(ok, rb, -jnp.inf), axis=1)[None, :]
    gap = jnp.maximum(jnp.maximum(qmin - pmax, pmin - qmax), 0.0)
    visit = ~(gap * gap > np.float32(float(radius) ** 2))  # (nb, nr)
    any_ = jnp.any(visit, axis=1)
    lo = jnp.argmax(visit, axis=1)
    hi = nr - jnp.argmax(visit[:, ::-1], axis=1)
    return (jnp.where(any_, lo, 0).astype(jnp.int32),
            jnp.where(any_, hi, 0).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("radius", "k", "block_b",
                                             "block_r", "interpret"))
def radius_join_pallas(px: jax.Array, py: jax.Array,
                       rx: jax.Array, ry: jax.Array,
                       radius: float, k: int,
                       ref_valid: jax.Array | None = None,
                       block_b: int = 256, block_r: int = 1024,
                       interpret: bool = False):
    """Returns (idx (B,k) int32 [-1], dist2 (B,k) [inf], count (B,) int32)
    for reference points within ``radius``, nearest first, ties to the
    lower reference index.  ``block_b`` is a multiple of 8 (sublanes),
    ``block_r`` of 128 (lanes)."""
    b, r = px.shape[0], rx.shape[0]
    b_pad = _round_up(max(b, block_b), block_b)
    r_pad = _round_up(max(r, block_r), block_r)
    f32, i32 = jnp.float32, jnp.int32

    # probes by latitude; padding repeats the last, so no block widens
    order = latitude_order(px.astype(f32), jnp.ones((b,), bool))
    pxs = jnp.pad(px.astype(f32)[order], (0, b_pad - b), mode="edge")
    pys = jnp.pad(py.astype(f32)[order], (0, b_pad - b), mode="edge")

    # references by latitude; invalid and padding ones last
    valid = (jnp.ones((r,), i32) if ref_valid is None
             else ref_valid.astype(i32))
    valid = jnp.pad(valid, (0, r_pad - r))
    rxp = jnp.pad(rx.astype(f32), (0, r_pad - r))
    ris = latitude_order(rxp, valid != 0)
    rxs, valids = rxp[ris], valid[ris]
    rys = jnp.pad(ry.astype(f32), (0, r_pad - r))[ris]

    lo, hi = band_plan(pxs, rxs, valids, radius, block_b, block_r)

    def ref_block(i, j, lo, hi):       # skipped steps keep a fetched block
        return 0, jnp.clip(j, lo[i], jnp.maximum(hi[i] - 1, lo[i]))

    def probe_block(i, j, lo, hi):
        return i, 0

    col = pl.BlockSpec((block_b, 1), probe_block)
    row = pl.BlockSpec((1, block_r), ref_block)
    # 32-bit kernel: trace it with the package's x64 mode off (Mosaic
    # rejects 64-bit types)
    with jax.enable_x64(False):
        bestd, besti, count = pl.pallas_call(
            functools.partial(_kernel, k=k, radius2=float(radius) ** 2),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(b_pad // block_b, r_pad // block_r),
                in_specs=[col, col, row, row, row, row],
                out_specs=[
                    pl.BlockSpec((block_b, k), probe_block),
                    pl.BlockSpec((block_b, k), probe_block),
                    pl.BlockSpec((block_b, 1), probe_block),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((b_pad, k), f32),
                jax.ShapeDtypeStruct((b_pad, k), i32),
                jax.ShapeDtypeStruct((b_pad, 1), i32),
            ],
            interpret=interpret,
        )(lo, hi, pxs.reshape(b_pad, 1), pys.reshape(b_pad, 1),
          rxs.reshape(1, r_pad), rys.reshape(1, r_pad),
          valids.reshape(1, r_pad), ris.reshape(1, r_pad))

    # back to the probes' input order
    inv = jnp.zeros((b,), i32).at[order].set(jnp.arange(b, dtype=i32))
    bestd, besti, count = bestd[inv], besti[inv], count[inv, 0]
    inside = bestd <= float(radius) ** 2
    return (jnp.where(inside, besti, -1),
            jnp.where(inside, bestd, jnp.inf), count)
