"""Spatial radius join (the paper's dominant enrichment cost, Fig 25/26) as
a tiled distance kernel with an in-register streaming top-k.

Paper workload: "monuments within 1.5 degrees of the tweet" (Q4), "3 closest
religious buildings within 3 degrees" (Q5/Q7).  A CUDA version would bucket
by spatial grid and chase neighbor lists; the TPU adaptation (DESIGN.md §2)
computes dense (bk x rk) distance tiles — perfectly regular VPU work — and
maintains, per probe row, a running ascending top-k of (distance, index)
entirely in registers/VMEM across reference blocks:

  extract the tile's k minima one at a time (min + iota-argmin + mask),
  insert each into the sorted running list with a compare-shift — no sort
  primitive needed, so everything lowers to Mosaic-supported elementwise
  ops and reductions.

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
from jax.experimental import pallas as pl

_IBIG = 2**31 - 1  # python int: pallas kernels cannot capture array constants


def _kernel(px_ref, py_ref, rx_ref, ry_ref, valid_ref,
            bestd_ref, besti_ref, count_ref, *,
            k: int, radius2: float, block_r: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        bestd_ref[...] = jnp.full_like(bestd_ref, jnp.inf)
        besti_ref[...] = jnp.full_like(besti_ref, -1)
        count_ref[...] = jnp.zeros_like(count_ref)

    px, py = px_ref[...], py_ref[...]                     # (bk, 1)
    rx, ry = rx_ref[...], ry_ref[...]                     # (1, rk)
    ok = valid_ref[...] != 0                              # (1, rk)

    dx = px - rx
    dy = py - ry
    d2 = jnp.where(ok, dx * dx + dy * dy, jnp.inf)        # (bk, rk)

    count_ref[...] += jnp.sum((d2 <= radius2).astype(jnp.int32), axis=1,
                              keepdims=True)

    bd, bi = bestd_ref[...], besti_ref[...]               # (bk, k) ascending
    bk_ = d2.shape[0]
    local = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1) + j * block_r
    slot = jax.lax.broadcasted_iota(jnp.int32, (bk_, k), 1)
    work = d2
    for _ in range(k):
        m = jnp.min(work, axis=1, keepdims=True)          # (bk, 1)
        sel_i = jnp.min(jnp.where(work == m, local, _IBIG), axis=1,
                        keepdims=True)
        # remove exactly the selected entry from the tile
        work = jnp.where(local == sel_i, jnp.inf, work)
        # sorted insert: after any equal values (keeps lower-index-first)
        pos = jnp.sum((bd <= m).astype(jnp.int32), axis=1, keepdims=True)
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


@functools.partial(jax.jit, static_argnames=("radius", "k", "block_b",
                                             "block_r", "interpret"))
def radius_join_pallas(px: jax.Array, py: jax.Array,
                       rx: jax.Array, ry: jax.Array,
                       radius: float, k: int,
                       ref_valid: jax.Array | None = None,
                       block_b: int = 256, block_r: int = 1024,
                       interpret: bool = False):
    """Returns (idx (B,k) int32 [-1], dist2 (B,k) [inf], count (B,) int32)
    for reference points within ``radius``, nearest first.  ``block_b`` is
    a multiple of 8 (sublanes), ``block_r`` of 128 (lanes)."""
    b, r = px.shape[0], rx.shape[0]
    b_pad = _round_up(max(b, block_b), block_b)
    r_pad = _round_up(max(r, block_r), block_r)
    f32 = jnp.float32

    def col(v):
        return jnp.pad(v.astype(f32), (0, b_pad - b)).reshape(b_pad, 1)

    def row(v, dtype=f32):                                # padding invalid
        return jnp.pad(v.astype(dtype), (0, r_pad - r)).reshape(1, r_pad)

    valid = (jnp.ones((r,), jnp.int32) if ref_valid is None
             else ref_valid)

    grid = (b_pad // block_b, r_pad // block_r)
    # 32-bit kernel: trace it with the package's x64 mode off (Mosaic
    # rejects 64-bit types)
    with jax.enable_x64(False):
        bestd, besti, count = pl.pallas_call(
            functools.partial(_kernel, k=k, radius2=float(radius) ** 2,
                              block_r=block_r),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((1, block_r), lambda i, j: (0, j)),
                pl.BlockSpec((1, block_r), lambda i, j: (0, j)),
                pl.BlockSpec((1, block_r), lambda i, j: (0, j)),
            ],
            out_specs=[
                pl.BlockSpec((block_b, k), lambda i, j: (i, 0)),
                pl.BlockSpec((block_b, k), lambda i, j: (i, 0)),
                pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b_pad, k), jnp.float32),
                jax.ShapeDtypeStruct((b_pad, k), jnp.int32),
                jax.ShapeDtypeStruct((b_pad, 1), jnp.int32),
            ],
            interpret=interpret,
        )(col(px), col(py), row(rx), row(ry), row(valid, jnp.int32))

    bestd, besti, count = bestd[:b], besti[:b], count[:b, 0]
    inside = bestd <= float(radius) ** 2
    return (jnp.where(inside, besti, -1),
            jnp.where(inside, bestd, jnp.inf), count)
