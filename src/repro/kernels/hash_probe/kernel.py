"""Equi-join probe ("hash join" probe phase) as a streaming compare kernel.

The paper's hash join builds an in-memory hash table and probes it per
record (§5.3.4).  TPUs have no efficient pointer-chase, so the adaptation
(DESIGN.md §2) probes the *sorted* key column instead.  A GPU port would
binary-search; on TPU even binary search is awkward (vector gather across a
large VMEM array).  This kernel instead streams reference-key blocks
through VMEM and does a dense (bk x rk) equality compare per tile — O(B·R)
compares instead of O(B log R), but every op is a full-width VPU op with
zero irregular memory traffic, and R-blocks are shared across all probes in
the block.  For reference tables that fit VMEM (all of the paper's), one
pass suffices; the match index is recovered from an iota-min reduction.

Keys are int64 (primary keys / 63-bit hashes); the compare is done on the
(hi, lo) int32 halves since the TPU VPU has no native 64-bit lanes.

Layout: probes enter as a (B, 1) column and reference keys as a (1, R)
row, so the (bk, rk) compare tile is a plain broadcast of each along the
other axis — no lane-to-sublane relayout inside the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BIG = 2**31 - 1  # python int: pallas kernels cannot capture array constants


def _split64(x: jax.Array):
    """int64 -> (hi, lo) int32 pair (TPU vectors are 32-bit)."""
    lo = (x & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32).astype(jnp.int32)
    hi = (x >> jnp.int64(32)).astype(jnp.int32)
    return hi, lo


def _kernel(phi_ref, plo_ref, rhi_ref, rlo_ref, idx_ref, *, block_r: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        idx_ref[...] = jnp.full_like(idx_ref, _BIG)

    # (bk, 1) column vs (1, rk) row -> (bk, rk)
    eq = ((phi_ref[...] == rhi_ref[...]) & (plo_ref[...] == rlo_ref[...]))
    local = jax.lax.broadcasted_iota(jnp.int32, eq.shape, 1) + j * block_r
    hit = jnp.min(jnp.where(eq, local, _BIG), axis=1, keepdims=True)
    idx_ref[...] = jnp.minimum(idx_ref[...], hit)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_r", "interpret"))
def sorted_probe_pallas(probe: jax.Array, ref_keys: jax.Array,
                        block_b: int = 256, block_r: int = 2048,
                        interpret: bool = False):
    """probe: (B,) int64; ref_keys: (R,) int64 (sentinel-padded; uniqueness
    assumed, as produced by RefTable.snapshot).  Returns (idx, found).
    ``block_b`` is a multiple of 8 (sublanes), ``block_r`` of 128 (lanes)."""
    from repro.core.refdata import KEY_SENTINEL

    b, r = probe.shape[0], ref_keys.shape[0]
    b_pad = _round_up(max(b, block_b), block_b)
    r_pad = _round_up(max(r, block_r), block_r)
    probe_p = jnp.pad(probe, (0, b_pad - b), constant_values=KEY_SENTINEL)
    # pad ref with sentinel-1 values: never equal to any probe (sentinel
    # probes must also miss, handled below)
    ref_p = jnp.pad(ref_keys, (0, r_pad - r),
                    constant_values=KEY_SENTINEL - 1)
    phi, plo = _split64(probe_p.reshape(b_pad, 1))
    rhi, rlo = _split64(ref_p.reshape(1, r_pad))

    # the kernel is 32-bit throughout; trace it with the package's x64 mode
    # off, or Mosaic meets 64-bit literals and index types it cannot lower
    with jax.enable_x64(False):
        idx = pl.pallas_call(
            functools.partial(_kernel, block_r=block_r),
            grid=(b_pad // block_b, r_pad // block_r),
            in_specs=[
                pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((1, block_r), lambda i, j: (0, j)),
                pl.BlockSpec((1, block_r), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((b_pad, 1), jnp.int32),
            interpret=interpret,
        )(phi, plo, rhi, rlo)

    idx = idx[:b, 0]
    found = (idx != _BIG) & (probe != KEY_SENTINEL) & (idx < r)
    return jnp.where(found, idx, -1), found
