"""Group-by aggregation as a one-hot x matmul on the MXU.

GPU group-by kernels scatter with atomics; TPU has neither atomics nor
efficient random scatter.  The TPU-native formulation turns the irregular
reduction into a dense GEMM (DESIGN.md §2):

    out[s] = sum_r 1[seg_r == s] * v_r    =    onehot(seg)^T @ v

The kernel streams value/segment blocks through VMEM over a (segment
block, row block) grid; each (block_s, 1) slice of the accumulator lives
in its output block, revisited along the row axis (the inner, 'arbitrary'
dimension, so the revisits are ordered).  The (block_s, block_r) one-hot
tile — segments on sublanes, rows on lanes — is built in-register from a
broadcasted iota compare: it never exists in HBM, which is what makes
this beat the XLA scatter lowering, and tiling the segment axis keeps it
VMEM-sized however many segments there are (Q6's district x ethnicity
state has tens of thousands).  Values and segment ids enter as lane-dense
(1, R) rows, so a million-row input costs its own size in HBM.

float32 values accumulate via the MXU (one-hot tile times the value row,
contracted over rows); int32 sums above 2^24 would lose bits in f32, so
the int path multiplies+reduces on the VPU in int32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# segments per one-hot tile (sublanes): a (512, block_r) int32 tile is
# 4 MiB at the default block_r, well inside a core's VMEM
_BLOCK_S = 512


def _kernel(values_ref, seg_ref, out_ref, *, block_s: int):
    s = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = values_ref[...]                       # (1, rk)
    seg = seg_ref[...]                           # (1, rk) int32
    ids = (jax.lax.broadcasted_iota(jnp.int32, (block_s, seg.shape[1]), 0)
           + s * block_s)
    onehot = ids == seg                          # (block_s, rk)
    if jnp.issubdtype(vals.dtype, jnp.floating):
        # (block_s, rk) x (1, rk)^T on the MXU
        acc = jax.lax.dot_general(
            onehot.astype(vals.dtype), vals, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        # exact integer accumulation on the VPU
        acc = jnp.sum(jnp.where(onehot, vals, 0), axis=1, keepdims=True)
    out_ref[...] += acc.astype(out_ref.dtype)   # (block_s, 1)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "block_r", "interpret"))
def segment_sum_pallas(values: jax.Array, seg: jax.Array, num_segments: int,
                       block_r: int = 2048, interpret: bool = False
                       ) -> jax.Array:
    """values: (R,); seg: (R,) int32.  Rows with seg >= num_segments are
    dropped (padding convention shared with the oracle).  ``block_r`` is a
    multiple of 128 (lanes)."""
    r = values.shape[0]
    r_pad = _round_up(max(r, block_r), block_r)
    block_s = min(_BLOCK_S, _round_up(num_segments, 8))
    s_pad = _round_up(num_segments, block_s)
    acc_dtype = (jnp.float32 if jnp.issubdtype(values.dtype, jnp.floating)
                 else jnp.int32)
    values_p = jnp.pad(values, (0, r_pad - r)).reshape(1, r_pad)
    # out-of-range segments (incl. padding) match no one-hot row
    seg_p = jnp.pad(seg.astype(jnp.int32), (0, r_pad - r),
                    constant_values=s_pad).reshape(1, r_pad)

    # 32-bit kernel: trace it with the package's x64 mode off (Mosaic
    # cannot lower the 64-bit literals x64 would give the body)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_kernel, block_s=block_s),
            grid=(s_pad // block_s, r_pad // block_r),
            in_specs=[
                pl.BlockSpec((1, block_r), lambda s, i: (0, i)),
                pl.BlockSpec((1, block_r), lambda s, i: (0, i)),
            ],
            out_specs=pl.BlockSpec((block_s, 1), lambda s, i: (s, 0)),
            out_shape=jax.ShapeDtypeStruct((s_pad, 1), acc_dtype),
            interpret=interpret,
        )(values_p, seg_p)
    return out[:num_segments, 0].astype(values.dtype)
