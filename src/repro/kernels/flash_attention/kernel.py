"""Causal GQA flash attention for TPU (the train/prefill compute hot spot of
every assigned LM architecture).

Standard online-softmax blocking, adapted to the TPU grid model: the grid is
(batch, q-heads, q-blocks, kv-blocks) with the kv dimension innermost and
'arbitrary' (sequential), so the running (m, l, acc) statistics live in VMEM
scratch and survive across kv steps; the output block is written once, on
the final kv step.  GQA is expressed entirely through the k/v BlockSpec
index maps (query head h reads kv head h // G) — no head-replicated copies
of K/V ever materialize, which is the main memory win over the XLA path at
long context.

Causality is exploited at block granularity: fully-masked kv blocks are
skipped via pl.when (a real TPU win — upper-triangle blocks cost zero), and
the diagonal blocks apply the element mask.

Block shapes default to (128 q x 512 kv) x head_dim, sized so q/k/v tiles +
scratch stay well under VMEM (~2 MB at D=128) and every matmul dim is a
multiple of the 128-lane MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, block_q: int, block_k: int, causal: bool):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (not causal) or (ik * block_k <= iq * block_q + block_q - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]                                   # (bq, d)
        k = k_ref[0, 0]                                   # (bk, d)
        v = v_ref[0, 0]
        s = jnp.dot(q, k.T,
                    preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jnp.dot(p.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True,
                           block_q: int = 128, block_k: int = 512,
                           interpret: bool = False) -> jax.Array:
    """q: (B, S, H, D); k/v: (B, T, Kv, D) with H = Kv * G.
    Returns (B, S, H, D) in q.dtype."""
    b, s, h, d = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    assert h == kv * g, (h, kv)
    scale = d ** -0.5

    block_q = min(block_q, _round_up(s, 128))
    block_k = min(block_k, _round_up(t, 128))
    s_pad = _round_up(s, block_q)
    t_pad = _round_up(t, block_k)
    d_pad = _round_up(d, 128)

    # (B, H, S, D) layout; zero-pad S/T/D (padded kv columns are masked by
    # causality for the padded q rows only — guard with an explicit big-neg
    # score via position masks when padding T)
    qx = jnp.moveaxis(q, 2, 1)
    kx = jnp.moveaxis(k, 2, 1)
    vx = jnp.moveaxis(v, 2, 1)
    qx = jnp.pad(qx, ((0, 0), (0, 0), (0, s_pad - s), (0, d_pad - d)))
    kx = jnp.pad(kx, ((0, 0), (0, 0), (0, t_pad - t), (0, d_pad - d)))
    vx = jnp.pad(vx, ((0, 0), (0, 0), (0, t_pad - t), (0, d_pad - d)))
    if t_pad != t:
        # padded keys sit at positions >= t; with causality and s <= t every
        # real query (qpos < s <= t <= kpos) masks them out.  Non-causal
        # callers must pre-align T to the kv block.
        assert causal and s <= t, "T padding requires causal and s <= t"

    grid = (b, h, s_pad // block_q, t_pad // block_k)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d_pad),
                         lambda bb, hh, iq, ik: (bb, hh, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d_pad),
                         lambda bb, hh, iq, ik, g=g: (bb, hh // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d_pad),
                         lambda bb, hh, iq, ik, g=g: (bb, hh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d_pad),
                               lambda bb, hh, iq, ik: (bb, hh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s_pad, d_pad), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qx, kx, vx)

    out = out[:, :, :s, :d]
    return jnp.moveaxis(out, 1, 2)
