"""Explicit expert-parallel MoE FFN (shard_map + all_to_all).

The GSPMD-compiled sort/scatter MoE (moe.py) is correct but the SPMD
partitioner cannot see that dispatch is a permutation: it materializes and
**all-gathers** the (E, C, D) expert buffers across the model axis — the
dry-run measured 65 TB/device/step of all-gather wire on
kimi-k2 train_4k (EXPERIMENTS.md §Perf).  This module routes tokens with
two explicit ``all_to_all``s instead, which is what the physics requires:

  per device:  t local tokens, k experts each
    1. route + sort by destination expert shard (E/M experts per shard)
    2. all_to_all  (M, cap, D) token payload        -> owning shards
    3. local sort by expert, capacity-bucket, batched expert GEMMs
    4. all_to_all the processed tokens back, combine with router weights

Wire bytes/device/layer = 2 x t*k*cf*D (payload there and back) — for
kimi-k2 train_4k that is ~4.7 GB vs the ~1 TB GSPMD path, a ~200x
reduction at the collective-roofline level.

Drop semantics match moe.py (capacity factor bounds both hops).  The
routing math (top-k, normalized weights, load-balance aux) is shared.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import moe as M
from repro.models.sharding import current_mesh

Array = jax.Array


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _sort_bucket(values: Array, keys: Array, num_buckets: int,
                 capacity: int, fill_value=0.0):
    """Stable-sort rows of ``values`` by ``keys`` and place them in a dense
    (num_buckets, capacity) layout.  Returns (bucketed values, the slot each
    input row landed in [-1 = dropped])."""
    n = values.shape[0]
    order = jnp.argsort(keys)
    skey = keys[order]
    start = jnp.searchsorted(skey, jnp.arange(num_buckets))
    pos = jnp.arange(n, dtype=jnp.int32) - start[skey].astype(jnp.int32)
    keep = (pos < capacity) & (skey < num_buckets)
    slot = jnp.where(keep, skey * capacity + pos, num_buckets * capacity)
    buf = jnp.full((num_buckets * capacity + 1,) + values.shape[1:],
                   fill_value, values.dtype)
    buf = buf.at[slot].set(values[order], mode="drop")   # sorted order!
    # slot of each ORIGINAL row (invert the sort)
    inv_slot = jnp.full((n,), -1, jnp.int32)
    inv_slot = inv_slot.at[order].set(
        jnp.where(keep, slot, -1).astype(jnp.int32))
    return buf[:-1].reshape((num_buckets, capacity) + values.shape[1:]), \
        inv_slot


def moe_ffn_ep(cfg: ModelConfig, p: Dict, x: Array,
               axis: str = "model") -> Tuple[Array, Array]:
    """Drop-in for moe.moe_ffn when a mesh with ``axis`` is active."""
    mesh = current_mesh()
    if mesh is None or axis not in mesh.shape or \
            cfg.num_experts % mesh.shape[axis] != 0:
        return M.moe_ffn(cfg, p, x)

    m_sz = mesh.shape[axis]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)

    body = functools.partial(_ep_body, cfg, axis, m_sz)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes if batch_axes else None, None, None),
                  P(None, None),                    # router replicated
                  P(axis), P(axis), P(axis)),       # experts sharded on E
        out_specs=(P(batch_axes if batch_axes else None, None, None),
                   P()),
        check_vma=False)
    y, aux = fn(x, p["router"], p["w_gate"].astype(x.dtype),
                p["w_up"].astype(x.dtype), p["w_down"].astype(x.dtype))
    return y, aux


def _ep_body(cfg: ModelConfig, axis: str, m_sz: int,
             x: Array, router: Array, wg: Array, wu: Array, wd: Array
             ) -> Tuple[Array, Array]:
    """Per-device body.  x: (B_l, S, D) local tokens; wg/wu/wd:
    (E_l, D, F) local experts."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    e_l = e // m_sz
    t = b * s
    xf = x.reshape(t, d)

    # router product in activation dtype with f32 accumulation — casting
    # xf up materialized a (t, d) f32 copy per layer (§Perf: 4.5 TB/step
    # of convert traffic on kimi-k2 before this)
    logits = jnp.einsum("td,de->te", xf, router.astype(xf.dtype),
                        preferred_element_type=jnp.float32)
    weights, idx = M._route(logits, k)                 # (t, k)

    # load-balance aux (local estimate; mean across devices via psum)
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
    aux = jax.lax.pmean(aux, axis)

    # ---- hop 1: tokens -> owning expert shard (bf16 features + int meta,
    # identical bucketing order so the slots line up) ----
    tk = t * k
    flat_e = idx.reshape(tk)                           # global expert id
    dst = (flat_e // e_l).astype(jnp.int32)            # owning shard
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_w = weights.reshape(tk)

    cap_send = _round_up(
        max(int(cfg.capacity_factor * tk / m_sz), 8), 8)
    send_x, sent_slot = _sort_bucket(xf[flat_t], dst, m_sz, cap_send,
                                     fill_value=0)
    send_e, _ = _sort_bucket((flat_e % e_l).astype(jnp.int32), dst,
                             m_sz, cap_send, fill_value=-1)
    recv_x = jax.lax.all_to_all(send_x, axis, split_axis=0,
                                concat_axis=0, tiled=False)
    recv_e = jax.lax.all_to_all(send_e, axis, split_axis=0,
                                concat_axis=0, tiled=False)

    rx = recv_x.reshape(m_sz * cap_send, d)
    rexp = recv_e.reshape(m_sz * cap_send)             # -1 = padding

    # ---- local expert GEMMs (same bucketing, per local expert) ----
    # single-expert shards (e.g. jamba: 16e over 16-way) need no second
    # over-provision: every received row fits by construction (§Perf —
    # the 1.25^2 double-padding showed up as +25% expert-GEMM flops)
    over = 1.25 if e_l > 1 else 1.0
    cap_e = _round_up(max(int(m_sz * cap_send / e_l * over), 8), 8)
    buf, rslot = _sort_bucket(rx, jnp.where(rexp >= 0, rexp, e_l),
                              e_l, cap_e)
    cdt = wg.dtype
    g = jnp.einsum("ecd,edf->ecf", buf.astype(cdt), wg)
    u = jnp.einsum("ecd,edf->ecf", buf.astype(cdt), wu)
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, wd)            # (E_l, cap_e, D)

    # un-bucket back to recv order, send back in the SAME slots
    out_flat = out.reshape(e_l * cap_e, d)
    out_flat = jnp.concatenate(
        [out_flat, jnp.zeros((1, d), out_flat.dtype)], axis=0)
    back = out_flat[jnp.where(rslot >= 0, rslot, e_l * cap_e)]
    back = back.reshape(m_sz, cap_send, d)
    ret = jax.lax.all_to_all(back, axis, split_axis=0, concat_axis=0,
                             tiled=False)
    ret_flat = ret.reshape(m_sz * cap_send, d)

    # ---- combine: gather each (token, k) contribution by its sent slot
    ret_flat = jnp.concatenate(
        [ret_flat, jnp.zeros((1, d), ret_flat.dtype)], axis=0)
    contrib = ret_flat[jnp.where(sent_slot >= 0, sent_slot,
                                 m_sz * cap_send)]
    contrib = contrib * jnp.where(sent_slot >= 0, flat_w,
                                  0.0).astype(contrib.dtype)[:, None]
    y = jnp.zeros((t, d), contrib.dtype).at[flat_t].add(contrib)
    return y.reshape(b, s, d).astype(x.dtype), aux
