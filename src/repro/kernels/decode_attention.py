"""Pallas TPU kernel: single-token decode attention over the packed KV pool.

Decode queries are one token per slot, so the flash kernel's (bq, dh)
query panel degenerates to a single sublane at bq=1 — almost the whole
MXU tile is padding.  This kernel instead packs the ``rep = H // KV``
query heads that share a KV head into the SUBLANE dimension: the grid is
``(S slots, nkv KV blocks)`` and each cell contracts, for every KV head
``g``, a (rep, dh) query panel against the (bkv, dh) panel of head ``g``,
so the score tile is (rep, bkv) and no panel row is wasted on sequence
padding.  K/V are never repeated in HBM: q is viewed as (S, KV, rep, dh)
and each grid cell DMAs one (bkv, KV, dh) block of the un-repeated
(S, C, KV, dh) pool — every KV head at once, since a block's last two
dimensions must be whole (or (8, 128)-aligned) for the TPU's tiling.

Masking is positional, matching the serving cache layout exactly: every
pool entry carries its absolute position (``kv_pos``; empty / padded
slots hold a huge sentinel) and each slot carries its own current
position ``q_pos``, so one rule covers causal validity, partially-filled
slots, AND ring-buffer sliding windows:

    ok = (kv_pos <= q_pos) & (q_pos - kv_pos < window)

with ``window = cache_len`` for non-windowed caches (a linear buffer
never holds a position older than cache_len).  ``q_pos`` is scalar-
prefetched into SMEM whole.  The KV axis is innermost so the
online-softmax running state (m, l, acc) lives in VMEM scratch across
sequential KV steps, exactly like the flash kernel.

The pool may be the model's whole layer stack, (L, S, C, KV, dh) with
``kv_pos`` (L, S, C): the layer index is scalar-prefetched beside
``q_pos`` and picks the layer in the block index maps, so a layer scan
that carries the stacked pool hands it over without slicing it.  A
single layer's (S, C, KV, dh) pool is the same call at L = 1.  The
positions' block is ``sub`` slots x bkv (8 x bkv, or all S when S is
not a multiple of 8), since a one-slot block of (L, S, C) breaks the
TPU's (8, 128) tiling; the cell reads its own slot's row of it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
NEG_INF = -1e30


def _decode_kernel(qpos_ref, layer_ref, q_ref, k_ref, v_ref, kpos_ref,
                   o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                   window: int, nkv: int, n_kv: int, sub: int):
    b, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qp = qpos_ref[b]                                   # scalar int32
    kp = kpos_ref[pl.ds(b % sub, 1), :]                # (1, bkv)
    # one mask covers causality, empty (sentinel-pos) slots and the ring
    # window; padded cache tails carry the sentinel so they fail kp <= qp
    ok = (kp <= qp) & (qp - kp < window)
    for g in range(n_kv):                              # static: KV heads
        q = q_ref[0, g].astype(jnp.float32) * scale    # (rep, dh)
        k = k_ref[0, :, g].astype(jnp.float32)         # (bkv, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(ok, s, NEG_INF)                  # (rep, bkv)
        m_prev = m_ref[g]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        # explicit mask on p: an all-masked block would otherwise exp(0)=1
        # while m is still NEG_INF
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[g] = l_ref[g] * corr + p.sum(-1, keepdims=True)
        m_ref[g] = m_new
        acc_ref[g] = acc_ref[g] * corr + jnp.dot(
            p, v_ref[0, :, g].astype(jnp.float32),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nkv - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_pallas(q: Array, k: Array, v: Array, q_pos: Array,
                            kv_pos: Array, *, window: int = 0,
                            scale: float = None, bkv: int = 128,
                            layer: Optional[Array] = None,
                            interpret: bool = False) -> Array:
    """q: (S, H, dh); k, v: (S, C, KV, dh); q_pos: (S,); kv_pos: (S, C).

    With ``layer`` (a scalar int, may be traced), k and v are the stacked
    (L, S, C, KV, dh) pool and kv_pos is (L, S, C); the kernel attends
    over layer ``layer`` of it in place.
    H = KV * rep, with query head h attending to KV head h // rep (the
    layout ``blockwise_attention`` and the serving cache pool share).
    ``window`` is the sliding-window width; 0 means un-windowed (masked
    internally as window = C, the most a linear buffer can hold).  On
    the chip ``bkv`` must be a multiple of 128 unless it covers the whole
    cache; a C that is not a multiple of ``bkv`` pads (copies) the pool.
    Returns (S, H, dh).
    """
    if layer is None:
        k, v, kv_pos, layer = k[None], v[None], kv_pos[None], 0
    s_slots, h, dh = q.shape
    c, n_kv = k.shape[2], k.shape[3]
    rep = h // n_kv
    scale = scale if scale is not None else dh ** -0.5
    window = window or c
    bkv = min(bkv, c)
    pad = (-c) % bkv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, 0), (0, pad)),
                         constant_values=jnp.iinfo(jnp.int32).max // 2)
    nkv = (c + pad) // bkv
    sub = 8 if s_slots % 8 == 0 else s_slots
    qg = q.reshape(s_slots, n_kv, rep, dh)
    kv_spec = pl.BlockSpec((None, 1, bkv, n_kv, dh),
                           lambda b, j, qp, li: (li[0], b, j, 0, 0))
    pos_spec = pl.BlockSpec((None, sub, bkv),
                            lambda b, j, qp, li: (li[0], b // sub, j))
    q_spec = pl.BlockSpec((1, n_kv, rep, dh),
                          lambda b, j, qp, li: (b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, window=window,
                          nkv=nkv, n_kv=n_kv, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s_slots, nkv),
            in_specs=[q_spec, kv_spec, kv_spec, pos_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((n_kv, rep, 1), jnp.float32),
                pltpu.VMEM((n_kv, rep, 1), jnp.float32),
                pltpu.VMEM((n_kv, rep, dh), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((s_slots, n_kv, rep, dh), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(q_pos.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      qg, k, v, kv_pos.astype(jnp.int32))
    return out.reshape(s_slots, h, dh)
