"""Attention: GQA (full / sliding-window / chunked-local / bidirectional /
cross) + KV-cache decode, and DeepSeek-V2 MLA with absorbed decode.

The training/prefill path is a blockwise online-softmax implementation
(lax.scan over KV blocks) so S x S score matrices are never materialised —
this is also the pure-jnp oracle mirrored by the Pallas flash kernel in
``repro.kernels.flash_attention``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import (
    apply_rope,
    linear,
    make_linear,
    make_rms_norm,
    rms_norm,
)

Array = jax.Array
KV_BLOCK = 1024
NEG_INF = -1e30


# ======================================================================
# mask helpers
def _mask_bias(q_pos: Array, k_pos: Array, kind: str, window: int,
               kv_len: Optional[Array]) -> Array:
    """(..., T, S_blk) additive bias. kind: causal|sliding|chunked|full."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    if kind == "full":
        ok = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    elif kind == "causal":
        ok = kp <= qp
    elif kind == "sliding":
        ok = (kp <= qp) & (qp - kp < window)
    elif kind == "chunked":
        ok = (kp <= qp) & (qp // window == kp // window)
    else:
        raise ValueError(kind)
    if kv_len is not None:
        ok = ok & (kp < kv_len)
    return jnp.where(ok, 0.0, NEG_INF)


# ======================================================================
# sharding hints (perf: pins attention internals to head-on-model sharding,
# preventing XLA SPMD from resharding the score/prob tensors every KV block
# — see EXPERIMENTS.md §Perf iteration 1)
def _hint(x: Array, rt, spec_dims) -> Array:
    """spec_dims: tuple of 'batch' | 'model' | None per dim; each entry is
    applied only if the dim divides the axis size (else dropped)."""
    if rt is None or getattr(rt, "mesh", None) is None:
        return x
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    mesh = rt.mesh
    parts = []
    for dim, want in enumerate(spec_dims):
        if want == "batch":
            axes = tuple(a for a in getattr(rt, "batch_axes", ())
                         if a in mesh.shape)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            parts.append(axes if (axes and size > 1
                                  and x.shape[dim] % size == 0) else None)
        elif want == "model":
            if "model" in getattr(rt, "batch_axes", ()):
                parts.append(None)   # model axis already carries batch (dp)
                continue
            size = mesh.shape.get("model", 1)
            if size > 1 and x.shape[dim] % size:
                # cannot satisfy the intended sharding: constraining would
                # force replication, which measured WORSE than XLA's own
                # choice (smollm h=9, §Perf iter 1) — leave unconstrained.
                return x
            parts.append("model" if size > 1 else None)
        else:
            parts.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))


# ======================================================================
# blockwise online-softmax attention (the jnp oracle; memory O(T * block)).
# Heads are processed FLAT (GQA K/V repeated per block — block-local, so
# the repeat never hits HBM at full length): flat H shards cleanly over the
# model axis where the grouped (KV=8, rep=4) layout cannot split 16 ways.
def blockwise_attention(q: Array, k: Array, v: Array, *,
                        kind: str = "causal", window: int = 0,
                        q_positions: Optional[Array] = None,
                        kv_positions: Optional[Array] = None,
                        kv_len: Optional[Array] = None,
                        kv_block: int = KV_BLOCK,
                        scale: Optional[float] = None,
                        rt=None) -> Array:
    """q: (B,T,H,dh); k,v: (B,S,KV,dh) with H = KV*rep. Returns (B,T,H,dh)."""
    b, t, h, dh = q.shape
    s, n_kv = k.shape[1], k.shape[2]
    rep = h // n_kv
    if rt is not None and getattr(rt, "kv_block", 0):
        kv_block = rt.kv_block
    scale = scale if scale is not None else dh ** -0.5
    if q_positions is None:
        q_positions = jnp.arange(t, dtype=jnp.int32)[None].repeat(b, 0)
    if kv_positions is None:
        kv_positions = jnp.arange(s, dtype=jnp.int32)[None].repeat(b, 0)

    kv_block = min(kv_block, s)
    pad = (-s) % kv_block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                               constant_values=jnp.iinfo(jnp.int32).max // 2)
    n_blk = (s + pad) // kv_block

    # grouped einsum: GQA K/V stay un-repeated (measured better for GQA
    # archs than flat-head + hints — §Perf mistral iters 1-2); MLA (flat by
    # construction) keeps its hinted path in mla_forward.
    qg = q.reshape(b, t, n_kv, rep, dh) * scale
    kb = k.reshape(b, n_blk, kv_block, n_kv, dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, n_blk, kv_block, n_kv, dh).transpose(1, 0, 2, 3, 4)
    pb = kv_positions.reshape(b, n_blk, kv_block).transpose(1, 0, 2)

    def body(carry, blk):
        m, l, acc = carry
        kj, vj, posj = blk
        sc = jnp.einsum("btgrd,bsgd->bgrts", qg, kj.astype(qg.dtype),
                        preferred_element_type=jnp.float32)
        bias = _mask_bias(q_positions[:, None, None, :],
                          posj[:, None, None, :], kind, window, kv_len)
        sc = sc + bias.astype(jnp.float32)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bgrts,bsgd->btgrd", p.astype(vj.dtype), vj)
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None].astype(acc.dtype) \
            + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, n_kv, rep, t), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n_kv, rep, t), jnp.float32)
    acc0 = jnp.zeros((b, t, n_kv, rep, dh), v.dtype)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kb, vb, pb))
    denom = l.transpose(0, 3, 1, 2)[..., None]
    out = acc.astype(jnp.float32) / jnp.maximum(denom, 1e-30)
    return out.reshape(b, t, h, dh).astype(q.dtype)


def direct_attention(q, k, v, **kw):
    """Single-block reference (used for small shapes / tests)."""
    return blockwise_attention(q, k, v, kv_block=max(k.shape[1], 1), **kw)


# ======================================================================
# GQA module
def make_gqa(key, cfg: ModelConfig, dtype, *, n_heads=None, n_kv=None,
             cross: bool = False) -> dict:
    h = n_heads or cfg.n_heads
    kvh = n_kv or cfg.n_kv_heads
    d, dh = cfg.d_model, cfg.head_dim
    kq, kk, kv_, ko = jax.random.split(key, 4)
    p = {
        "wq": make_linear(kq, d, h * dh, dtype),
        "wk": make_linear(kk, d, kvh * dh, dtype),
        "wv": make_linear(kv_, d, kvh * dh, dtype),
        "wo": make_linear(ko, h * dh, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = make_rms_norm(dh, dtype)
        p["k_norm"] = make_rms_norm(dh, dtype)
    return p


def _qkv(p: dict, x: Array, x_kv: Array, cfg: ModelConfig, h: int, kvh: int):
    b, t = x.shape[:2]
    s = x_kv.shape[1]
    q = linear(x, p["wq"]).reshape(b, t, h, cfg.head_dim)
    k = linear(x_kv, p["wk"]).reshape(b, s, kvh, cfg.head_dim)
    v = linear(x_kv, p["wv"]).reshape(b, s, kvh, cfg.head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"]["scale"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"]["scale"], cfg.norm_eps)
    return q, k, v


def gqa_forward(p: dict, x: Array, cfg: ModelConfig, *,
                kind: str = "causal", window: int = 0,
                positions: Optional[Array] = None,
                x_cross: Optional[Array] = None,
                n_heads=None, n_kv=None, rope: bool = True,
                return_kv: bool = False, rt=None):
    """Full-sequence (train/prefill) attention."""
    h = n_heads or cfg.n_heads
    kvh = n_kv or cfg.n_kv_heads
    b, t = x.shape[:2]
    x_kv = x_cross if x_cross is not None else x
    q, k, v = _qkv(p, x, x_kv, cfg, h, kvh)
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.int32)[None].repeat(b, 0)
    if rope and cfg.rope_theta > 0 and x_cross is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    out = blockwise_attention(
        q, k, v, kind=("full" if x_cross is not None else kind), window=window,
        q_positions=positions, rt=rt,
        kv_positions=None if x_cross is None else
        jnp.arange(x_kv.shape[1], dtype=jnp.int32)[None].repeat(b, 0))
    y = linear(out.reshape(b, t, h * cfg.head_dim), p["wo"])
    if return_kv:
        return y, {"k": k, "v": v}          # k already rope'd (cache layout)
    return y


# ----------------------------------------------------------------------
# KV cache (decode). Ring buffer when window > 0 (sliding window / chunked).
def init_kv_cache(batch: int, cache_len: int, n_kv: int, head_dim: int,
                  dtype) -> dict:
    return {
        "k": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
        # absolute position per slot (for rope'd keys the slot stores its
        # pos). Empty slots hold a huge sentinel so kp<=qp masks them out.
        "pos": jnp.full((batch, cache_len), jnp.iinfo(jnp.int32).max // 2,
                        jnp.int32),
        "len": jnp.zeros((), jnp.int32),       # tokens seen so far
    }


def gqa_decode(p: dict, x: Array, cache: dict, cfg: ModelConfig, *,
               kind: str = "causal", window: int = 0,
               n_heads=None, n_kv=None, rt=None) -> Tuple[Array, dict]:
    """One-token decode. x: (B, 1, d_model)."""
    h = n_heads or cfg.n_heads
    kvh = n_kv or cfg.n_kv_heads
    b = x.shape[0]
    cache_len = cache["k"].shape[1]
    pos = cache["len"]                                    # scalar int32
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = _qkv(p, x, x, cfg, h, kvh)
    if cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # ring buffer for windowed attention, linear buffer otherwise
    slot = (pos % cache_len) if window > 0 else jnp.minimum(pos, cache_len - 1)
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    pos_cache = jax.lax.dynamic_update_slice(
        cache["pos"], positions, (0, slot))
    # empty slots carry a huge position sentinel, so kp<=qp masks them
    out = blockwise_attention(
        q, k_cache, v_cache, kind=kind, window=window or cache_len,
        q_positions=positions, kv_positions=pos_cache, rt=rt)
    new_cache = {"k": k_cache, "v": v_cache, "pos": pos_cache, "len": pos + 1}
    o = linear(out.reshape(b, 1, h * cfg.head_dim), p["wo"])
    return o, new_cache


def gqa_decode_slots(p: dict, x: Array, cache: dict, cfg: ModelConfig, *,
                     kind: str = "causal", window: int = 0,
                     n_heads=None, n_kv=None, rt=None,
                     backend: str = "reference",
                     interpret: bool = False,
                     layer: Optional[Array] = None) -> Tuple[Array, dict]:
    """One-token decode with PER-SLOT positions (the serving cache pool).

    Unlike ``gqa_decode`` (one scalar ``len`` for the whole batch), every
    slot carries its own position: x: (S, 1, d_model); cache: ``k``/``v``
    (S, C, KV, dh), ``pos`` (S, C), ``lens`` (S,) int32.  Slot s writes its
    new K/V at ring index ``lens[s] % C`` (windowed) or ``lens[s]``
    (linear) and attends at query position ``lens[s]`` — slots at
    different depths coexist in one batched call, which is what lets new
    requests be admitted mid-decode without recompiling.

    ``backend='pallas'`` routes the attention contraction to
    ``kernels.decode_attention`` (interpret mode off-TPU); the default is
    the blockwise jnp oracle.

    With ``layer`` (a scalar, may be traced), ``k``/``v``/``pos`` are the
    model's stacked (L, S, C, KV, dh) / (L, S, C) pool: the new rows are
    written at ``[layer, s, slot]`` and the whole updated pool comes back,
    so a layer scan can carry it in place.
    """
    h = n_heads or cfg.n_heads
    kvh = n_kv or cfg.n_kv_heads
    b = x.shape[0]
    cache_len = cache["k"].shape[-3]
    lens = cache["lens"]                                  # (S,) int32
    positions = lens[:, None]                             # (S, 1)
    q, k, v = _qkv(p, x, x, cfg, h, kvh)
    if cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    slot = (lens % cache_len) if window > 0 \
        else jnp.minimum(lens, cache_len - 1)
    rows = jnp.arange(b, dtype=jnp.int32)
    at = (rows, slot) if layer is None else (layer, rows, slot)
    with jax.named_scope("kv_write"):
        k_cache = cache["k"].at[at].set(k[:, 0])
        v_cache = cache["v"].at[at].set(v[:, 0])
        pos_cache = cache["pos"].at[at].set(lens)
    if backend == "pallas":
        from repro.kernels.decode_attention import decode_attention_pallas
        out = decode_attention_pallas(q[:, 0], k_cache, v_cache, lens,
                                      pos_cache, window=window, layer=layer,
                                      interpret=interpret)[:, None]
    else:
        kr, vr, pr = (k_cache, v_cache, pos_cache) if layer is None else \
            (k_cache[layer], v_cache[layer], pos_cache[layer])
        out = blockwise_attention(q, kr, vr, kind=kind,
                                  window=window or cache_len,
                                  q_positions=positions,
                                  kv_positions=pr, rt=rt)
    new_cache = {"k": k_cache, "v": v_cache, "pos": pos_cache,
                 "lens": lens + 1}
    o = linear(out.reshape(b, 1, h * cfg.head_dim), p["wo"])
    return o, new_cache


def gqa_cross_decode(p: dict, x: Array, cross_cache: dict,
                     cfg: ModelConfig, *, n_heads=None, n_kv=None) -> Array:
    """Cross-attention during decode: kv precomputed from the encoder."""
    h = n_heads or cfg.n_heads
    b = x.shape[0]
    q = linear(x, p["wq"]).reshape(b, 1, h, cfg.head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"]["scale"], cfg.norm_eps)
    out = blockwise_attention(q, cross_cache["k"], cross_cache["v"],
                              kind="full")
    return linear(out.reshape(b, 1, h * cfg.head_dim), p["wo"])


def precompute_cross_kv(p: dict, x_enc: Array, cfg: ModelConfig, *,
                        n_kv=None) -> dict:
    kvh = n_kv or cfg.n_kv_heads
    b, s = x_enc.shape[:2]
    k = linear(x_enc, p["wk"]).reshape(b, s, kvh, cfg.head_dim)
    v = linear(x_enc, p["wv"]).reshape(b, s, kvh, cfg.head_dim)
    if "k_norm" in p:
        k = rms_norm(k, p["k_norm"]["scale"], cfg.norm_eps)
    return {"k": k, "v": v}


# ======================================================================
# DeepSeek-V2 MLA [arXiv:2405.04434]
def make_mla(key, cfg: ModelConfig, dtype) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qd = m.nope_head_dim + m.rope_head_dim
    keys = jax.random.split(key, 6)
    p = {}
    if m.q_lora_rank:
        p["wq_a"] = make_linear(keys[0], d, m.q_lora_rank, dtype)
        p["q_norm"] = make_rms_norm(m.q_lora_rank, dtype)
        p["wq_b"] = make_linear(keys[1], m.q_lora_rank, h * qd, dtype)
    else:
        p["wq"] = make_linear(keys[0], d, h * qd, dtype)
    p["w_dkv"] = make_linear(keys[2], d, m.kv_lora_rank + m.rope_head_dim, dtype)
    p["kv_norm"] = make_rms_norm(m.kv_lora_rank, dtype)
    p["w_ukv"] = make_linear(keys[3], m.kv_lora_rank,
                             h * (m.nope_head_dim + m.v_head_dim), dtype)
    p["wo"] = make_linear(keys[4], h * m.v_head_dim, d, dtype)
    return p


def _mla_q(p: dict, x: Array, cfg: ModelConfig, positions: Array):
    m = cfg.mla
    b, t = x.shape[:2]
    h = cfg.n_heads
    qd = m.nope_head_dim + m.rope_head_dim
    if "wq_a" in p:
        ql = rms_norm(linear(x, p["wq_a"]), p["q_norm"]["scale"], cfg.norm_eps)
        q = linear(ql, p["wq_b"]).reshape(b, t, h, qd)
    else:
        q = linear(x, p["wq"]).reshape(b, t, h, qd)
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(p: dict, x: Array, cfg: ModelConfig, positions: Array):
    m = cfg.mla
    ckv_rope = linear(x, p["w_dkv"])
    c_kv, k_rope = jnp.split(ckv_rope, [m.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, p["kv_norm"]["scale"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return c_kv, k_rope


def mla_forward(p: dict, x: Array, cfg: ModelConfig, *,
                positions: Optional[Array] = None,
                kv_block: int = KV_BLOCK, return_kv: bool = False,
                rt=None):
    """Train/prefill MLA: blockwise attention, up-projecting K/V lazily per
    KV block inside the scan (never materialises full K/V)."""
    m = cfg.mla
    b, t = x.shape[:2]
    h = cfg.n_heads
    if rt is not None and getattr(rt, "kv_block", 0):
        kv_block = rt.kv_block
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.int32)[None].repeat(b, 0)
    q_nope, q_rope = _mla_q(p, x, cfg, positions)       # (b,t,h,*)
    c_kv, k_rope = _mla_ckv(p, x, cfg, positions)       # (b,t,kvr),(b,t,rd)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5

    s = t
    kv_block = min(kv_block, s)
    pad = (-s) % kv_block
    if pad:
        c_kv = jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
    n_blk = (s + pad) // kv_block
    ckv_b = c_kv.reshape(b, n_blk, kv_block, -1).transpose(1, 0, 2, 3)
    krope_b = k_rope.reshape(b, n_blk, kv_block, -1).transpose(1, 0, 2, 3)
    pos_b = jnp.pad(positions, ((0, 0), (0, pad)),
                    constant_values=jnp.iinfo(jnp.int32).max // 2
                    ).reshape(b, n_blk, kv_block).transpose(1, 0, 2)
    w_ukv = p["w_ukv"]["w"]

    def body(carry, blk):
        mx, l, acc = carry
        ckv_j, kr_j, pos_j = blk
        kv = (ckv_j @ w_ukv.astype(ckv_j.dtype)).reshape(
            b, kv_block, h, m.nope_head_dim + m.v_head_dim)
        k_nope, v = jnp.split(kv, [m.nope_head_dim], axis=-1)
        sc = (jnp.einsum("bthd,bshd->bhts", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthd,bsd->bhts", q_rope, kr_j,
                           preferred_element_type=jnp.float32)) * scale
        sc = _hint(sc, rt, ("batch", "model", None, None))
        bias = _mask_bias(positions[:, None, :], pos_j[:, None, :],
                          "causal", 0, None)
        sc = sc + bias.astype(jnp.float32)
        m_new = jnp.maximum(mx, sc.max(axis=-1))
        pr = _hint(jnp.exp(sc - m_new[..., None]), rt,
                   ("batch", "model", None, None))
        corr = jnp.exp(mx - m_new)
        l_new = l * corr + pr.sum(axis=-1)
        pv = jnp.einsum("bhts,bshd->bthd", pr.astype(v.dtype), v)
        acc_new = acc * corr.transpose(0, 2, 1)[..., None].astype(acc.dtype) + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, t), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    acc0 = jnp.zeros((b, t, h, m.v_head_dim), x.dtype)
    (mx, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0),
                                   (ckv_b, krope_b, pos_b))
    out = acc.astype(jnp.float32) / jnp.maximum(
        l.transpose(0, 2, 1)[..., None], 1e-30)
    out = out.reshape(b, t, h * m.v_head_dim).astype(x.dtype)
    y = linear(out, p["wo"])
    if return_kv:
        return y, {"c_kv": c_kv[:, :t], "k_rope": k_rope[:, :t]}
    return y


def init_mla_cache(batch: int, cache_len: int, cfg: ModelConfig, dtype) -> dict:
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, cache_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, cache_len, m.rope_head_dim), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def mla_decode_slots(p: dict, x: Array, cache: dict, cfg: ModelConfig,
                     rt=None) -> Tuple[Array, dict]:
    """Absorbed MLA decode with PER-SLOT positions (serving cache pool).
    cache: ``c_kv`` (S, C, kvr), ``k_rope`` (S, C, rd), ``lens`` (S,)."""
    m = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    lens = cache["lens"]                                  # (S,)
    positions = lens[:, None]                             # (S, 1)
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_new, kr_new = _mla_ckv(p, x, cfg, positions)
    rows = jnp.arange(b, dtype=jnp.int32)
    with jax.named_scope("kv_write"):
        c_cache = cache["c_kv"].at[rows, lens].set(c_new[:, 0])
        kr_cache = cache["k_rope"].at[rows, lens].set(kr_new[:, 0])

    w_ukv = p["w_ukv"]["w"].reshape(m.kv_lora_rank, h,
                                    m.nope_head_dim + m.v_head_dim)
    w_uk = w_ukv[..., : m.nope_head_dim]
    w_uv = w_ukv[..., m.nope_head_dim:]
    q_c = jnp.einsum("bthd,chd->bhc", q_nope, w_uk.astype(q_nope.dtype))
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    sc = (jnp.einsum("bhc,bsc->bhs", q_c, c_cache,
                     preferred_element_type=jnp.float32)
          + jnp.einsum("bthd,bsd->bhs", q_rope, kr_cache,
                       preferred_element_type=jnp.float32)) * scale
    s_len = c_cache.shape[1]
    valid = jnp.arange(s_len)[None, None, :] <= lens[:, None, None]
    sc = jnp.where(valid, sc, NEG_INF)
    alpha = jax.nn.softmax(sc, axis=-1).astype(c_cache.dtype)
    o_c = jnp.einsum("bhs,bsc->bhc", alpha, c_cache)
    out = jnp.einsum("bhc,chd->bhd", o_c, w_uv.astype(o_c.dtype))
    out = out.reshape(b, 1, h * m.v_head_dim)
    new_cache = {"c_kv": c_cache, "k_rope": kr_cache, "lens": lens + 1}
    return linear(out, p["wo"]), new_cache


def mla_decode(p: dict, x: Array, cache: dict, cfg: ModelConfig,
               rt=None) -> Tuple[Array, dict]:
    """Absorbed MLA decode: attention runs in the compressed kv_lora space —
    the cache stays (S, 512+64) per token and K/V are never up-projected."""
    m = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    pos = cache["len"]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, x, cfg, positions)         # (b,1,h,*)
    c_new, kr_new = _mla_ckv(p, x, cfg, positions)        # (b,1,kvr),(b,1,rd)
    c_cache = jax.lax.dynamic_update_slice(cache["c_kv"], c_new, (0, pos, 0))
    kr_cache = jax.lax.dynamic_update_slice(cache["k_rope"], kr_new, (0, pos, 0))

    w_ukv = p["w_ukv"]["w"].reshape(m.kv_lora_rank, h,
                                    m.nope_head_dim + m.v_head_dim)
    w_uk = w_ukv[..., : m.nope_head_dim]                  # (kvr, h, nope)
    w_uv = w_ukv[..., m.nope_head_dim:]                   # (kvr, h, v)
    # absorb: q_c = q_nope @ W_uk^T  -> (b, h, kvr)
    q_c = jnp.einsum("bthd,chd->bhc", q_nope, w_uk.astype(q_nope.dtype))
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    sc = (jnp.einsum("bhc,bsc->bhs", q_c, c_cache,
                     preferred_element_type=jnp.float32)
          + jnp.einsum("bthd,bsd->bhs", q_rope, kr_cache,
                       preferred_element_type=jnp.float32)) * scale
    # decode sequence-parallelism: scores/weights sharded over cache
    # positions (matches the S-sharded MLA cache layout); the softmax and
    # the o_c contraction reduce over S -> small cross-shard psums only
    sc = _hint(sc, rt, ("batch", None, "model"))
    s_len = c_cache.shape[1]
    valid = jnp.arange(s_len)[None, None, :] <= pos
    sc = jnp.where(valid, sc, NEG_INF)
    alpha = _hint(jax.nn.softmax(sc, axis=-1).astype(c_cache.dtype),
                  rt, ("batch", None, "model"))
    o_c = jnp.einsum("bhs,bsc->bhc", alpha, c_cache)      # (b,h,kvr)
    out = jnp.einsum("bhc,chd->bhd", o_c, w_uv.astype(o_c.dtype))
    out = out.reshape(b, 1, h * m.v_head_dim)
    new_cache = {"c_kv": c_cache, "k_rope": kr_cache, "len": pos + 1}
    return linear(out, p["wo"]), new_cache
