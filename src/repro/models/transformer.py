"""The homogeneous transformer: init/forward/prefill/decode for every
assigned architecture family (dense, moe, ssm, hybrid, vlm, audio).

Layers are stacked (leading axis L) and executed with ``lax.scan`` so the
HLO stays compact for 40-64 layer models; ``Runtime.remat`` wraps the scan
body in ``jax.checkpoint`` for training.  Every linear accepts GeoLoRA /
GeoDoRA side-cars (see ``repro.core.lora.attach_lora``), which is how the
paper's technique composes with any backbone.

``prefill`` is a real prefill: the forward scan also emits per-layer cache
entries (rope'd K/V, MLA latents, or recurrent states), which are packed
into the decode cache — windowed attention uses a ring buffer layout.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (
    linear,
    make_linear,
    make_rms_norm,
    make_swiglu,
    mean_pool,
    rms_norm,
    sinusoidal_positions,
    swiglu,
    truncated_normal_init,
)

Array = jax.Array
_SENTINEL = jnp.iinfo(jnp.int32).max // 2


@dataclass(frozen=True)
class Runtime:
    """Execution context threaded through model calls."""
    mesh: Any = None
    ep_axis: Optional[str] = None            # expert-parallel mesh axis
    batch_axes: Tuple[str, ...] = ()
    remat: bool = False
    window_override: int = 0                 # force SWA width (long_500k variant)
    use_pallas: bool = False
    seq_shard: bool = False                  # sequence-parallel residual stream
    kv_block: int = 0                        # attention KV block override
    sp_attn_gather: bool = False             # Megatron-SP gather at attention


def _seq_constraint(x, rt: "Runtime"):
    """Megatron-style sequence parallelism: between layers the residual
    stream (B, S, D) is sharded over (batch axes, 'model', None), so saved
    remat residuals scale with 1/model_parallel.  XLA inserts the
    all-gather before attention/FFN and the reduce-scatter after."""
    if not rt.seq_shard or rt.mesh is None:
        return x
    from jax.sharding import PartitionSpec as P
    if x.ndim != 3 or x.shape[1] % rt.mesh.shape.get("model", 1):
        return x
    bspec = rt.batch_axes if (rt.batch_axes and
                              x.shape[0] % _axes_size(rt) == 0) else None
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(rt.mesh, P(bspec, "model", None)))


def _axes_size(rt: "Runtime") -> int:
    n = 1
    for a in rt.batch_axes:
        n *= rt.mesh.shape.get(a, 1)
    return n


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _attn_kind(cfg: ModelConfig, rt: Runtime) -> Tuple[str, int]:
    if rt.window_override:
        return "sliding", rt.window_override
    if cfg.sliding_window:
        return "sliding", cfg.sliding_window
    if cfg.attention_chunk:
        return "chunked", cfg.attention_chunk
    return "causal", 0


# ======================================================================
# init
def _stack_init(key, n: int, fn):
    return jax.vmap(fn)(jax.random.split(key, n))


def _make_dense_block(cfg: ModelConfig, dtype):
    def f(k):
        k1, k2 = jax.random.split(k)
        return {
            "ln1": make_rms_norm(cfg.d_model, dtype),
            "attn": attn.make_gqa(k1, cfg, dtype),
            "ln2": make_rms_norm(cfg.d_model, dtype),
            "mlp": make_swiglu(k2, cfg.d_model, cfg.d_ff, dtype),
        }
    return f


def _make_moe_block(cfg: ModelConfig, dtype):
    def f(k):
        k1, k2 = jax.random.split(k)
        return {
            "ln1": make_rms_norm(cfg.d_model, dtype),
            "attn": (attn.make_mla(k1, cfg, dtype) if cfg.mla is not None
                     else attn.make_gqa(k1, cfg, dtype)),
            "ln2": make_rms_norm(cfg.d_model, dtype),
            "moe": moe_mod.make_moe(k2, cfg, dtype),
        }
    return f


def _make_ssm_block(cfg: ModelConfig, dtype):
    def f(k):
        return {"ln": make_rms_norm(cfg.d_model, dtype),
                "mixer": ssm_mod.make_mamba(k, cfg, dtype)}
    return f


def _make_hybrid_rec_block(cfg: ModelConfig, dtype):
    def f(k):
        k1, k2 = jax.random.split(k)
        return {
            "ln1": make_rms_norm(cfg.d_model, dtype),
            "mixer": rglru_mod.make_rglru_block(k1, cfg, dtype),
            "ln2": make_rms_norm(cfg.d_model, dtype),
            "mlp": make_swiglu(k2, cfg.d_model, cfg.d_ff, dtype),
        }
    return f


def _make_dec_block(cfg: ModelConfig, dtype):
    def f(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "ln1": make_rms_norm(cfg.d_model, dtype),
            "self_attn": attn.make_gqa(k1, cfg, dtype),
            "ln2": make_rms_norm(cfg.d_model, dtype),
            "cross_attn": attn.make_gqa(k2, cfg, dtype),
            "ln3": make_rms_norm(cfg.d_model, dtype),
            "mlp": make_swiglu(k3, cfg.d_model, cfg.d_ff, dtype),
        }
    return f


def init_params(key, cfg: ModelConfig) -> dict:
    dtype = _dtype(cfg)
    ke, kb, kh, kx = jax.random.split(key, 4)
    p: Dict[str, Any] = {
        "embed": truncated_normal_init(ke, (cfg.vocab_size, cfg.d_model),
                                       dtype=dtype),
        "final_norm": make_rms_norm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = make_linear(kh, cfg.d_model, cfg.vocab_size, dtype)

    fam = cfg.family
    if fam in ("dense", "vlm"):
        p["blocks"] = _stack_init(kb, cfg.n_layers, _make_dense_block(cfg, dtype))
        if fam == "vlm":
            p["adapter"] = make_linear(kx, cfg.image_embed_dim, cfg.d_model, dtype)
    elif fam == "moe":
        p["blocks"] = _stack_init(kb, cfg.n_layers, _make_moe_block(cfg, dtype))
    elif fam == "ssm":
        p["blocks"] = _stack_init(kb, cfg.n_layers, _make_ssm_block(cfg, dtype))
    elif fam == "hybrid":
        pat = cfg.rglru.block_pattern
        n_groups, tail_n = divmod(cfg.n_layers, len(pat))
        kg, kt = jax.random.split(kb)

        def group_init(k):
            ks = jax.random.split(k, len(pat))
            return {f"b{i}": (_make_hybrid_rec_block(cfg, dtype)(ks[i])
                              if pat[i] == "recurrent"
                              else _make_dense_block(cfg, dtype)(ks[i]))
                    for i in range(len(pat))}
        p["groups"] = _stack_init(kg, n_groups, group_init)
        kts = jax.random.split(kt, max(tail_n, 1))
        p["tail"] = [
            (_make_hybrid_rec_block(cfg, dtype)(kts[i])
             if pat[i % len(pat)] == "recurrent"
             else _make_dense_block(cfg, dtype)(kts[i]))
            for i in range(tail_n)]
    elif fam == "audio":
        kenc, kdec = jax.random.split(kb)
        p["enc_blocks"] = _stack_init(kenc, cfg.n_encoder_layers,
                                      _make_dense_block(cfg, dtype))
        p["blocks"] = _stack_init(kdec, cfg.n_layers, _make_dec_block(cfg, dtype))
        p["enc_adapter"] = make_linear(kx, cfg.encoder_embed_dim, cfg.d_model,
                                       dtype)
        p["enc_norm"] = make_rms_norm(cfg.d_model, dtype)
    else:
        raise ValueError(fam)
    return p


# ======================================================================
# ring-buffer packing for windowed caches
def _ring_pack(x: Array, s: int, w: int, fill=0):
    """Pack the last min(s, w) entries of x (B, S, ...) into ring layout of
    width w where entry for position p sits at slot p % w."""
    if s >= w:
        last = x[:, s - w:]
        return jnp.roll(last, s % w, axis=1)
    pad_cfg = [(0, 0), (0, w - s)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad_cfg, constant_values=fill)


# ======================================================================
# block bodies. Each returns (x, aux) with aux = {"lb", "rz", "cache"}.
def _zero_aux(cache=None):
    return {"lb": jnp.zeros((), jnp.float32),
            "rz": jnp.zeros((), jnp.float32),
            "cache": cache}


def _attn_gather(x, rt):
    """Megatron-SP attention entry: force the block input to full sequence
    (replicated over 'model') so attention runs purely head-sharded; the
    exit _seq_constraint turns the output psum into a reduce-scatter.
    Without this, t-sharded queries force per-KV-block dK/dV all-reduces in
    the backward (measured: §Perf iter 5)."""
    if not rt.seq_shard or not rt.sp_attn_gather or rt.mesh is None \
            or x.ndim != 3:
        return x
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    bspec = rt.batch_axes if (rt.batch_axes and
                              x.shape[0] % _axes_size(rt) == 0) else None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rt.mesh, P(bspec, None, None)))


def _dense_body(cfg, rt, kind, window, collect: bool):
    def body(x, bp, positions):
        h = _attn_gather(rms_norm(x, bp["ln1"]["scale"], cfg.norm_eps), rt)
        if cfg.mla is not None:
            r = attn.mla_forward(bp["attn"], h, cfg, positions=positions,
                                 return_kv=collect, rt=rt)
        else:
            r = attn.gqa_forward(bp["attn"], h, cfg, kind=kind, window=window,
                                 positions=positions, return_kv=collect,
                                 rt=rt)
        h, kv = r if collect else (r, None)
        x = x + h
        h = rms_norm(x, bp["ln2"]["scale"], cfg.norm_eps)
        if "moe" in bp:
            h, moe_aux = moe_mod.moe_ffn(bp["moe"], h, cfg, mesh=rt.mesh,
                                         ep_axis=rt.ep_axis,
                                         batch_axes=rt.batch_axes)
            aux = _zero_aux(kv)
            aux["lb"] = moe_aux["load_balance"]
            aux["rz"] = moe_aux["router_z"]
        else:
            h, aux = swiglu(bp["mlp"], h), _zero_aux(kv)
        return x + h, aux
    return body


def _ssm_body(cfg, collect: bool):
    def body(x, bp, positions):
        h = rms_norm(x, bp["ln"]["scale"], cfg.norm_eps)
        y, state = ssm_mod.mamba_forward(bp["mixer"], h, cfg)
        return x + y, _zero_aux(state if collect else None)
    return body


def _hybrid_rec_body(cfg, collect: bool):
    def body(x, bp, positions):
        h = rms_norm(x, bp["ln1"]["scale"], cfg.norm_eps)
        y, state = rglru_mod.rglru_forward(bp["mixer"], h, cfg)
        x = x + y
        h = rms_norm(x, bp["ln2"]["scale"], cfg.norm_eps)
        return x + swiglu(bp["mlp"], h), _zero_aux(state if collect else None)
    return body


def _hybrid_attn_body(cfg, collect: bool, rt=None):
    w = cfg.rglru.local_window

    def body(x, bp, positions):
        h = rms_norm(x, bp["ln1"]["scale"], cfg.norm_eps)
        r = attn.gqa_forward(bp["attn"], h, cfg, kind="sliding", window=w,
                             positions=positions, return_kv=collect, rt=rt)
        h, kv = r if collect else (r, None)
        x = x + h
        h = rms_norm(x, bp["ln2"]["scale"], cfg.norm_eps)
        return x + swiglu(bp["mlp"], h), _zero_aux(kv)
    return body


# ======================================================================
def _run_stack(blocks, body, x, positions, remat: bool,
               rt: "Runtime" = None):
    def scan_body(carry, bp):
        y, aux = body(carry, bp, positions)
        if rt is not None:
            y = _seq_constraint(y, rt)
        return y, aux
    if remat:
        scan_body = jax.checkpoint(scan_body)
    x, aux = jax.lax.scan(scan_body, x, blocks)
    return x, aux


def _embed_inputs(params, batch, cfg: ModelConfig):
    if "inputs_embeds" in batch:                  # paper's adapter path
        x = batch["inputs_embeds"].astype(_dtype(cfg))
    else:
        x = params["embed"][batch["tokens"]]
    if cfg.family == "vlm" and "image_embeds" in batch:
        img = linear(batch["image_embeds"].astype(x.dtype), params["adapter"])
        x = jnp.concatenate([img, x], axis=1)
    b, s = x.shape[:2]
    positions = jnp.arange(s, dtype=jnp.int32)[None].repeat(b, 0)
    return x, positions


def _encoder_forward(params, batch, cfg: ModelConfig, rt: Runtime) -> Array:
    x = linear(batch["enc_embeds"].astype(_dtype(cfg)), params["enc_adapter"])
    s = x.shape[1]
    x = x + sinusoidal_positions(s, cfg.d_model).astype(x.dtype)[None]
    positions = jnp.arange(s, dtype=jnp.int32)[None].repeat(x.shape[0], 0)

    def body(h, bp, pos):
        a = rms_norm(h, bp["ln1"]["scale"], cfg.norm_eps)
        a = attn.gqa_forward(bp["attn"], a, cfg, kind="full", positions=pos,
                             rope=False)
        h = h + a
        m = rms_norm(h, bp["ln2"]["scale"], cfg.norm_eps)
        return h + swiglu(bp["mlp"], m), _zero_aux()
    x, _ = _run_stack(params["enc_blocks"], body, x, positions, rt.remat,
                      rt)
    return rms_norm(x, params["enc_norm"]["scale"], cfg.norm_eps)


def _dec_body(cfg, enc_out, collect: bool):
    def body(h, bp, pos):
        a = rms_norm(h, bp["ln1"]["scale"], cfg.norm_eps)
        r = attn.gqa_forward(bp["self_attn"], a, cfg, kind="causal",
                             positions=pos, rope=False, return_kv=collect)
        a, kv = r if collect else (r, None)
        h = h + a
        c = rms_norm(h, bp["ln2"]["scale"], cfg.norm_eps)
        c = attn.gqa_forward(bp["cross_attn"], c, cfg, x_cross=enc_out,
                             positions=pos)
        h = h + c
        m = rms_norm(h, bp["ln3"]["scale"], cfg.norm_eps)
        cache = None
        if collect:
            cross = attn.precompute_cross_kv(bp["cross_attn"], enc_out, cfg)
            cache = {"k": kv["k"], "v": kv["v"],
                     "cross_k": cross["k"], "cross_v": cross["v"]}
        return h + swiglu(bp["mlp"], m), _zero_aux(cache)
    return body


def _forward_impl(params: dict, batch: dict, cfg: ModelConfig, rt: Runtime,
                  collect: bool):
    fam = cfg.family
    kind, window = _attn_kind(cfg, rt)
    tails_aux = []

    if fam == "audio":
        enc_out = _encoder_forward(params, batch, cfg, rt)
        x = params["embed"][batch["tokens"]]
        s = x.shape[1]
        x = x + sinusoidal_positions(s, cfg.d_model).astype(x.dtype)[None]
        positions = jnp.arange(s, dtype=jnp.int32)[None].repeat(x.shape[0], 0)
        x, aux = _run_stack(params["blocks"], _dec_body(cfg, enc_out, collect),
                            x, positions, rt.remat, rt)
    elif fam == "hybrid":
        x, positions = _embed_inputs(params, batch, cfg)
        pat = cfg.rglru.block_pattern
        rec_body = _hybrid_rec_body(cfg, collect)
        att_body = _hybrid_attn_body(cfg, collect, rt)

        def group_body(h, gp, pos):
            caches = {}
            lb = jnp.zeros((), jnp.float32)
            for i, kind_i in enumerate(pat):
                body_i = rec_body if kind_i == "recurrent" else att_body
                h, a = body_i(h, gp[f"b{i}"], pos)
                caches[f"b{i}"] = a["cache"]
            out_aux = _zero_aux(caches if collect else None)
            return h, out_aux
        x, aux = _run_stack(params["groups"], group_body, x, positions,
                            rt.remat, rt)
        for i, bp in enumerate(params["tail"]):
            body_i = rec_body if pat[i % len(pat)] == "recurrent" else att_body
            x, a = body_i(x, bp, positions)
            tails_aux.append(a)
    else:
        x, positions = _embed_inputs(params, batch, cfg)
        body = (_ssm_body(cfg, collect) if fam == "ssm"
                else _dense_body(cfg, rt, kind, window, collect))
        x, aux = _run_stack(params["blocks"], body, x, positions, rt.remat,
                            rt)

    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    out_aux = {
        "load_balance": aux["lb"].mean(),
        "router_z": aux["rz"].mean(),
        "pooled": mean_pool(x),
        "_cache": aux["cache"],
        "_tail_caches": [a["cache"] for a in tails_aux],
    }
    if cfg.family == "vlm" and "image_embeds" in batch:
        x = x[:, batch["image_embeds"].shape[1]:]
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T.astype(x.dtype)
    else:
        logits = linear(x, params["lm_head"])
    return logits, out_aux


def forward(params: dict, batch: dict, cfg: ModelConfig,
            rt: Runtime = Runtime()) -> Tuple[Array, dict]:
    """Full-sequence forward -> (logits, aux). aux['pooled'] (B, d_model)
    feeds the paper's Gram/CKA alignment."""
    logits, aux = _forward_impl(params, batch, cfg, rt, collect=False)
    aux.pop("_cache"), aux.pop("_tail_caches")
    return logits, aux


# ======================================================================
# prefill: forward + pack the collected per-layer caches for decode
def prefill(params: dict, batch: dict, cfg: ModelConfig,
            rt: Runtime = Runtime(), cache_len: Optional[int] = None
            ) -> Tuple[Array, dict]:
    """Prefill: forward + pack per-layer caches, with room to decode up to
    ``cache_len`` total positions (defaults to S + 1024)."""
    logits, aux = _forward_impl(params, batch, cfg, rt, collect=True)
    raw, tails = aux.pop("_cache"), aux.pop("_tail_caches")
    fam = cfg.family
    kind, window = _attn_kind(cfg, rt)

    def grow(x, target, axis, fill=0):
        if x.shape[axis] >= target:
            return x
        cfg_pad = [(0, 0)] * x.ndim
        cfg_pad[axis] = (0, target - x.shape[axis])
        return jnp.pad(x, cfg_pad, constant_values=fill)

    def pack_kv(kv, w, target):
        """kv leaves (L, B, S, ...) -> ring/full cache + pos."""
        s = kv["k"].shape[2]
        b = kv["k"].shape[1]
        pos_vals = jnp.arange(s, dtype=jnp.int32)[None].repeat(b, 0)
        if w:
            pk = jax.vmap(lambda t: _ring_pack(t, s, w))(kv["k"])
            pv = jax.vmap(lambda t: _ring_pack(t, s, w))(kv["v"])
            pp1 = _ring_pack(pos_vals, s, w, fill=_SENTINEL)      # (B, w)
        else:
            pk = grow(kv["k"], target, 2)
            pv = grow(kv["v"], target, 2)
            pp1 = grow(pos_vals, target, 1, fill=_SENTINEL)
        pp = jnp.broadcast_to(pp1, (kv["k"].shape[0],) + pp1.shape)
        return {"k": pk, "v": pv, "pos": pp}

    s_len = logits.shape[1]
    if fam == "vlm" and "image_embeds" in batch:
        s_len = s_len + batch["image_embeds"].shape[1]
    target = cache_len if cache_len is not None else s_len + 1024
    if fam in ("dense", "vlm", "moe") and cfg.mla is None:
        cache = pack_kv(raw, window, target)
    elif fam == "moe":                          # MLA
        cache = {"c_kv": grow(raw["c_kv"], target, 2),
                 "k_rope": grow(raw["k_rope"], target, 2)}
    elif fam == "ssm":
        cache = raw                              # stacked states (L, B, ...)
    elif fam == "hybrid":
        pat = cfg.rglru.block_pattern
        w = cfg.rglru.local_window
        groups = {}
        for i, kind_i in enumerate(pat):
            groups[f"b{i}"] = (raw[f"b{i}"] if kind_i == "recurrent"
                               else pack_kv(raw[f"b{i}"], w, target))
        tail = []
        for i, tc in enumerate(tails):
            if pat[i % len(pat)] == "recurrent":
                tail.append(tc)
            else:
                one = {k: v[None] for k, v in tc.items()}
                packed = pack_kv(one, w, target)
                tail.append({k: v[0] for k, v in packed.items()})
        cache = {"groups": groups, "tail": tail}
    elif fam == "audio":
        cache = pack_kv({"k": raw["k"], "v": raw["v"]}, 0, target)
        cache["cross_k"] = raw["cross_k"]
        cache["cross_v"] = raw["cross_v"]
    else:
        raise ValueError(fam)
    cache["len"] = jnp.asarray(s_len, jnp.int32)
    return logits, cache


# ======================================================================
# decode
def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               rt: Runtime = Runtime()) -> dict:
    dtype = _dtype(cfg)
    fam = cfg.family
    L = cfg.n_layers
    kind, window = _attn_kind(cfg, rt)
    eff_len = min(cache_len, window) if window else cache_len

    def kv(n, b, length, n_kv):
        shape = (n, b, length, n_kv, cfg.head_dim) if n else \
            (b, length, n_kv, cfg.head_dim)
        pshape = (n, b, length) if n else (b, length)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                "pos": jnp.full(pshape, _SENTINEL, jnp.int32)}

    if fam in ("dense", "vlm") or (fam == "moe" and cfg.mla is None):
        c = kv(L, batch, eff_len, cfg.n_kv_heads)
    elif fam == "moe":
        m = cfg.mla
        c = {"c_kv": jnp.zeros((L, batch, eff_len, m.kv_lora_rank), dtype),
             "k_rope": jnp.zeros((L, batch, eff_len, m.rope_head_dim), dtype)}
    elif fam == "ssm":
        st = ssm_mod.init_mamba_state(batch, cfg, dtype)
        c = {k: jnp.broadcast_to(v, (L,) + v.shape).copy()
             for k, v in st.items()}
    elif fam == "hybrid":
        pat = cfg.rglru.block_pattern
        n_groups, tail_n = divmod(cfg.n_layers, len(pat))
        w = rglru_mod.lru_width(cfg)
        alen = min(cache_len, cfg.rglru.local_window)

        def rec_state(n):
            shape_h = (n, batch, w) if n else (batch, w)
            shape_c = ((n, batch, cfg.rglru.conv_kernel - 1, w) if n
                       else (batch, cfg.rglru.conv_kernel - 1, w))
            return {"h": jnp.zeros(shape_h, jnp.float32),
                    "conv": jnp.zeros(shape_c, dtype)}
        groups = {f"b{i}": (rec_state(n_groups) if pat[i] == "recurrent"
                            else kv(n_groups, batch, alen, cfg.n_kv_heads))
                  for i in range(len(pat))}
        tail = [(rec_state(0) if pat[i % len(pat)] == "recurrent"
                 else kv(0, batch, alen, cfg.n_kv_heads))
                for i in range(tail_n)]
        c = {"groups": groups, "tail": tail}
    elif fam == "audio":
        c = kv(L, batch, eff_len, cfg.n_kv_heads)
        c["cross_k"] = jnp.zeros((L, batch, cfg.encoder_seq_len,
                                  cfg.n_kv_heads, cfg.head_dim), dtype)
        c["cross_v"] = jnp.zeros_like(c["cross_k"])
    else:
        raise ValueError(fam)
    c["len"] = jnp.zeros((), jnp.int32)
    return c


def decode_step(params: dict, cache: dict, batch: dict, cfg: ModelConfig,
                rt: Runtime = Runtime()) -> Tuple[Array, dict]:
    """One new token for every sequence. batch: {'tokens': (B, 1)}."""
    fam = cfg.family
    kind, window = _attn_kind(cfg, rt)
    x = params["embed"][batch["tokens"]]
    pos = cache["len"]

    if fam == "audio":
        x = x + jax.lax.dynamic_slice_in_dim(
            sinusoidal_positions(65536, cfg.d_model), pos, 1,
            axis=0).astype(x.dtype)[None]

        def body(h, layer):
            bp, kc, vc, pc, ck, cv = layer
            a = rms_norm(h, bp["ln1"]["scale"], cfg.norm_eps)
            lc = {"k": kc, "v": vc, "pos": pc, "len": pos}
            a, nc = attn.gqa_decode(bp["self_attn"], a, lc, cfg, kind="causal")
            h = h + a
            c = rms_norm(h, bp["ln2"]["scale"], cfg.norm_eps)
            c = attn.gqa_cross_decode(bp["cross_attn"], c,
                                      {"k": ck, "v": cv}, cfg)
            h = h + c
            m = rms_norm(h, bp["ln3"]["scale"], cfg.norm_eps)
            h = h + swiglu(bp["mlp"], m)
            return h, (nc["k"], nc["v"], nc["pos"])
        x, (nk, nv, np_) = jax.lax.scan(
            body, x, (params["blocks"], cache["k"], cache["v"], cache["pos"],
                      cache["cross_k"], cache["cross_v"]))
        new_cache = dict(cache, k=nk, v=nv, pos=np_, len=pos + 1)
    elif fam == "ssm":
        def body(h, layer):
            bp, hs, cs = layer
            a = rms_norm(h, bp["ln"]["scale"], cfg.norm_eps)
            y, ns = ssm_mod.mamba_decode(bp["mixer"], a, {"h": hs, "conv": cs},
                                         cfg)
            return h + y, (ns["h"], ns["conv"])
        x, (nh, nc) = jax.lax.scan(body, x,
                                   (params["blocks"], cache["h"], cache["conv"]))
        new_cache = dict(cache, h=nh, conv=nc, len=pos + 1)
    elif fam == "hybrid":
        pat = cfg.rglru.block_pattern
        w = cfg.rglru.local_window

        def rec_step(h, bp, st):
            a = rms_norm(h, bp["ln1"]["scale"], cfg.norm_eps)
            y, ns = rglru_mod.rglru_decode(bp["mixer"], a, st, cfg)
            h = h + y
            m = rms_norm(h, bp["ln2"]["scale"], cfg.norm_eps)
            return h + swiglu(bp["mlp"], m), ns

        def att_step(h, bp, st):
            a = rms_norm(h, bp["ln1"]["scale"], cfg.norm_eps)
            lc = dict(st, len=pos)
            a, nc = attn.gqa_decode(bp["attn"], a, lc, cfg, kind="sliding",
                                    window=w)
            h = h + a
            m = rms_norm(h, bp["ln2"]["scale"], cfg.norm_eps)
            nc.pop("len")
            return h + swiglu(bp["mlp"], m), nc

        def group_body(h, layer):
            gp, gc = layer
            ncs = {}
            for i, kind_i in enumerate(pat):
                step = rec_step if kind_i == "recurrent" else att_step
                h, ncs[f"b{i}"] = step(h, gp[f"b{i}"], gc[f"b{i}"])
            return h, ncs
        x, new_groups = jax.lax.scan(group_body, x,
                                     (params["groups"], cache["groups"]))
        new_tail = []
        for i, bp in enumerate(params["tail"]):
            step = rec_step if pat[i % len(pat)] == "recurrent" else att_step
            x, nc = step(x, bp, cache["tail"][i])
            new_tail.append(nc)
        new_cache = dict(cache, groups=new_groups, tail=new_tail, len=pos + 1)
    else:  # dense / vlm / moe
        is_mla = cfg.mla is not None

        def body(h, layer):
            if is_mla:
                bp, ck, kr = layer
                lc = {"c_kv": ck, "k_rope": kr, "len": pos}
            else:
                bp, kc, vc, pc = layer
                lc = {"k": kc, "v": vc, "pos": pc, "len": pos}
            a = rms_norm(h, bp["ln1"]["scale"], cfg.norm_eps)
            if is_mla:
                a, nc = attn.mla_decode(bp["attn"], a, lc, cfg, rt=rt)
                out_c = (nc["c_kv"], nc["k_rope"])
            else:
                a, nc = attn.gqa_decode(bp["attn"], a, lc, cfg, kind=kind,
                                        window=window, rt=rt)
                out_c = (nc["k"], nc["v"], nc["pos"])
            h = h + a
            m = rms_norm(h, bp["ln2"]["scale"], cfg.norm_eps)
            if "moe" in bp:
                y, _ = moe_mod.moe_ffn(bp["moe"], m, cfg, mesh=rt.mesh,
                                       ep_axis=rt.ep_axis,
                                       batch_axes=rt.batch_axes)
            else:
                y = swiglu(bp["mlp"], m)
            return h + y, out_c

        if is_mla:
            xs = (params["blocks"], cache["c_kv"], cache["k_rope"])
            x, (nck, nkr) = jax.lax.scan(body, x, xs)
            new_cache = dict(cache, c_kv=nck, k_rope=nkr, len=pos + 1)
        else:
            xs = (params["blocks"], cache["k"], cache["v"], cache["pos"])
            x, (nk, nv, np_) = jax.lax.scan(body, x, xs)
            new_cache = dict(cache, k=nk, v=nv, pos=np_, len=pos + 1)

    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T.astype(x.dtype)
    else:
        logits = linear(x, params["lm_head"])
    return logits, new_cache


def decode_step_slots(params: dict, cache: dict, batch: dict,
                      cfg: ModelConfig, rt: Runtime = Runtime(), *,
                      step_mask: Optional[Array] = None,
                      attn_backend: str = "reference",
                      attn_interpret: bool = False) -> Tuple[Array, dict]:
    """One new token per SLOT, each slot at its own position (the serving
    cache pool's decode path).

    Unlike ``decode_step`` (one scalar ``cache['len']`` for the whole
    batch), ``cache['len']`` is (S,) int32 — slot s reads/writes its
    caches at position ``len[s]``, so freshly-admitted prompts and
    long-running decodes share one batched call without recompiling.
    ``step_mask`` (S,) bool freezes masked slots IN PLACE: their cache
    position does not advance, and recurrent state (SSM ``h``/``conv``,
    RG-LRU) is held — attention writes at a frozen position are
    idempotent, but a recurrent update is not, and the serving engine
    unmasks slots that later resume (deadline-cancelled or chaos-frozen
    slots), which must continue bit-identically.
    ``attn_backend='pallas'`` routes GQA slot attention to
    ``kernels.decode_attention`` (interpret mode off-TPU).
    """
    fam = cfg.family
    kind, window = _attn_kind(cfg, rt)
    x = params["embed"][batch["tokens"]]
    lens = cache["len"]                                  # (S,) int32
    akw = dict(backend=attn_backend, interpret=attn_interpret)

    def keep(new, old):
        """Hold recurrent state for masked slots (slot axis 0)."""
        if step_mask is None:
            return new
        m = step_mask.reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(m, new, old)

    if fam == "audio":
        x = x + sinusoidal_positions(65536, cfg.d_model)[lens][:, None] \
            .astype(x.dtype)

        def body(h, layer):
            bp, kc, vc, pc, ck, cv = layer
            a = rms_norm(h, bp["ln1"]["scale"], cfg.norm_eps)
            lc = {"k": kc, "v": vc, "pos": pc, "lens": lens}
            a, nc = attn.gqa_decode_slots(bp["self_attn"], a, lc, cfg,
                                          kind="causal", **akw)
            h = h + a
            c = rms_norm(h, bp["ln2"]["scale"], cfg.norm_eps)
            c = attn.gqa_cross_decode(bp["cross_attn"], c,
                                      {"k": ck, "v": cv}, cfg)
            h = h + c
            m = rms_norm(h, bp["ln3"]["scale"], cfg.norm_eps)
            h = h + swiglu(bp["mlp"], m)
            return h, (nc["k"], nc["v"], nc["pos"])
        x, (nk, nv, np_) = jax.lax.scan(
            body, x, (params["blocks"], cache["k"], cache["v"], cache["pos"],
                      cache["cross_k"], cache["cross_v"]))
        new_cache = dict(cache, k=nk, v=nv, pos=np_)
    elif fam == "ssm":
        def body(h, layer):
            bp, hs, cs = layer
            a = rms_norm(h, bp["ln"]["scale"], cfg.norm_eps)
            y, ns = ssm_mod.mamba_decode(bp["mixer"], a, {"h": hs, "conv": cs},
                                         cfg)
            return h + y, (keep(ns["h"], hs), keep(ns["conv"], cs))
        x, (nh, nc) = jax.lax.scan(body, x,
                                   (params["blocks"], cache["h"], cache["conv"]))
        new_cache = dict(cache, h=nh, conv=nc)
    elif fam == "hybrid":
        pat = cfg.rglru.block_pattern
        w = cfg.rglru.local_window

        def rec_step(h, bp, st):
            a = rms_norm(h, bp["ln1"]["scale"], cfg.norm_eps)
            y, ns = rglru_mod.rglru_decode(bp["mixer"], a, st, cfg)
            h = h + y
            m = rms_norm(h, bp["ln2"]["scale"], cfg.norm_eps)
            return h + swiglu(bp["mlp"], m), jax.tree.map(keep, ns, st)

        def att_step(h, bp, st):
            a = rms_norm(h, bp["ln1"]["scale"], cfg.norm_eps)
            lc = dict(st, lens=lens)
            a, nc = attn.gqa_decode_slots(bp["attn"], a, lc, cfg,
                                          kind="sliding", window=w, **akw)
            h = h + a
            m = rms_norm(h, bp["ln2"]["scale"], cfg.norm_eps)
            nc.pop("lens")
            return h + swiglu(bp["mlp"], m), nc

        def group_body(h, layer):
            gp, gc = layer
            ncs = {}
            for i, kind_i in enumerate(pat):
                step = rec_step if kind_i == "recurrent" else att_step
                h, ncs[f"b{i}"] = step(h, gp[f"b{i}"], gc[f"b{i}"])
            return h, ncs
        x, new_groups = jax.lax.scan(group_body, x,
                                     (params["groups"], cache["groups"]))
        new_tail = []
        for i, bp in enumerate(params["tail"]):
            step = rec_step if pat[i % len(pat)] == "recurrent" else att_step
            x, nc = step(x, bp, cache["tail"][i])
            new_tail.append(nc)
        new_cache = dict(cache, groups=new_groups, tail=new_tail)
    else:  # dense / vlm / moe
        def block(h, bp, attend):
            with jax.named_scope("attention"):
                a = rms_norm(h, bp["ln1"]["scale"], cfg.norm_eps)
                a, nc = attend(bp["attn"], a)
            h = h + a
            with jax.named_scope("mlp"):
                m = rms_norm(h, bp["ln2"]["scale"], cfg.norm_eps)
                if "moe" in bp:
                    y, _ = moe_mod.moe_ffn(bp["moe"], m, cfg, mesh=rt.mesh,
                                           ep_axis=rt.ep_axis,
                                           batch_axes=rt.batch_axes)
                else:
                    y = swiglu(bp["mlp"], m)
            return h + y, nc

        with jax.named_scope("layers"):
            if cfg.mla is not None:
                def body(h, layer):
                    bp, ck, kr = layer
                    lc = {"c_kv": ck, "k_rope": kr, "lens": lens}
                    h, nc = block(h, bp, lambda p, a: attn.mla_decode_slots(
                        p, a, lc, cfg, rt=rt))
                    return h, (nc["c_kv"], nc["k_rope"])
                xs = (params["blocks"], cache["c_kv"], cache["k_rope"])
                x, (nck, nkr) = jax.lax.scan(body, x, xs)
                new_cache = dict(cache, c_kv=nck, k_rope=nkr)
            else:
                # The stacked pool rides in the carry and is updated in
                # place: layer i writes only its new token's row at
                # [i, slot], and the decode kernel takes the whole pool
                # and the layer index, because a slice of the carry fed
                # to a custom call would be materialised each layer.
                def body(carry, layer):
                    h, kc, vc, pc = carry
                    bp, i = layer
                    lc = {"k": kc, "v": vc, "pos": pc, "lens": lens}
                    h, nc = block(h, bp, lambda p, a: attn.gqa_decode_slots(
                        p, a, lc, cfg, kind=kind, window=window, rt=rt,
                        layer=i, **akw))
                    return (h, nc["k"], nc["v"], nc["pos"]), None
                carry = (x, cache["k"], cache["v"], cache["pos"])
                xs = (params["blocks"], jnp.arange(cache["k"].shape[0]))
                (x, nk, nv, np_), _ = jax.lax.scan(body, carry, xs)
                new_cache = dict(cache, k=nk, v=nv, pos=np_)

    new_lens = lens + 1 if step_mask is None \
        else jnp.where(step_mask, lens + 1, lens)
    new_cache["len"] = new_lens
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = x @ params["embed"].T.astype(x.dtype)
        else:
            logits = linear(x, params["lm_head"])
    return logits, new_cache


__all__ = ["Runtime", "init_params", "forward", "decode_step",
           "decode_step_slots", "prefill", "init_cache"]
