"""Plain reference of the dense decoder both configurations use (a
llama-style stack: RMSNorm, rotary GQA attention, SwiGLU, optional tied
LM head), written from the published equations in plain
``jax.numpy``: float32, full-precision matmuls, full causal score
matrices, no cache, no kernels.  It imports nothing of the program.

``make_einsum("f32")`` is the reference; ``make_einsum("fp8")`` is the
control, the same code with every matmul operand rounded to float8 e4m3
(one scale per tensor), the precision below the configurations' bf16.

``init_params`` draws weights with the same recipe and the same key
splits as the system's initialiser, so the serving benchmark can make
its weights on the device in one call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8 e4m3fn


def _round_fp8(x):
    """Round to float8 e4m3 with one scale per tensor; the gradient passes
    straight through."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-30) / F8_MAX
    q = (x32 / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x32 + jax.lax.stop_gradient(q - x32)


def make_einsum(mode: str):
    if mode == "f32":
        return lambda eq, a, b: jnp.einsum(
            eq, a.astype(jnp.float32), b.astype(jnp.float32),
            precision=HIGHEST)
    if mode == "fp8":
        return lambda eq, a, b: jnp.einsum(
            eq, _round_fp8(a), _round_fp8(b), precision=HIGHEST)
    raise ValueError(f"unknown precision mode {mode!r}")


# ----------------------------------------------------------------------
def _trunc(key, shape, scale):
    return scale * jax.random.truncated_normal(key, -2.0, 2.0, shape)


def init_params(key, s: dict, dtype=jnp.bfloat16) -> dict:
    """Weights of the dense stack in the system's tree layout: truncated
    normals, 0.02 for the embedding and d_in^-1/2 for every linear, unit
    norms, layers stacked on a leading axis."""
    d, L, h, kvh, dh, f, v = (s["d_model"], s["n_layers"], s["n_heads"],
                              s["n_kv_heads"], s["head_dim"], s["d_ff"],
                              s["vocab_size"])
    ke, kb, kh, _ = jax.random.split(key, 4)

    def lin(k, i, o):
        return {"w": _trunc(k, (i, o), i ** -0.5).astype(dtype)}

    def layer(k):
        k1, k2 = jax.random.split(k)
        kq, kk, kv, ko = jax.random.split(k1, 4)
        kg, ku, kd = jax.random.split(k2, 3)
        return {"ln1": {"scale": jnp.ones((d,), dtype)},
                "attn": {"wq": lin(kq, d, h * dh), "wk": lin(kk, d, kvh * dh),
                         "wv": lin(kv, d, kvh * dh), "wo": lin(ko, h * dh, d)},
                "ln2": {"scale": jnp.ones((d,), dtype)},
                "mlp": {"gate": lin(kg, d, f), "up": lin(ku, d, f),
                        "down": lin(kd, f, d)}}

    p = {"embed": _trunc(ke, (v, d), 0.02).astype(dtype),
         "final_norm": {"scale": jnp.ones((d,), dtype)},
         "blocks": jax.vmap(layer)(jax.random.split(kb, L))}
    if not s.get("tie_embeddings", False):
        p["lm_head"] = lin(kh, d, v)
    return p


# ----------------------------------------------------------------------
def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """Rotary embedding, halves layout: x (..., T, H, dh), pos (T,)."""
    dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def linear(ein, x, p):
    """x @ W."""
    return ein("...i,io->...o", x, p["w"])


def attention(ein, p, x, s):
    b, t, _ = x.shape
    h, kvh, dh = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    pos = jnp.arange(t)
    q = rope(linear(ein, x, p["wq"]).reshape(b, t, h, dh), pos,
             s["rope_theta"])
    k = rope(linear(ein, x, p["wk"]).reshape(b, t, kvh, dh), pos,
             s["rope_theta"])
    v = linear(ein, x, p["wv"]).reshape(b, t, kvh, dh)
    rep = h // kvh
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    sc = ein("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
    sc = jnp.where(pos[None, :] <= pos[:, None], sc, -jnp.inf)
    o = ein("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)
    return linear(ein, o.reshape(b, t, h * dh), p["wo"])


def hidden(ein, blocks, final_norm, x, s):
    """(B, T, D) input embeddings -> (B, T, D) final-normed hidden states,
    one layer at a time."""
    eps = s["norm_eps"]

    def layer(x, bp):
        x = x + attention(ein, bp["attn"], rms_norm(x, bp["ln1"]["scale"],
                                                    eps), s)
        hh = rms_norm(x, bp["ln2"]["scale"], eps)
        m = bp["mlp"]
        g = linear(ein, hh, m["gate"])
        x = x + linear(ein, jax.nn.silu(g) * linear(ein, hh, m["up"]),
                       m["down"])
        return x, None

    x, _ = jax.lax.scan(layer, x.astype(jnp.float32), blocks)
    return rms_norm(x, final_norm["scale"], eps)


def logits(ein, params, tokens, s):
    """(B, T) token ids -> (B, T, V) float32 logits of the next token."""
    x = params["embed"][tokens].astype(jnp.float32)
    hs = hidden(ein, params["blocks"], params["final_norm"], x, s)
    head = (params["embed"].T if s.get("tie_embeddings", False)
            else params["lm_head"]["w"])
    return ein("btd,dv->btv", hs, head)
