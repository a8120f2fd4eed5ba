"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU), with
shape/dtype sweeps per the brief."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gram import cosine_gram_pallas
from repro.kernels.lora_matmul import lora_matmul_pallas
from repro.kernels.selective_scan import selective_scan_pallas

KEY = jax.random.PRNGKey(0)


def rnd(i, shape, dtype=jnp.float32):
    x = jax.random.normal(jax.random.fold_in(KEY, i), shape)
    return x.astype(dtype)


@pytest.mark.parametrize("b,d", [(8, 16), (32, 128), (50, 130), (128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_kernel(b, d, dtype):
    x = rnd(1, (b, d), dtype)
    got = cosine_gram_pallas(x, block=32, interpret=True)
    want = ref.cosine_gram_ref(x)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)


@pytest.mark.parametrize("b,d", [(8, 32), (33, 96)])
def test_gram_kernel_matches_core_cka(b, d):
    """The engine's server-side Gram dispatch target: the Pallas kernel in
    interpret mode must match ``core.cka.cosine_gram`` (the reference the
    engine uses off-TPU) to float32 tolerance."""
    from repro.core.cka import cosine_gram
    x = rnd(17, (b, d))
    got = cosine_gram_pallas(x, block=32, interpret=True)
    want = cosine_gram(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_engine_gram_backend_dispatch():
    """RoundEngine's ``gram_backend='pallas'`` path (interpret mode on CPU)
    must agree with the reference backend through a full engine round."""
    from repro.core.engine import EngineConfig, RoundEngine
    k, ba, dm = 3, 8, 16
    pooled_a = rnd(18, (k, ba, dm))
    ref_eng = RoundEngine(
        EngineConfig(n_nodes=k, local_steps=1, gram_backend="reference"),
        None, lambda *a: None, ({},))
    pal_eng = RoundEngine(
        EngineConfig(n_nodes=k, local_steps=1, gram_backend="pallas"),
        None, lambda *a: None, ({},))
    np.testing.assert_allclose(np.asarray(pal_eng._grams_of(pooled_a)),
                               np.asarray(ref_eng._grams_of(pooled_a)),
                               atol=1e-5)


@pytest.mark.parametrize("m,k,n,r", [(16, 32, 24, 4), (70, 100, 90, 8),
                                     (128, 256, 128, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_matmul_kernel(m, k, n, r, dtype):
    x, w = rnd(2, (m, k), dtype), rnd(3, (k, n), dtype)
    a, b = rnd(4, (k, r), dtype), rnd(5, (r, n), dtype)
    got = lora_matmul_pallas(x, w, a, b, scale=0.7, bm=32, bn=32, bk=64,
                             interpret=True)
    want = ref.lora_matmul_ref(x, w, a, b, 0.7)
    scale = float(jnp.abs(want.astype(jnp.float32)).max()) + 1e-6
    err = float(jnp.abs(got.astype(jnp.float32)
                        - want.astype(jnp.float32)).max()) / scale
    assert err < (1e-5 if dtype == jnp.float32 else 3e-2)


@pytest.mark.parametrize("bh,sq,dh,n_rep", [(4, 64, 32, 1), (8, 100, 32, 2),
                                            (6, 128, 64, 3)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_kernel(bh, sq, dh, n_rep, causal):
    q = rnd(6, (bh, sq, dh))
    k = rnd(7, (bh // n_rep, sq, dh))
    v = rnd(8, (bh // n_rep, sq, dh))
    got = flash_attention_pallas(q, k, v, causal=causal, n_rep=n_rep,
                                 bq=32, bkv=32, interpret=True)
    want = ref.flash_attention_ref(q, jnp.repeat(k, n_rep, 0),
                                   jnp.repeat(v, n_rep, 0), causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_bf16():
    q = rnd(9, (4, 64, 32), jnp.bfloat16)
    k = rnd(10, (4, 64, 32), jnp.bfloat16)
    v = rnd(11, (4, 64, 32), jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, bq=32, bkv=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)


# ----------------------------------------------------------------------
# decode attention: single-token queries against the packed KV pool
SENTINEL = jnp.iinfo(jnp.int32).max // 2


def _pool(i, s_slots, c, n_kv, rep, dh, lens, window=0, dtype=jnp.float32):
    """Build a serving-style pool: slot j holds lens[j] tokens, laid out as
    a ring of width c when window > 0 (entry for position p at slot p % c),
    linear otherwise; empty entries carry the position sentinel."""
    h = n_kv * rep
    q = rnd(100 + i, (s_slots, h, dh), dtype)
    k = rnd(101 + i, (s_slots, c, n_kv, dh), dtype)
    v = rnd(102 + i, (s_slots, c, n_kv, dh), dtype)
    lens = jnp.asarray(lens, jnp.int32)
    slots = jnp.arange(c, dtype=jnp.int32)[None, :]
    if window:
        # ring layout: slot j holds positions p with p % c == slot index
        # and lens[j] - c <= p < lens[j]
        wrap = ((lens[:, None] - 1 - slots) // c) * c + slots
        pos = jnp.where(wrap >= 0, wrap, SENTINEL)
        pos = jnp.where(slots < jnp.minimum(lens[:, None], c), pos, SENTINEL)
        pos = jnp.where(wrap < lens[:, None], pos, SENTINEL)
    else:
        pos = jnp.where(slots < lens[:, None], slots, SENTINEL)
    return q, k, v, lens, pos


@pytest.mark.parametrize("n_kv,rep", [(2, 1), (2, 4), (3, 2)])
def test_decode_attention_gqa_grouping(n_kv, rep):
    """GQA head grouping: query head h must read KV head h // rep."""
    s_slots, c, dh = 3, 40, 32
    q, k, v, lens, pos = _pool(0, s_slots, c, n_kv, rep, dh, [40, 17, 1])
    got = decode_attention_pallas(q, k, v, lens, pos, bkv=16, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lens, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_decode_attention_ring_window():
    """Ring-buffer SWA: positions wrap mod C and only the last ``window``
    are visible; wrapped and unwrapped slots must agree with the oracle."""
    s_slots, c, n_kv, rep, dh, w = 4, 24, 2, 2, 32, 24
    # lens: partially filled, exactly full, wrapped once, wrapped many times
    q, k, v, lens, pos = _pool(7, s_slots, c, n_kv, rep, dh,
                               [9, 24, 31, 100], window=w)
    got = decode_attention_pallas(q, k, v, lens, pos, window=w, bkv=8,
                                  interpret=True)
    want = ref.decode_attention_ref(q, k, v, lens, pos, window=w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_decode_attention_padded_slots():
    """Partially-filled slots: entries beyond each slot's length carry the
    position sentinel and must get exactly zero attention weight."""
    s_slots, c, n_kv, rep, dh = 3, 50, 2, 2, 32
    q, k, v, lens, pos = _pool(13, s_slots, c, n_kv, rep, dh, [1, 13, 50])
    # poison the invalid tail: if masking leaks, the output moves
    bad = jnp.where((pos == SENTINEL)[..., None, None], 1e4, 1.0)
    got = decode_attention_pallas(q, k * bad, v * bad, lens, pos, bkv=16,
                                  interpret=True)
    want = ref.decode_attention_ref(q, k, v, lens, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_decode_attention_matches_blockwise_oracle():
    """The kernel must agree with the model's decode path oracle
    (attention.blockwise_attention with per-slot positions)."""
    from repro.models.attention import blockwise_attention
    # q_pos <= C-1, as in the engine: a linear buffer always has room for
    # the current token, so the un-windowed bound (q_pos - kv_pos < C)
    # never masks a live entry
    s_slots, c, n_kv, rep, dh = 2, 33, 2, 3, 32
    q, k, v, lens, pos = _pool(21, s_slots, c, n_kv, rep, dh, [20, 32])
    got = decode_attention_pallas(q, k, v, lens, pos, bkv=16, interpret=True)
    want = blockwise_attention(q[:, None].reshape(s_slots, 1, n_kv * rep, dh),
                               k, v, kind="causal", window=c,
                               q_positions=lens[:, None], kv_positions=pos)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want[:, 0]), atol=1e-5)


@pytest.mark.parametrize("window", [0, 24], ids=["linear", "ring"])
@pytest.mark.parametrize("layer", [0, 2, 4], ids=["first", "middle", "last"])
def test_decode_attention_stacked_layer(layer, window):
    """With a layer index the kernel reads that layer of the stacked
    (L, S, C, KV, dh) pool in place and equals the single-layer call on
    the layer's slice.  16 slots: two 8-slot groups of positions."""
    n_layers, s_slots, c, n_kv, rep, dh = 5, 16, 24, 2, 2, 32
    top = 100 if window else c
    lens = np.random.default_rng(3).integers(1, top + 1, s_slots)
    # every layer its own K/V and positions, so reading the wrong one shows
    pools = [_pool(40 + 3 * i, s_slots, c, n_kv, rep, dh,
                   np.maximum(lens - i, 1), window=window)
             for i in range(n_layers)]
    q, lens = pools[layer][0], jnp.asarray(lens, jnp.int32)
    k, v, pos = (jnp.stack([p[j] for p in pools]) for j in (1, 2, 4))
    got = decode_attention_pallas(q, k, v, lens, pos, window=window, bkv=8,
                                  layer=jnp.asarray(layer), interpret=True)
    want = decode_attention_pallas(q, k[layer], v[layer], lens, pos[layer],
                                   window=window, bkv=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_decode_attention_bf16():
    s_slots, c, n_kv, rep, dh = 2, 32, 2, 2, 32
    q, k, v, lens, pos = _pool(29, s_slots, c, n_kv, rep, dh, [32, 11],
                               dtype=jnp.bfloat16)
    got = decode_attention_pallas(q, k, v, lens, pos, bkv=16, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lens, pos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)


@pytest.mark.parametrize("b,s,c,chunk", [(2, 37, 45, 16), (1, 64, 32, 32),
                                         (3, 128, 17, 16)])
def test_selective_scan_kernel(b, s, c, chunk):
    da = jax.random.uniform(jax.random.fold_in(KEY, 12), (b, s, c),
                            minval=0.3, maxval=0.99)
    dbx = rnd(13, (b, s, c))
    h0 = rnd(14, (b, c))
    h, hl = selective_scan_pallas(da, dbx, h0, chunk=chunk, bc=16,
                                  interpret=True)
    hr, hlr = ref.selective_scan_ref(da, dbx, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=2e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hlr), rtol=2e-4,
                               atol=1e-5)


def test_selective_scan_matches_model_scan():
    """Kernel agrees with the chunked associative scan used in the model."""
    from repro.models.ssm import _chunked_diag_scan
    da = jax.random.uniform(jax.random.fold_in(KEY, 15), (2, 32, 8),
                            minval=0.5, maxval=0.99)
    dbx = rnd(16, (2, 32, 8))
    h0 = jnp.zeros((2, 8))
    h1, hl1 = _chunked_diag_scan(da, dbx, h0, 8)
    h2, hl2 = ref.selective_scan_ref(da, dbx, h0)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-4,
                               atol=1e-5)
