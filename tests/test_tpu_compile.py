"""Pallas kernels compiled for a described TPU v5e, with no chip attached.

Interpret mode (the rest of the kernel tests) cannot see the TPU's tiling
and memory rules; the chip's compiler can, and it is installed here.  Each
test lowers one kernel at real widths for one described v5e chip and
asserts that the compiled program really holds the Mosaic kernel
(``tpu_custom_call``) rather than an interpreted loop.  Nothing runs, so
these say nothing about results or times.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gram import cosine_gram_pallas
from repro.kernels.lora_matmul import lora_matmul_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_gram_vmapped_over_nodes(one_chip):
    """The federation server step's Gram: vmapped over 4 nodes at
    fedmm-small width (32 anchors x d_model 768)."""
    txt = _compiled_text(jax.vmap(cosine_gram_pallas), one_chip,
                         ((4, 32, 768), jnp.float32))
    assert "tpu_custom_call" in txt


# (layers, slots, cache_len, q heads, KV heads, head_dim, window):
# smollm-135m and fedmm-small at the serving pool's shape, a ring-buffer
# window, and smollm-135m's whole stacked pool (layers > 0) read at a
# traced layer index, as the decode step's layer scan calls the kernel
DECODE_CASES = {
    "smollm_135m": (0, 8, 1024, 9, 3, 64, 0),
    "fedmm_small": (0, 8, 1024, 12, 4, 64, 0),
    "smollm_135m_ring": (0, 8, 1024, 9, 3, 64, 1024),
    "smollm_135m_stacked": (30, 48, 2048, 9, 3, 64, 0),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_decode_attention(one_chip, case):
    n_layers, s, c, h, kv, dh, window = DECODE_CASES[case]
    lead = (n_layers,) if n_layers else ()
    shapes = [((s, h, dh), jnp.bfloat16),
              (lead + (s, c, kv, dh), jnp.bfloat16),
              (lead + (s, c, kv, dh), jnp.bfloat16),
              ((s,), jnp.int32), (lead + (s, c), jnp.int32)]
    if n_layers:
        fn = lambda q, k, v, qp, kp, layer: decode_attention_pallas(
            q, k, v, qp, kp, layer=layer)
        shapes.append(((), jnp.int32))
    else:
        fn = functools.partial(decode_attention_pallas, window=window)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


def test_serve_decode_block_keeps_pool_in_place(one_chip):
    """The serving engine's decode block at smollm-135m widths (2 layers,
    16 slots x 2048): inside the block's loops the K/V pool is neither
    copied nor sliced, whole or one layer of it; the only ops of its
    shape there are the in-place row writes.  The program's own entry
    and exit may copy the pool, from the TPU's default layout of a
    (L, S, C, KV, dh) array (C minor) to the row-major one the kernel
    reads and back, but never slice it."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve import ServeConfig, ServeEngine
    cfg = get_config("smollm-135m").with_(n_layers=2, vocab_size=512)
    scfg = ServeConfig(n_slots=16, cache_len=2048, block_steps=2,
                       attn_backend="pallas")
    params = jax.eval_shape(functools.partial(T.init_params, cfg=cfg),
                            jax.random.PRNGKey(0))
    eng = ServeEngine(params, cfg, scfg)
    eng.attn_interpret = False      # the engine chose the CPU's interpreter

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    hlo = eng._get_block(None).lower(
        on_chip(params), on_chip(eng.state),
        jax.ShapeDtypeStruct((scfg.n_slots,), bool, sharding=one_chip)
    ).compile().as_text()
    assert "tpu_custom_call" in hlo
    layer = f"{scfg.n_slots},{scfg.cache_len},{cfg.n_kv_heads},{cfg.head_dim}"
    inst = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[(?:1,)*(?:"
                      f"{cfg.n_layers},)?{layer}" r"\]\S* ([\w-]+)\(")
    moved, entry = [], False
    for line in hlo.splitlines():
        if line.rstrip().endswith("{") and not line.startswith(" "):
            entry = line.startswith("ENTRY ")
        m = inst.match(line)
        if m and re.search("dynamic-slice|dynamic-update-slice" if entry
                           else "copy|dynamic-slice|dynamic-update-slice",
                           m.group(1) + " " + m.group(2)):
            moved.append(m.group(1))
    assert not moved


def test_flash_attention_gqa(one_chip):
    """fedmm-small prefill: 12 query heads over 4 KV heads, 1024 tokens."""
    fn = functools.partial(flash_attention_pallas, n_rep=3)
    txt = _compiled_text(fn, one_chip,
                         ((12, 1024, 64), jnp.bfloat16),
                         ((4, 1024, 64), jnp.bfloat16),
                         ((4, 1024, 64), jnp.bfloat16))
    assert "tpu_custom_call" in txt


def test_lora_matmul(one_chip):
    """A rank-8 LoRA projection at fedmm-small width (768 -> 768)."""
    txt = _compiled_text(lora_matmul_pallas, one_chip,
                         ((512, 768), jnp.bfloat16),
                         ((768, 768), jnp.bfloat16),
                         ((768, 8), jnp.bfloat16),
                         ((8, 768), jnp.bfloat16))
    assert "tpu_custom_call" in txt
