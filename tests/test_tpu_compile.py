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


# (slots, cache_len, q heads, KV heads, head_dim, window): smollm-135m and
# fedmm-small at the serving pool's shape, plus a ring-buffer window
DECODE_CASES = {
    "smollm_135m": (8, 1024, 9, 3, 64, 0),
    "fedmm_small": (8, 1024, 12, 4, 64, 0),
    "smollm_135m_ring": (8, 1024, 9, 3, 64, 1024),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_decode_attention(one_chip, case):
    s, c, h, kv, dh, window = DECODE_CASES[case]
    fn = functools.partial(decode_attention_pallas, window=window)
    txt = _compiled_text(fn, one_chip,
                         ((s, h, dh), jnp.bfloat16),
                         ((s, c, kv, dh), jnp.bfloat16),
                         ((s, c, kv, dh), jnp.bfloat16),
                         ((s,), jnp.int32), ((s, c), jnp.int32))
    assert "tpu_custom_call" in txt


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
