"""Frozen per-modality tokenizer stubs (the paper's phi_m).

The paper uses pretrained frozen tokenizers (DINOv3 for images, DNABERT for
genetics, TabPFN for tabular, Llama for text).  Those checkpoints are a data
gate (repro band 2/5), so we simulate them: a deterministic frozen random
featurizer mapping raw modality vectors to L tokens of width d_m.  Crucially
it PRESERVES the latent class geometry (a smooth injective map of the raw
space), which is exactly the property the paper's platonic-convergence
argument relies on — so the CKA-alignment math is exercised faithfully.

Tokenizers are never trained and never shipped (paper: "frozen and not
shared in the federation").
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp

Array = jax.Array


@dataclass(frozen=True)
class FrozenTokenizer:
    """phi_m: raw (N, d_raw) -> tokens (N, L, d_m)."""
    modality: str
    d_raw: int
    n_tokens: int
    d_out: int
    seed: int = 0

    def _weights(self):
        k = jax.random.PRNGKey(zlib.crc32(
            f"{self.modality}/{self.seed}".encode()) % (2 ** 31))
        k1, k2, k3 = jax.random.split(k, 3)
        w1 = jax.random.normal(k1, (self.d_raw, self.n_tokens, self.d_out)) \
            * self.d_raw ** -0.5
        b1 = 0.1 * jax.random.normal(k2, (self.n_tokens, self.d_out))
        w2 = jax.random.normal(k3, (self.d_out, self.d_out)) * self.d_out ** -0.5
        return w1, b1, w2

    def __call__(self, raw: Array) -> Array:
        w1, b1, w2 = self._weights()
        h = jnp.einsum("nd,dlo->nlo", raw.astype(jnp.float32), w1) + b1
        return jnp.tanh(h) @ w2                      # (N, L, d_out)

    def padded_weights(self, width: int):
        """Weights zero-padded to token width ``width`` >= d_out, for the
        node-stacked engine (one program over heterogeneous tokenizers).
        Zero padding is exact: padded inputs stay 0 through tanh, padded
        w2 rows/cols contribute 0, so outputs match the unpadded tokenizer
        on the first d_out channels and are 0 beyond."""
        w1, b1, w2 = self._weights()
        pad = width - self.d_out
        if pad < 0:
            raise ValueError(f"width {width} < d_out {self.d_out}")
        w1 = jnp.pad(w1, ((0, 0), (0, 0), (0, pad)))
        b1 = jnp.pad(b1, ((0, 0), (0, pad)))
        w2 = jnp.pad(w2, ((0, pad), (0, pad)))
        return w1, b1, w2


def default_tokenizers(modality_dims: dict, d_raw: int, n_tokens: int = 16,
                       seed: int = 0) -> dict:
    """One frozen tokenizer per modality with its published embedding width
    (see configs.fedmm_base.MODALITY_TOKENIZER_DIMS)."""
    return {m: FrozenTokenizer(m, d_raw, n_tokens, d, seed=seed)
            for m, d in modality_dims.items()}
