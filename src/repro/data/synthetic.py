"""Synthetic unpaired multimodal task with a shared latent concept space.

Simulates the paper's hospital federation (repro band 2/5 — no real TCGA /
MIMIC access): ``n_classes`` disease concepts live in a latent space; a
sample of class c in modality m is an independent draw around prototype c
pushed through a fixed modality-specific map.  Nodes hold ONE modality each
and never share samples; the public anchor set holds a few *unpaired* draws
per class per modality ("same medical concept, not same patient").

Because every modality is a different view of the same latent geometry, the
cross-modal Gram matrices are alignable — which is the hypothesis the
paper's CKA regulariser operationalises.  A ``corrupt`` flag yields nodes
whose data is latent-free noise (for validating precision-weighted
aggregation).
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


@dataclass(frozen=True)
class SyntheticMultimodal:
    n_classes: int = 8
    d_latent: int = 32
    d_raw: int = 64
    noise: float = 0.25
    seed: int = 0
    modalities: Tuple[str, ...] = ("image", "text", "genetics", "tabular")

    def _keys(self):
        return jax.random.split(jax.random.PRNGKey(self.seed), 4)

    def prototypes(self) -> Array:
        k, *_ = self._keys()
        return jax.random.normal(k, (self.n_classes, self.d_latent))

    def modality_map(self, modality: str):
        """Public accessor for the fixed modality map (w, b) — the
        node-stacked engine bakes these in as per-node constants so data
        sampling can run inside the compiled round."""
        return self._modality_map(modality)

    def _modality_map(self, modality: str):
        _, k, *_ = self._keys()
        km = jax.random.fold_in(k, zlib.crc32(modality.encode()) % (2 ** 31))
        k1, k2 = jax.random.split(km)
        w = jax.random.normal(k1, (self.d_latent, self.d_raw)) \
            * self.d_latent ** -0.5
        b = 0.3 * jax.random.normal(k2, (self.d_raw,))
        return w, b

    def sample(self, key, modality: str, n: int, *,
               class_probs: Optional[Array] = None,
               corrupt: bool = False) -> Tuple[Array, Array]:
        """-> raw (n, d_raw), labels (n,). ``corrupt`` nodes emit pure noise
        with random labels (no latent structure)."""
        k1, k2, k3 = jax.random.split(key, 3)
        if corrupt:
            raw = jax.random.normal(k2, (n, self.d_raw))
            labels = jax.random.randint(k1, (n,), 0, self.n_classes)
            return raw, labels
        probs = (class_probs if class_probs is not None
                 else jnp.full((self.n_classes,), 1.0 / self.n_classes))
        labels = jax.random.categorical(
            k1, jnp.log(jnp.maximum(probs, 1e-9)), shape=(n,))
        latent = self.prototypes()[labels] \
            + self.noise * jax.random.normal(k2, (n, self.d_latent))
        w, b = self._modality_map(modality)
        raw = jnp.tanh(latent @ w + b) \
            + 0.05 * jax.random.normal(k3, (n, self.d_raw))
        return raw, labels

    def sample_in_scan(self, key, mod_w: Array, mod_b: Array, n: int,
                       corrupt: Array, *, mod2_w: Optional[Array] = None,
                       mod2_b: Optional[Array] = None):
        """Traceable twin of ``sample`` for compiled round/block bodies
        (vmap over nodes, lax.scan over steps and rounds): the modality map
        is passed as arrays instead of looked up by name, and ``corrupt``
        is a traced selector — both the clean and corrupt branches are
        drawn from the SAME key splits as ``sample`` and selected per node,
        so one program serves every node type with reference-identical RNG
        streams.  With ``mod2_w/b`` (bridge nodes) the identical latent and
        output-noise draws are pushed through the second modality map,
        reproducing the reference's re-sample-with-same-key pairing.

        -> (raw (n, d_raw), labels (n,), raw2 (n, d_raw) | None)
        """
        k1, k2, k3 = jax.random.split(key, 3)
        log_probs = jnp.log(jnp.full((self.n_classes,),
                                     1.0 / self.n_classes))
        labels_c = jax.random.categorical(k1, log_probs, shape=(n,))
        latent = self.prototypes()[labels_c] \
            + self.noise * jax.random.normal(k2, (n, self.d_latent))
        out_noise = 0.05 * jax.random.normal(k3, (n, self.d_raw))
        raw_c = jnp.tanh(latent @ mod_w + mod_b) + out_noise
        raw_x = jax.random.normal(k2, (n, self.d_raw))
        labels_x = jax.random.randint(k1, (n,), 0, self.n_classes)
        raw = jnp.where(corrupt, raw_x, raw_c)
        labels = jnp.where(corrupt, labels_x, labels_c)
        raw2 = (jnp.tanh(latent @ mod2_w + mod2_b) + out_noise
                if mod2_w is not None else None)
        return raw, labels, raw2

    def anchor_set(self, key, n_per_class: int = 4
                   ) -> Dict[str, Tuple[Array, Array]]:
        """Public anchors: for each modality, n_per_class *independent*
        (unpaired) draws per class, class-sorted so Gram rows correspond
        across modalities at the concept level."""
        out = {}
        labels = jnp.repeat(jnp.arange(self.n_classes), n_per_class)
        for i, m in enumerate(self.modalities):
            km = jax.random.fold_in(key, i)
            latent = self.prototypes()[labels] + self.noise * \
                jax.random.normal(km, (labels.shape[0], self.d_latent))
            w, b = self._modality_map(m)
            kn = jax.random.fold_in(km, 1)
            raw = jnp.tanh(latent @ w + b) \
                + 0.05 * jax.random.normal(kn, (labels.shape[0], self.d_raw))
            out[m] = (raw, labels)
        return out
