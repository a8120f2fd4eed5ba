"""Production mesh builders (TPU v5e pods; host-device placeholders in the
dry-run container).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init; smoke tests see
one device)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh (CPU smoke tests of the sharded code paths)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def make_abstract_mesh(shape, axes):
    """AbstractMesh of the given axis sizes and names.  Abstract meshes
    carry only shape/name information — exactly what the sharding rule
    engine and its tests need without touching device state."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def n_nodes(mesh) -> int:
    """Federated nodes = slices along the batch axes (one node per slice)."""
    out = 1
    for a in batch_axes(mesh):
        out *= mesh.shape[a]
    return out


# TPU v5e hardware constants (per chip) used by the roofline analysis.
HW = {
    "peak_flops_bf16": 197e12,      # FLOP/s
    "hbm_bw": 819e9,                # B/s
    "ici_bw": 50e9,                 # B/s per link
    "hbm_bytes": 16 * 2 ** 30,
}
