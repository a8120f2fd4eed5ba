"""Probe: how does the CKA regulariser change cross-modal geometry?

Trains two tiny federations (lambda_geo=0 vs 1) on the same unpaired data
and prints the pairwise modality CKA matrix before/after — a direct view of
the paper's 'geometric Rosetta stone' at work.

    PYTHONPATH=src python examples/alignment_probe.py
"""
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core import cka as C
from repro.core.federation import Federation, FederationConfig


def run(lam):
    model = get_config("fedmm-small").with_(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256, dtype="float32")
    fed = FederationConfig(n_nodes=4, rounds=3, local_steps=6,
                           local_batch=24, method="geolora",
                           lambda_geo=lam)
    f = Federation(fed, model)
    def gram_matrix():
        grams = []
        for i, node in enumerate(f.nodes):
            params = f.node_params(i)
            pooled = f._pooled(params, f.anchor_tokens[node["modality"]])
            grams.append(C.cosine_gram(pooled))
        return jnp.stack(grams)
    before = C.pairwise_cka(gram_matrix())
    f.run()
    after = C.pairwise_cka(gram_matrix())
    return before, after, f


def show(m, mods):
    print("      " + "  ".join(f"{x[:5]:>6s}" for x in mods))
    for i, row in enumerate(m):
        print(f"{mods[i][:5]:>6s}" + "  ".join(f"{float(v):6.3f}"
                                               for v in row))


def main():
    for lam in (0.0, 1.0):
        before, after, f = run(lam)
        mods = [n["modality"] for n in f.nodes]
        print(f"\n=== lambda_geo = {lam} ===")
        print("pairwise modality CKA before training:")
        show(before, mods)
        print("after 3 federated rounds:")
        show(after, mods)


if __name__ == "__main__":
    enable_compile_cache()
    main()
