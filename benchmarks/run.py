"""Benchmark harness — one function per paper claim/table.

The paper is a methods paper: its two tables are literature comparisons,
and its quantitative claims are (a) >99.9% communication reduction from
GeoLoRA at foundation-model scale, (b) O(B^2) Gram upload vs raw-activation
sharing, (c) CKA-regularised alignment of disjoint modalities, (d)
precision weighting suppressing bad nodes, (e) fixed-A update consistency.
Each bench validates one claim and prints ``name,us_per_call,derived`` CSV.

Run: PYTHONPATH=src python -m benchmarks.run  [--quick]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache

ROWS = []


def _timeit(fn, n=3, warmup=1):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6          # us


def _row(name, us, derived):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}", flush=True)


# ----------------------------------------------------------------------
def bench_comm_reduction():
    """Claim: LoRA shrinks the per-round update by >99.9% at foundation
    scale (paper: 'gigabytes to megabytes')."""
    from repro.configs import get_config
    from repro.core import lora as L

    for arch in ("fedmm-base", "mistral-nemo-12b", "qwen3-32b"):
        cfg = get_config(arch)
        # analytic bytes: full model vs rank-16 B factors on attn targets
        full = cfg.param_count * 2                        # bf16
        d, dh = cfg.d_model, cfg.head_dim
        h, kv = max(cfg.n_heads, 1), max(cfg.n_kv_heads, 1)
        rank = 16
        per_layer_b = rank * (h * dh + 2 * kv * dh + d)   # wq wk wv wo B's
        lora = cfg.n_layers * per_layer_b * 2 + 32 * 32 * 4
        saving = 100.0 * (1 - lora / full)
        _row(f"comm_reduction_{arch}", 0.0,
             f"{saving:.3f}%_saved;up={lora/1e6:.2f}MB;full={full/1e9:.2f}GB")


def bench_gram_vs_activations():
    """Claim: Gram upload is O(B^2), far below raw anchor activations
    (B x L x d) — and shares only relational geometry."""
    from repro.configs import get_config
    cfg = get_config("fedmm-base")
    b, l, d = 32, 128, cfg.d_model
    gram = b * b * 4
    acts = b * l * d * 2
    _row("gram_vs_raw_activations", 0.0,
         f"gram={gram/1e3:.1f}KB;raw={acts/1e6:.2f}MB;"
         f"ratio={acts/gram:.0f}x")


def bench_cka_alignment(quick: bool):
    """Claim: CKA-regularised rounds align disjoint unpaired modalities."""
    from repro.configs import get_config
    from repro.core.federation import Federation, FederationConfig
    tiny = get_config("fedmm-small").with_(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256, dtype="float32")
    rounds = 2 if quick else 5
    fed = FederationConfig(n_nodes=4, rounds=rounds, local_steps=5,
                           local_batch=16, method="geolora", lambda_geo=1.0)
    t0 = time.perf_counter()
    f = Federation(fed, tiny)
    hist = f.run()
    us = (time.perf_counter() - t0) / rounds * 1e6
    _row("cka_alignment_geolora", us,
         f"xcka_r0={hist[0]['cross_node_cka']:.3f};"
         f"xcka_final={hist[-1]['cross_node_cka']:.3f};"
         f"task_final={hist[-1]['task_loss']:.3f}")

    # ablation: lambda_geo = 0 (no alignment regulariser)
    fed0 = FederationConfig(n_nodes=4, rounds=rounds, local_steps=5,
                            local_batch=16, method="geolora", lambda_geo=0.0)
    h0 = Federation(fed0, tiny).run()
    _row("cka_alignment_ablation_lambda0", 0.0,
         f"xcka_final={h0[-1]['cross_node_cka']:.3f}")


def bench_precision_weighting(quick: bool):
    """Claim: LAP precision weighting downweights a corrupted node."""
    from repro.configs import get_config
    from repro.core.federation import Federation, FederationConfig
    tiny = get_config("fedmm-small").with_(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256, dtype="float32")
    fed = FederationConfig(n_nodes=4, rounds=2, local_steps=5,
                           local_batch=16, method="geolora",
                           aggregation="precision", corrupt_nodes=(2,))
    f = Federation(fed, tiny)
    hist = f.run()
    w = hist[-1]["weights"]
    others = sum(w[i] for i in range(4) if i != 2) / 3
    _row("precision_weighting_corrupt_node", 0.0,
         f"w_corrupt={w[2]:.3f};w_others_mean={others:.3f};"
         f"suppression={others/max(w[2],1e-6):.2f}x")


def bench_fixed_a_consistency():
    """Claim (Eq. 4): frozen shared A makes B-averaging exact."""
    import numpy as np
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 8)).astype(np.float32)
    bs = rng.standard_normal((4, 8, 64)).astype(np.float32)
    exact = np.mean([a @ b for b in bs], axis=0)
    ours = a @ bs.mean(0)
    err = float(np.abs(exact - ours).max())
    a_k = rng.standard_normal((4, 64, 8)).astype(np.float32)
    naive = a_k.mean(0) @ bs.mean(0)
    hetero = np.mean([ak @ b for ak, b in zip(a_k, bs)], axis=0)
    err_het = float(np.abs(hetero - naive).max())
    _row("fixed_a_aggregation_consistency", 0.0,
         f"fixedA_err={err:.2e};heteroA_err={err_het:.3f}")


def bench_kernels(quick: bool):
    """Kernel wall-times (jnp oracle path on CPU; the Pallas kernels target
    TPU and are correctness-validated in interpret mode by the tests)."""
    from repro.kernels import ref
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (128, 1024))
    g = jax.jit(ref.cosine_gram_ref)
    _row("gram_128x1024_ref", _timeit(lambda: g(x).block_until_ready()),
         "oracle")
    w = jax.random.normal(k, (1024, 1024))
    a = jax.random.normal(k, (1024, 16))
    b = jax.random.normal(k, (16, 1024))
    lm = jax.jit(ref.lora_matmul_ref)
    _row("lora_matmul_1024_ref",
         _timeit(lambda: lm(x, w, a, b).block_until_ready()), "oracle")
    q = jax.random.normal(k, (8, 512, 64))
    fa = jax.jit(lambda q: ref.flash_attention_ref(q, q, q))
    _row("attention_512_ref",
         _timeit(lambda: fa(q).block_until_ready()), "oracle")
    da = jax.random.uniform(k, (4, 512, 256), minval=0.5, maxval=0.99)
    db = jax.random.normal(k, (4, 512, 256))
    h0 = jnp.zeros((4, 256))
    ss = jax.jit(ref.selective_scan_ref)
    _row("selective_scan_512_ref",
         _timeit(lambda: ss(da, db, h0)[0].block_until_ready()), "oracle")


def bench_geodora_magnitude_direction(quick: bool):
    """Claim (Eq. 5): GeoDoRA decouples magnitude from direction — scaling
    a node's inputs moves its magnitudes, not its aligned direction."""
    from repro.core import lora as L
    from repro.models.common import dora_column_norm, linear, make_linear
    import numpy as np
    key = jax.random.PRNGKey(1)
    lin = make_linear(key, 32, 24, jnp.float32)
    from repro.models.common import add_dora, add_lora
    d = add_dora(add_lora(key, lin, 4, jnp.float32))
    d["lora_B"] = 0.1 * jax.random.normal(key, (4, 24))
    x = jax.random.normal(key, (16, 32))
    y1 = linear(x, d)
    d2 = dict(d, dora_m=2.0 * d["dora_m"])
    y2 = linear(x, d2)
    ratio = float(jnp.median(jnp.abs(y2 / y1)))
    _row("geodora_magnitude_scaling", 0.0,
         f"output_scale_ratio={ratio:.3f}(expect~2)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--fed-round", action="store_true",
                    help="also run the sequential-vs-engine round-latency "
                         "bench (writes BENCH_federation.json)")
    args, _ = ap.parse_known_args()
    print("name,us_per_call,derived")
    bench_comm_reduction()
    bench_gram_vs_activations()
    bench_fixed_a_consistency()
    bench_geodora_magnitude_direction(args.quick)
    bench_kernels(args.quick)
    bench_precision_weighting(args.quick)
    bench_cka_alignment(args.quick)
    if args.fed_round:
        from benchmarks.federation_round import main as fed_round_main
        fed_round_main()


if __name__ == "__main__":
    enable_compile_cache()
    main()
