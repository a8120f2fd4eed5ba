"""Round-latency benchmark: sequential per-node loop vs node-stacked engine,
width-bucketed vs pad-to-max-width layouts, fused multi-round blocks, and
the server-step Gram backend.

The sequential reference dispatches one jitted step per node per local step
(K x E per round) and tokenizes each batch eagerly on the host; the engine
runs the whole round — vmapped local epochs per width bucket + the server
step — as ONE compiled call with donated round-state buffers.  This bench
measures wall-clock per round for both at K in {4, 8, 16} and writes
``BENCH_federation.json``.

The K sweep uses the image+text modality pair; the ``mixed_width`` row runs
the full 4-modality mix (192..2048-dim tokenizers) and compares the legacy
single-bucket layout (every node padded to 2048, narrow nodes paying the
quadratic w^2 padding tax) against width bucketing, which groups nodes by
tokenizer width inside the same single-dispatch round.  A peak-memory
column (XLA ``memory_analysis`` on the compiled round) reports the
round-state donation savings: donated buffers alias outputs onto inputs,
so peak round-state memory stays ~1x instead of 2x.

``fused_rounds_m{M}`` rows measure the block executor (``run_block``:
lax.scan over M whole rounds, donated carry) against the per-round engine:
ms/round, dispatches and blocking host syncs per round (both 1/M fused),
and the compiled block's peak bytes.  ``sampled_cohort_*`` / ``dropout_*``
rows measure partial participation through the fused blocks: a uniform
C-of-K cohort must cost ~C/K of the full round (the gather-compact path)
and a dropout straggler mask ~1x (masked path), both still at 1/M
dispatches per round.  The ``gram_backend`` row compares the reference jnp
Gram against the Pallas kernel (interpret mode on CPU — the
dispatch-correctness datapoint; the performance target is TPU) on the
server step.

``async_lagged_k{K}`` / ``quarantine_1_poisoned`` rows measure the
buffered staleness-aware protocol through the fused blocks: rounds/sec vs
the synchronous baseline, the staleness histogram of delivered reports,
the device quarantine counters against an independent host-side count of
poisoned report attempts, a finite-globals check, and the final
cross-node CKA convergence proxy — all still at one measured dispatch per
M-round block.

Run: PYTHONPATH=src python -m benchmarks.federation_round [--quick|--smoke]
(``--only SUBSTR`` re-runs just the matching rows and merges them into
the existing JSON.)
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core.federation import (Federation, FederationConfig,
                                   SequentialFederation)

TINY = get_config("fedmm-small").with_(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=256, dtype="float32")

LOCAL_STEPS = 4
MIXED_MODALITIES = ("image", "text", "genetics", "tabular")


def _fedcfg(k: int, modalities) -> FederationConfig:
    return FederationConfig(n_nodes=k, rounds=1, local_steps=LOCAL_STEPS,
                            local_batch=8, method="geolora", lora_rank=4,
                            anchors_per_class=2, n_tokens=4,
                            modalities=modalities)


def _light_fedcfg(k: int, modalities) -> FederationConfig:
    """The high-round-rate regime (small batches, tiny anchor set) shared
    by the fused-rounds and participation rows, so their ms/round numbers
    stay comparable in BENCH_federation.json."""
    return FederationConfig(n_nodes=k, rounds=1, local_steps=LOCAL_STEPS,
                            local_batch=4, method="geolora", lora_rank=2,
                            anchors_per_class=1, n_tokens=2,
                            modalities=modalities)


def _time_rounds(f, rounds: int) -> float:
    """Best-of-N ms/round (min is the robust latency estimator under CPU
    contention; the first round is warmup and pays compilation)."""
    f.run_round()
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        f.run_round()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _peak_bytes(f: Federation, block_m: int = None) -> int:
    """Estimated peak live bytes of one compiled round (or, with
    ``block_m``, one fused M-round block): arguments + outputs + XLA
    temporaries, minus the donated input/output aliases."""
    args = (f._trains, f._opts, f._keys, f.gbar, f._server_m, f._staticss,
            (None,) * len(f._trains))
    fn = f.engine.round_fn if block_m is None else f.engine.block_fn(block_m)
    ma = fn.lower(*args).compile().memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)



def _count_calls(holder, key=None, attr=None):
    """Wrap a compiled engine function with a dispatch counter so the
    bench MEASURES the dispatch structure it reports (and CI guards)
    instead of asserting a constant.  ``holder`` is either the engine's
    ``_block_cache`` dict (pass ``key``) or the engine itself (pass
    ``attr`` for the per-round ``round_fn``)."""
    calls = {"n": 0}
    orig = holder[key] if attr is None else getattr(holder, attr)

    def counting(*args, **kwargs):
        calls["n"] += 1
        return orig(*args, **kwargs)

    if attr is None:
        holder[key] = counting
    else:
        setattr(holder, attr, counting)
    return calls


def bench_cfg(name: str, k: int, modalities, rounds: int) -> dict:
    fedcfg = _fedcfg(k, modalities)
    seq_ms = _time_rounds(SequentialFederation(fedcfg, TINY), rounds)
    eng_ms = _time_rounds(Federation(fedcfg, TINY), rounds)
    row = {
        "name": name,
        "k_nodes": k,
        "modalities": list(modalities),
        "local_steps": LOCAL_STEPS,
        "sequential_ms_per_round": round(seq_ms, 2),
        "engine_ms_per_round": round(eng_ms, 2),
        "speedup": round(seq_ms / eng_ms, 2),
        # dispatch structure: the loop issues one jitted call per node per
        # local step; the engine compiles the whole round into one call
        "sequential_dispatches_per_round": k * LOCAL_STEPS,
        "engine_dispatches_per_round": 1,
    }
    print(f"{name} K={k}: sequential={seq_ms:.1f}ms "
          f"engine={eng_ms:.1f}ms speedup={row['speedup']}x", flush=True)
    return row


def bench_mixed_bucketed(name: str, k: int, modalities, rounds: int) -> dict:
    """Padded (single-bucket, pad-to-max-width) vs width-bucketed engine on
    a heterogeneous-width modality mix, plus the donation memory column."""
    fedcfg = _fedcfg(k, modalities)
    seq_ms = _time_rounds(SequentialFederation(fedcfg, TINY), rounds)

    padded = Federation(fedcfg, TINY, width_bucketing=False)
    padded_peak = _peak_bytes(padded)
    padded_ms = _time_rounds(padded, rounds)

    bucketed = Federation(fedcfg, TINY)
    bucketed_peak = _peak_bytes(bucketed)
    no_donate_peak = _peak_bytes(Federation(fedcfg, TINY, donate=False))
    bucketed_ms = _time_rounds(bucketed, rounds)

    row = {
        "name": name,
        "k_nodes": k,
        "modalities": list(modalities),
        "local_steps": LOCAL_STEPS,
        "bucket_widths": list(bucketed._bucket_widths),
        "sequential_ms_per_round": round(seq_ms, 2),
        "padded_engine_ms_per_round": round(padded_ms, 2),
        "engine_ms_per_round": round(bucketed_ms, 2),
        "speedup": round(seq_ms / bucketed_ms, 2),
        "padded_speedup": round(seq_ms / padded_ms, 2),
        "bucketed_vs_padded": round(padded_ms / bucketed_ms, 2),
        "sequential_dispatches_per_round": k * LOCAL_STEPS,
        "engine_dispatches_per_round": 1,
        # donation column: peak live bytes of the compiled round
        "peak_bytes_donated": bucketed_peak,
        "peak_bytes_no_donation": no_donate_peak,
        "donation_saved_bytes": no_donate_peak - bucketed_peak,
        "padded_peak_bytes_donated": padded_peak,
    }
    print(f"{name} K={k}: sequential={seq_ms:.1f}ms padded={padded_ms:.1f}ms "
          f"bucketed={bucketed_ms:.1f}ms "
          f"(bucketed vs padded {row['bucketed_vs_padded']}x, "
          f"vs sequential {row['speedup']}x) "
          f"peak {bucketed_peak/1e6:.1f}MB donated vs "
          f"{no_donate_peak/1e6:.1f}MB undonated", flush=True)
    return row


def bench_fused_rounds(name: str, k: int, modalities, reps: int,
                       m: int) -> dict:
    """Per-round engine (1 dispatch + 1 blocking host sync per round) vs
    the fused M-round block executor (1 donated dispatch + 1 sync per M
    rounds: lax.scan over the round body, metrics in (M, ...) buffers).

    Uses a light round config (the high-round-rate regime the fusion
    targets, where the host round-trip is a visible slice of the round)
    and INTERLEAVES the two timings rep by rep so slow machine-load drift
    cancels instead of biasing whichever variant ran later."""
    fedcfg = _light_fedcfg(k, modalities)
    per_round = Federation(fedcfg, TINY)
    fused = Federation(fedcfg, TINY)
    per_round_peak = _peak_bytes(per_round)
    fused_peak = _peak_bytes(fused, block_m=m)
    for _ in range(m):                     # warmup + compile both variants
        per_round.run_round()
    fused.run_rounds(m, block_size=m)
    # dispatch counters wrap the already-compiled functions AFTER warmup,
    # so the timed reps below measure the real dispatch structure
    pr_calls = _count_calls(per_round.engine, attr="round_fn")
    fu_calls = _count_calls(fused.engine._block_cache,
                            key=(m, False, None, False, 0))
    best_r = best_f = float("inf")
    # small M means short timed spans; take more reps so a transient
    # contention burst cannot bias a whole variant
    reps = max(reps, 32 // m)
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(m):
            per_round.run_round()
        best_r = min(best_r, time.perf_counter() - t0)
        t0 = time.perf_counter()
        fused.run_rounds(m, block_size=m)
        best_f = min(best_f, time.perf_counter() - t0)
    per_round_ms = best_r / m * 1e3
    fused_ms = best_f / m * 1e3
    timed_rounds = reps * m

    row = {
        "name": name,
        "k_nodes": k,
        "modalities": list(modalities),
        "local_steps": LOCAL_STEPS,
        "block_rounds": m,
        "per_round_engine_ms_per_round": round(per_round_ms, 2),
        "fused_ms_per_round": round(fused_ms, 2),
        "fused_speedup": round(per_round_ms / fused_ms, 2),
        # dispatch structure, MEASURED over the timed reps (counters on
        # the compiled functions): the per-round driver issues one jitted
        # call per round; the block executor amortises it over M rounds.
        # Host syncs mirror the dispatch structure by construction (one
        # blocking metric readback per dispatch in both drivers).
        "dispatches_per_round": round(fu_calls["n"] / timed_rounds, 4),
        "host_syncs_per_round": round(1.0 / m, 4),
        "per_round_dispatches_per_round": round(
            pr_calls["n"] / timed_rounds, 4),
        "per_round_host_syncs_per_round": 1,
        "peak_bytes_per_round_engine": per_round_peak,
        "peak_bytes_fused_block": fused_peak,
    }
    print(f"{name} K={k} M={m}: per-round={per_round_ms:.1f}ms "
          f"fused={fused_ms:.1f}ms/round "
          f"(x{row['fused_speedup']}, dispatches/round 1 -> 1/{m}) "
          f"peak {fused_peak/1e6:.1f}MB vs {per_round_peak/1e6:.1f}MB",
          flush=True)
    return row


def bench_participation(name: str, k: int, modalities, reps: int, m: int,
                        plan) -> dict:
    """Partial participation through the fused-block executor: full
    participation vs a sampled cohort (gather-compact: local-epoch compute
    scales with the cohort size C, not K) or a dropout straggler mask
    (masked path: full compute, masked updates), all at 1/M dispatches and
    host syncs per round.  Interleaved best-of timing, same protocol as
    the fused-rounds bench."""
    fedcfg = _light_fedcfg(k, modalities)
    full = Federation(fedcfg, TINY)
    samp = Federation(fedcfg, TINY)
    full.run_rounds(m, block_size=m)                   # warmup + compile
    recs = samp.run_rounds(m, block_size=m, participation=plan)
    # measure the dispatch structure (counter on the compiled block fn,
    # installed after warmup): participation must not add dispatches
    samp_calls = _count_calls(samp.engine._block_cache,
                              key=(m, False, plan, False, 0))
    best_full = best_samp = float("inf")
    reps = max(reps, 32 // m)
    for _ in range(reps):
        t0 = time.perf_counter()
        full.run_rounds(m, block_size=m)
        best_full = min(best_full, time.perf_counter() - t0)
        t0 = time.perf_counter()
        recs = samp.run_rounds(m, block_size=m, participation=plan)
        best_samp = min(best_samp, time.perf_counter() - t0)
    full_ms = best_full / m * 1e3
    samp_ms = best_samp / m * 1e3
    timed_rounds = reps * m
    mean_cohort = sum(r["cohort_size"] for r in recs) / len(recs)

    row = {
        "name": name,
        "k_nodes": k,
        "modalities": list(modalities),
        "local_steps": LOCAL_STEPS,
        "block_rounds": m,
        "strategy": plan.strategy,
        "cohort_size": plan.cohort_size,
        "dropout_rate": (plan.dropout_rate if plan.strategy == "dropout"
                         else None),
        "mean_cohort": round(mean_cohort, 2),
        "full_ms_per_round": round(full_ms, 2),
        "sampled_ms_per_round": round(samp_ms, 2),
        # < 1 when compute tracks the cohort (gather-compact strategies);
        # ~1 for the masked dropout path (compute stays at K by design)
        "cost_vs_full": round(samp_ms / full_ms, 2),
        "cohort_fraction": round(mean_cohort / k, 2),
        # participation must not change the dispatch structure: still one
        # donated dispatch per M-round block — MEASURED over the timed
        # reps (host syncs mirror dispatches: one readback per block)
        "dispatches_per_round": round(samp_calls["n"] / timed_rounds, 4),
        "host_syncs_per_round": round(1.0 / m, 4),
    }
    print(f"{name} K={k} M={m} {plan.strategy}: full={full_ms:.1f}ms "
          f"sampled={samp_ms:.1f}ms/round (cost x{row['cost_vs_full']} at "
          f"cohort {mean_cohort:.1f}/{k}, measured dispatches/round "
          f"{row['dispatches_per_round']})", flush=True)
    return row


def bench_async(name: str, k: int, modalities, reps: int, m: int,
                plan) -> dict:
    """Asynchronous buffered federation through the fused-block executor:
    nodes report after a sampled lag (and may crash, rejoin, or be
    poisoned), the server staleness-weights whatever landed this round —
    still ONE donated dispatch per M-round block (measured).  Reports
    rounds/sec, the staleness histogram of delivered reports, the
    per-node quarantine counters against an independent host-side count
    of poisoned report attempts, a finite-globals check, and the final
    cross-node CKA against a synchronous full-participation baseline
    (the convergence proxy CI guards for sign flips)."""
    import numpy as np
    import jax

    fedcfg = _light_fedcfg(k, modalities)
    sync = Federation(fedcfg, TINY)
    asyn = Federation(fedcfg, TINY)
    sync_recs = sync.run_rounds(m, block_size=m)       # warmup + compile
    all_recs = list(asyn.run_rounds(m, block_size=m, participation=plan))
    asy_calls = _count_calls(asyn.engine._block_cache,
                             key=(m, False, plan, False, 0))
    best_sync = best_async = float("inf")
    reps = max(reps, 32 // m)
    for _ in range(reps):
        t0 = time.perf_counter()
        sync_recs = sync.run_rounds(m, block_size=m)
        best_sync = min(best_sync, time.perf_counter() - t0)
        t0 = time.perf_counter()
        recs = asyn.run_rounds(m, block_size=m, participation=plan)
        best_async = min(best_async, time.perf_counter() - t0)
        all_recs += recs
    sync_ms = best_sync / m * 1e3
    async_ms = best_async / m * 1e3
    timed_rounds = reps * m
    # staleness histogram over DELIVERED reports (lag in rounds)
    hist = {}
    for r in all_recs:
        for lag, d in zip(r["staleness"], r["delivered"]):
            if d > 0:
                hist[int(lag)] = hist.get(int(lag), 0) + 1
    n_del = sum(hist.values())
    mean_stale = (sum(l * c for l, c in hist.items()) / n_del
                  if n_del else 0.0)
    # the device quarantine counters vs an INDEPENDENT host-side count:
    # a poisoned node must be quarantined on every round it starts a
    # report, so the two columns must agree exactly (CI checks this)
    quarantined = [int(round(x)) for x in all_recs[-1]["quarantined"]]
    expected_q = [0] * k
    for r in all_recs:
        for i in plan.poison_nodes:
            expected_q[i] += int(round(r["participation"][i]))
    finite = bool(np.isfinite(np.asarray(asyn.gbar)).all())
    for i in range(k):
        for leaf in jax.tree.leaves(asyn.node_params(i)):
            if leaf is not None:
                finite &= bool(np.isfinite(np.asarray(leaf)).all())

    row = {
        "name": name,
        "k_nodes": k,
        "modalities": list(modalities),
        "local_steps": LOCAL_STEPS,
        "block_rounds": m,
        "strategy": "async",
        "lag_dist": plan.lag_dist,
        "max_lag": plan.max_lag,
        "crash_rate": plan.crash_rate,
        "poison_nodes": list(plan.poison_nodes),
        "sync_ms_per_round": round(sync_ms, 2),
        "async_ms_per_round": round(async_ms, 2),
        "rounds_per_sec": round(1e3 / async_ms, 2),
        "cost_vs_sync": round(async_ms / sync_ms, 2),
        # async must not change the dispatch structure: still one donated
        # dispatch per M-round block — MEASURED over the timed reps
        "dispatches_per_round": round(asy_calls["n"] / timed_rounds, 4),
        "host_syncs_per_round": round(1.0 / m, 4),
        "staleness_hist": {str(l): hist[l] for l in sorted(hist)},
        "mean_staleness": round(mean_stale, 3),
        "n_delivered": n_del,
        "quarantined": quarantined,
        "expected_quarantined": expected_q,
        "finite_global": finite,
        "async_final_cka": round(float(all_recs[-1]["cross_node_cka"]), 4),
        "sync_final_cka": round(float(sync_recs[-1]["cross_node_cka"]), 4),
    }
    print(f"{name} K={k} M={m} {plan.lag_dist}: sync={sync_ms:.1f}ms "
          f"async={async_ms:.1f}ms/round ({row['rounds_per_sec']} r/s, "
          f"measured dispatches/round {row['dispatches_per_round']}) "
          f"stale-hist={row['staleness_hist']} "
          f"quarantined={quarantined} finite={finite}", flush=True)
    return row


def bench_gram_backend(name: str, k: int, modalities, rounds: int) -> dict:
    """Server-step Gram backend: reference jnp vs the Pallas kernel (MXU
    path on TPU; interpret mode here, so the CPU number is a correctness /
    dispatch-overhead datapoint, not a kernel speed claim)."""
    fedcfg = _fedcfg(k, modalities)
    ref_ms = _time_rounds(Federation(fedcfg, TINY,
                                     gram_backend="reference"), rounds)
    pal_ms = _time_rounds(Federation(fedcfg, TINY,
                                     gram_backend="pallas"), rounds)
    row = {
        "name": name,
        "k_nodes": k,
        "modalities": list(modalities),
        "local_steps": LOCAL_STEPS,
        "reference_ms_per_round": round(ref_ms, 2),
        "pallas_interpret_ms_per_round": round(pal_ms, 2),
        "backend_note": ("pallas runs in interpreter mode on CPU; "
                         "the MXU-tiled path targets TPU"),
    }
    print(f"{name} K={k}: gram reference={ref_ms:.1f}ms "
          f"pallas(interpret)={pal_ms:.1f}ms", flush=True)
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-config CI smoke: K=2, 1 timed round, "
                         "separate output file")
    ap.add_argument("--only", default=None,
                    help="substring filter: run only the matching rows "
                         "and MERGE them into an existing output JSON "
                         "(other rows are kept as-is)")
    ap.add_argument("--out", default=None)
    args, _ = ap.parse_known_args()
    out = args.out or ("BENCH_federation.smoke.json" if args.smoke
                       else "BENCH_federation.json")
    from repro.core.participation import ParticipationPlan
    if args.smoke:
        ks, rounds = (2,), 1
        sweep_modalities = ("genetics", "tabular")
        mixed = ("genetics", "tabular")
        mixed_k = 2
        fused_ms = (2,)                    # CI smoke: M=2 fused block
        fused_modalities = ("genetics", "tabular")
        gram_k = 2
        # one modality -> one width bucket, so the C=1 cohort satisfies
        # the >= 1-slot-per-bucket allocation
        part_rows = [("sampled_cohort_c1_of_k2", 2, ("tabular",), 2,
                      ParticipationPlan(strategy="uniform", cohort_size=1))]
        async_rows = [
            ("async_lagged_k2", 2, fused_modalities, 2,
             ParticipationPlan(strategy="async", lag_dist="geometric",
                               lag_p=0.5, max_lag=2, crash_rate=0.1,
                               rejoin_rate=0.5, seed=11)),
            ("quarantine_1_poisoned", 2, fused_modalities, 2,
             ParticipationPlan(strategy="async", lag_dist="fixed", lag=0,
                               poison_nodes=(1,), seed=13)),
        ]
    else:
        ks = (4, 8) if args.quick else (4, 8, 16)
        rounds = 2 if args.quick else 3
        sweep_modalities = ("image", "text")
        mixed = MIXED_MODALITIES
        mixed_k = 8
        fused_ms = (4,) if args.quick else (4, 16)
        # narrow tokenizers keep per-round compute small: the high-round-
        # rate regime where the host round-trip (dispatch + blocking metric
        # readback) is a visible fraction of the round — what block fusion
        # amortises
        fused_modalities = ("genetics", "tabular")
        gram_k = 8
        # participation rows ride the M=4 fused block: per-round cost must
        # track the cohort size while dispatches stay at 1/M per round
        part_rows = [
            ("sampled_cohort_c4_of_k8", 8, fused_modalities, 4,
             ParticipationPlan(strategy="uniform", cohort_size=4)),
            ("dropout_p25", 8, fused_modalities, 4,
             ParticipationPlan(strategy="dropout", dropout_rate=0.25)),
        ]
        async_rows = [
            ("async_lagged_k8", 8, fused_modalities, 4,
             ParticipationPlan(strategy="async", lag_dist="geometric",
                               lag_p=0.5, max_lag=4, crash_rate=0.1,
                               rejoin_rate=0.5, seed=11)),
            ("quarantine_1_poisoned", 8, fused_modalities, 4,
             ParticipationPlan(strategy="async", lag_dist="fixed", lag=1,
                               poison_nodes=(1,), seed=13)),
        ]
    jobs = [(f"round_latency_k{k}",
             lambda k=k: bench_cfg(f"round_latency_k{k}", k,
                                   sweep_modalities, rounds))
            for k in ks]
    jobs.append((f"mixed_width_bucketed_k{mixed_k}",
                 lambda: bench_mixed_bucketed(
                     f"mixed_width_bucketed_k{mixed_k}", mixed_k, mixed,
                     rounds)))
    jobs += [(f"fused_rounds_m{m}",
              lambda m=m: bench_fused_rounds(f"fused_rounds_m{m}", mixed_k,
                                             fused_modalities, rounds, m))
             for m in fused_ms]
    jobs += [(name, lambda a=(name, k, mods, rounds, m, plan):
              bench_participation(*a))
             for name, k, mods, m, plan in part_rows]
    jobs += [(name, lambda a=(name, k, mods, rounds, m, plan):
              bench_async(*a))
             for name, k, mods, m, plan in async_rows]
    jobs.append((f"gram_backend_k{gram_k}",
                 lambda: bench_gram_backend(f"gram_backend_k{gram_k}",
                                            gram_k, sweep_modalities,
                                            rounds)))
    if args.only:
        jobs = [(n, fn) for n, fn in jobs if args.only in n]
        if not jobs:
            print(f"--only {args.only!r} matches no bench rows")
            return
    rows = [fn() for _, fn in jobs]
    results = {
        "bench": "federation_round_latency",
        "model": "fedmm-small (reduced: 2L/64d)",
        "backend": "cpu",
        "rows": rows,
    }
    if args.only and os.path.exists(out):
        # merge mode: replace matching rows in the existing JSON in place,
        # append rows it didn't have, keep everything else untouched
        with open(out) as fh:
            old = json.load(fh)
        fresh = {r["name"]: r for r in rows}
        merged = [fresh.pop(r.get("name"), r) for r in old.get("rows", ())]
        merged += list(fresh.values())
        results = dict(old)
        results["rows"] = merged
    with open(out, "w") as fh:
        json.dump(results, fh, indent=2)
    print(f"wrote {out}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
