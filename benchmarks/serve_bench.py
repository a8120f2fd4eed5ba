"""Continuous-batching serving benchmark: fused-block engine vs the
legacy per-token loop, across model families.

For each family the same request stream runs through

  - ``ServeEngine``: slot-stacked cache pool, M decode steps fused into
    one jitted ``lax.scan`` with on-device sampling/stop accounting, one
    host readback per block, mid-decode admission; and
  - ``naive_generate``: the legacy loop — one jit dispatch plus one
    blocking argmax readback per token, head-of-line batches.

Reported per row (everything MEASURED, nothing asserted):

  - tokens/s end-to-end for both paths and the speedup;
  - dispatches/token and host-syncs/token from the engine's counters
    (CI guards these at <= 1/M via ``check_smoke``);
  - TTFT p50/p99 under Poisson arrivals at swept rates (engine runs
    with ``sync_ttft`` — a per-REQUEST sync used only for timestamping);
  - the ``decode_roofline`` memory-bound prediction (bytes/token over
    HBM bandwidth) next to measured throughput, so the gap between
    bandwidth-bound ideal and dispatch-bound reality is visible.

Resilience rows (PR 8):

  - ``overload_*``: the same stream at 2x and 4x the MEASURED
    sustainable Poisson rate, with and without deadline-based shedding —
    goodput, terminal-state accounting, and TTFT p99 (shedding must hold
    p99 bounded where the no-shedding queue grows without bound);
  - ``chaos_*``: a deterministic seeded fault schedule (NaN-poisoned
    logits, a silent slot freeze, host delays, one simulated mid-stream
    crash recovered via snapshot/resume) — fault/stall/retry counters,
    exactly-one-terminal-state accounting, and the no-garbage invariant
    (every emitted token stream is a PREFIX of the fault-free run's).

Run:  python -m benchmarks.serve_bench            -> BENCH_serve.json
      python -m benchmarks.serve_bench --smoke    -> BENCH_serve.smoke.json
      python -m benchmarks.serve_bench --only chaos   (re-run matching
      rows and MERGE them into the existing JSON, like
      federation_round.py)
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import tempfile
import time

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduced
from repro.models import transformer as T
from repro.roofline.analysis import decode_roofline
from repro.serve import (FaultPlan, ServeConfig, ServeEngine,
                         SimulatedCrash, naive_generate, poisson_requests,
                         state_counts)


def _prep(cfg):
    """Expert-capacity headroom: token dropping depends on batch
    composition, which would make the batched engine and the batch-1
    oracle legitimately diverge — not what this bench measures."""
    if cfg.moe is not None:
        cfg = cfg.with_(moe=dataclasses.replace(cfg.moe,
                                                capacity_factor=8.0))
    return cfg


def _tiny(arch):
    """Federation-smoke-sized config (2L/64d) for the CI lane."""
    cfg = reduced(get_config(arch))
    kw = dict(n_layers=2, d_model=64, d_ff=128 if cfg.d_ff else 0,
              vocab_size=256)
    if cfg.n_heads:
        kw.update(n_heads=4, n_kv_heads=min(cfg.n_kv_heads or 1, 2),
                  head_dim=16)
    if cfg.family == "ssm":
        kw.update(ssm=dataclasses.replace(cfg.ssm, chunk=16))
    if cfg.family == "hybrid":
        kw.update(n_layers=3, n_kv_heads=1,
                  rglru=dataclasses.replace(cfg.rglru, lru_width=64,
                                            local_window=32, chunk=16))
    if cfg.sliding_window:
        kw["sliding_window"] = 32
    return _prep(cfg.with_(**kw))


def _gen_tokens(records):
    return sum(len(r.tokens) for r in records.values())


def bench_family(name, cfg, *, n_slots, block_steps, cache_len, n_requests,
                 prompt_len, max_new, max_new_mix=(), ttft_rates=(),
                 reps=1, seed=0):
    """One engine-vs-naive row.  ``max_new_mix`` cycles per-request
    generation lengths — the heavy-tailed regime where the naive loop's
    head-of-line blocking wastes batch slots and continuous admission
    back-fills them.  Timing is best-of-``reps`` after a full warm-up
    pass of each path (CPU wall-clock is noisy)."""
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    scfg = ServeConfig(n_slots=n_slots, cache_len=cache_len,
                       block_steps=block_steps, max_new_tokens=max_new)
    reqs = poisson_requests(n_requests, 0.0, prompt_len=prompt_len,
                            vocab_size=cfg.vocab_size, seed=seed,
                            max_new=None)
    if max_new_mix:
        reqs = [dataclasses.replace(r, max_new=max_new_mix[i %
                                                          len(max_new_mix)])
                for i, r in enumerate(reqs)]

    # ---- engine throughput (warm-up run compiles admission + block) --
    eng = ServeEngine(params, cfg, scfg)
    eng.serve(reqs[:n_slots])
    eng_s = float("inf")
    for _ in range(reps):
        for k in eng.stats:
            eng.stats[k] = 0
        t0 = time.perf_counter()
        recs = eng.serve(reqs)
        eng_s = min(eng_s, time.perf_counter() - t0)
    eng_tokens = _gen_tokens(recs)
    st = eng.stats

    # ---- naive baseline (same batch width, head-of-line) -------------
    # full-stream warm-up: a ragged tail group has its own batch shape,
    # and paying its compile inside the timed run would flatter the engine
    naive_generate(params, cfg, reqs, scfg)
    naive_s = float("inf")
    for _ in range(reps):
        nstats = {}
        t0 = time.perf_counter()
        nrecs = naive_generate(params, cfg, reqs, scfg, stats=nstats)
        naive_s = min(naive_s, time.perf_counter() - t0)
    naive_tokens = _gen_tokens(nrecs)

    mismatch = sum(recs[r.rid].tokens != nrecs[r.rid].tokens for r in reqs)
    roof = decode_roofline(cfg, n_slots=n_slots, cache_len=cache_len)
    eng_tps = eng_tokens / eng_s
    row = {
        "name": name,
        "family": cfg.family,
        "n_slots": n_slots,
        "block_steps": block_steps,
        "cache_len": cache_len,
        "n_requests": n_requests,
        "prompt_len": prompt_len,
        "max_new": max_new,
        "max_new_mix": list(max_new_mix),
        "engine_tokens_per_s": round(eng_tps, 2),
        "naive_tokens_per_s": round(naive_tokens / naive_s, 2),
        "speedup": round((eng_tokens / eng_s) / (naive_tokens / naive_s), 2),
        "tokens_mismatched_vs_naive": mismatch,
        # dispatch structure, measured from the engine's counters
        "dispatches_per_token": round(
            st["block_dispatches"] / max(st["block_tokens"], 1), 4),
        "host_syncs_per_token": round(
            st["block_syncs"] / max(st["block_tokens"], 1), 4),
        "per_token_extra_syncs": st["request_reads"],
        "naive_dispatches_per_token": round(
            nstats["decode_dispatches"] / max(nstats["decode_tokens"], 1), 4),
        "naive_host_syncs_per_token": round(
            nstats["host_syncs"] / max(nstats["decode_tokens"], 1), 4),
        # memory-bound prediction vs measurement
        "roofline": roof,
        "pred_tokens_per_s": round(roof["pred_tokens_per_s"], 2),
        "measured_over_pred": round(eng_tps / roof["pred_tokens_per_s"], 6),
    }

    # ---- TTFT under Poisson arrivals (per-request sync_ttft runs) ----
    ttft = {}
    for rate in ttft_rates:
        sreqs = poisson_requests(n_requests, rate, prompt_len=prompt_len,
                                 vocab_size=cfg.vocab_size, seed=seed + 1)
        e2 = ServeEngine(params, cfg, scfg)
        rr = e2.serve(sreqs, sync_ttft=True)
        lats = sorted(1e3 * r.ttft_s for r in rr.values()
                      if r.ttft_s is not None)
        ttft[f"rate_{rate:g}"] = {
            "p50_ms": round(statistics.median(lats), 2),
            "p99_ms": round(lats[min(len(lats) - 1,
                                     int(0.99 * len(lats)))], 2),
        }
    if ttft:
        row["ttft"] = ttft
    print(f"{name}: engine {row['engine_tokens_per_s']} tok/s, naive "
          f"{row['naive_tokens_per_s']} tok/s ({row['speedup']}x), "
          f"disp/tok {row['dispatches_per_token']} "
          f"(naive {row['naive_dispatches_per_token']})", flush=True)
    return row


def _ttft_ms(records):
    """(p50_ms, p99_ms) over requests that received a first token."""
    lats = sorted(1e3 * r.ttft_s for r in records.values()
                  if r.ttft_s is not None)
    if not lats:
        return None, None
    return (round(statistics.median(lats), 2),
            round(lats[min(len(lats) - 1, int(0.99 * len(lats)))], 2))


def _accounting(records, n_requests):
    counts = state_counts(records)
    ok = sum(counts.get(s, 0) for s in
             ("completed", "shed", "timed_out", "failed")) == n_requests
    return counts, ok


def bench_overload(name, cfg, *, n_slots, block_steps, cache_len,
                   n_requests, prompt_len, max_new,
                   overload_xs=(2.0, 4.0), seed=0):
    """Graceful-degradation row: measure the sustainable service rate,
    then offer the stream at ``overload_xs`` times it, with and without
    SLO shedding.  Without shedding every request eventually runs and
    queue latency (TTFT p99) grows with the backlog; with a TTFT
    deadline + bounded queue, late requests are shed and the p99 of what
    IS served stays bounded near the deadline."""
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    base = ServeConfig(n_slots=n_slots, cache_len=cache_len,
                       block_steps=block_steps, max_new_tokens=max_new)
    calib = poisson_requests(n_requests, 0.0, prompt_len=prompt_len,
                             vocab_size=cfg.vocab_size, seed=seed)
    eng = ServeEngine(params, cfg, base)
    eng.serve(calib)                       # compile
    t0 = time.perf_counter()
    eng.serve(calib)
    svc_s = time.perf_counter() - t0       # all-at-once drain time
    sustainable = n_requests / svc_s
    ttft_deadline = 0.35 * svc_s
    row = {"name": name, "kind": "overload", "family": cfg.family,
           "n_slots": n_slots, "block_steps": block_steps,
           "n_requests": n_requests, "max_new": max_new,
           "sustainable_req_s": round(sustainable, 2),
           "ttft_deadline_s": round(ttft_deadline, 4), "sweeps": {}}
    last_stats = None
    for x in overload_xs:
        rate = x * sustainable
        reqs = poisson_requests(n_requests, rate, prompt_len=prompt_len,
                                vocab_size=cfg.vocab_size, seed=seed + 1)
        sweep = {"rate_req_s": round(rate, 2)}
        for label, scfg in (
                ("noshed", base),
                ("shed", dataclasses.replace(
                    base, ttft_deadline_s=ttft_deadline,
                    queue_cap=2 * n_slots))):
            e = ServeEngine(params, cfg, scfg)
            e.serve(calib[:n_slots])     # compile admit + block outside
            for k in e.stats:            # the timed window
                e.stats[k] = 0
            t0 = time.perf_counter()
            recs = e.serve(reqs, sync_ttft=True)
            wall = time.perf_counter() - t0
            counts, ok = _accounting(recs, n_requests)
            p50, p99 = _ttft_ms(recs)
            sweep[label] = {
                "counts": counts, "accounting_ok": ok,
                "goodput_req_s": round(counts["completed"] / wall, 2),
                "ttft_p50_ms": p50, "ttft_p99_ms": p99,
            }
            last_stats = e.stats
        sweep["shed_bounds_ttft_p99"] = (
            sweep["shed"]["ttft_p99_ms"] is not None
            and sweep["shed"]["ttft_p99_ms"]
            <= sweep["noshed"]["ttft_p99_ms"])
        row["sweeps"][f"x{x:g}"] = sweep
    st = last_stats
    row["dispatches_per_token"] = round(
        st["block_dispatches"] / max(st["block_tokens"], 1), 4)
    row["host_syncs_per_token"] = round(
        st["block_syncs"] / max(st["block_tokens"], 1), 4)
    top = row["sweeps"][f"x{overload_xs[-1]:g}"]
    print(f"{name}: sustainable {row['sustainable_req_s']} req/s; at "
          f"{overload_xs[-1]:g}x noshed p99 {top['noshed']['ttft_p99_ms']}"
          f"ms vs shed p99 {top['shed']['ttft_p99_ms']}ms "
          f"(shed {top['shed']['counts']['shed']}/{n_requests})",
          flush=True)
    return row


def bench_chaos(name, cfg, *, n_slots, block_steps, cache_len, n_requests,
                prompt_len, max_new, crash_after_block=2, seed=0):
    """Chaos row: a seeded deterministic fault schedule — NaN-poisoned
    logits on chosen global steps, a silent slot freeze the stall
    watchdog must catch, host-side block delays, and one simulated
    engine crash recovered through the serve snapshot.  Gated
    invariants: every request lands in exactly one terminal state, every
    emitted token stream is a PREFIX of the fault-free run's (no token
    derived from poisoned logits ever escapes), completed requests match
    the clean run exactly, and the dispatch structure stays <= 1/M."""
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    scfg = ServeConfig(n_slots=n_slots, cache_len=cache_len,
                       block_steps=block_steps, max_new_tokens=max_new,
                       max_attempts=3, retry_backoff_s=0.0,
                       stall_blocks=2, guard_nonfinite=True)
    reqs = poisson_requests(n_requests, 0.0, prompt_len=prompt_len,
                            vocab_size=cfg.vocab_size, seed=seed)
    clean = ServeEngine(params, cfg, scfg).serve(reqs)
    m = block_steps
    plan = FaultPlan(
        nan_steps=(m + 1, 3 * m), nan_slots=(0, min(2, n_slots - 1)),
        freeze_steps=tuple(range(2 * m, 5 * m)),
        freeze_slots=(min(1, n_slots - 1),),
        delay_blocks=(1, 3), delay_s=0.002,
        crash_after_block=crash_after_block)
    snap = os.path.join(tempfile.gettempdir(), f"serve_snap_{name}.npz")
    eng = ServeEngine(params, cfg, scfg)
    t0 = time.perf_counter()
    resumed = False
    try:
        recs = eng.serve(reqs, fault_plan=plan, snapshot_path=snap,
                         snapshot_every_blocks=1)
        stats = dict(eng.stats)
    except SimulatedCrash:
        eng2 = ServeEngine.resume(snap, params, cfg)
        recs = eng2.resume_serve(
            fault_plan=dataclasses.replace(plan, crash_after_block=-1))
        resumed = True
        stats = {k: eng.stats[k] + eng2.stats[k] for k in eng.stats
                 if k != "last_serve"}
    wall = time.perf_counter() - t0
    counts, ok = _accounting(recs, n_requests)
    prefix_ok = all(
        recs[r.rid].tokens == clean[r.rid].tokens[:len(recs[r.rid].tokens)]
        for r in reqs)
    completed_match = all(recs[r.rid].tokens == clean[r.rid].tokens
                          for r in reqs
                          if recs[r.rid].state == "completed")
    row = {
        "name": name, "kind": "chaos", "family": cfg.family,
        "n_slots": n_slots, "block_steps": block_steps,
        "n_requests": n_requests, "max_new": max_new,
        "counts": counts, "accounting_ok": ok,
        "goodput_req_s": round(counts["completed"] / wall, 2),
        "faults_detected": stats["faults_detected"],
        "stalls_detected": stats["stalls_detected"],
        "retries": sum(recs[r.rid].retries for r in reqs),
        "snapshot_writes": stats["snapshot_writes"],
        "resumed_after_crash": resumed,
        "prefix_clean_ok": prefix_ok,
        "completed_match_clean": completed_match,
        "dispatches_per_token": round(
            stats["block_dispatches"] / max(stats["block_tokens"], 1), 4),
        "host_syncs_per_token": round(
            stats["block_syncs"] / max(stats["block_tokens"], 1), 4),
    }
    if os.path.exists(snap):
        os.remove(snap)
    print(f"{name}: {counts} | faults {row['faults_detected']} stalls "
          f"{row['stalls_detected']} retries {row['retries']} | resumed "
          f"{resumed} | prefix_clean {prefix_ok}", flush=True)
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-config CI smoke, separate output file")
    ap.add_argument("--only", default=None,
                    help="substring filter: run only the matching rows "
                         "and MERGE them into an existing output JSON "
                         "(other rows are kept as-is)")
    ap.add_argument("--out", default=None)
    args, _ = ap.parse_known_args()
    out = args.out or ("BENCH_serve.smoke.json" if args.smoke
                       else "BENCH_serve.json")
    if args.smoke:
        fams = [("dense_gqa", _tiny("qwen3-32b")),
                ("ssm_mamba", _tiny("falcon-mamba-7b"))]
        jobs = [(name, lambda name=name, cfg=cfg: bench_family(
                    name, cfg, n_slots=4, block_steps=4, cache_len=48,
                    n_requests=6, prompt_len=8, max_new=8))
                for name, cfg in fams]
        jobs.append(("chaos_dense_gqa", lambda: bench_chaos(
            "chaos_dense_gqa", _tiny("qwen3-32b"), n_slots=4,
            block_steps=4, cache_len=48, n_requests=8, prompt_len=8,
            max_new=12)))
        jobs.append(("overload_dense_gqa", lambda: bench_overload(
            "overload_dense_gqa", _tiny("qwen3-32b"), n_slots=4,
            block_steps=4, cache_len=48, n_requests=32, prompt_len=8,
            max_new=16, overload_xs=(4.0,))))
    else:
        # primary regime: small per-step compute (dispatch-bound, the
        # CPU proxy for accelerator decode) + heavy-tailed generation
        # lengths, where head-of-line blocking wastes the naive loop's
        # batch slots and continuous admission back-fills them
        mix = (96, 4, 64, 8, 96, 4, 32, 8)
        fams = [("dense_gqa", _tiny("qwen3-32b")),
                ("swa_ring", _tiny("mistral-nemo-12b")),
                ("mla_latent", _tiny("deepseek-v2-236b")),
                ("ssm_mamba", _tiny("falcon-mamba-7b")),
                ("hybrid_rglru", _tiny("recurrentgemma-9b"))]
        kw = dict(n_slots=8, block_steps=16, cache_len=128, n_requests=24,
                  prompt_len=8, max_new=96, max_new_mix=mix, reps=3,
                  ttft_rates=(8.0, 32.0))
        jobs = [(name, lambda name=name, cfg=cfg: bench_family(
                    name, cfg, **kw)) for name, cfg in fams]
        # secondary regime: wider (d=256) models where per-step compute
        # dominates dispatch overhead on CPU — the fused-block win
        # shrinks, which the roofline column makes legible
        for name, arch in (("dense_gqa_d256", "qwen3-32b"),
                           ("ssm_mamba_d256", "falcon-mamba-7b")):
            jobs.append((name, lambda name=name, arch=arch: bench_family(
                name, _prep(reduced(get_config(arch))), n_slots=8,
                block_steps=8, cache_len=128, n_requests=16, prompt_len=16,
                max_new=32, reps=2)))
        # resilience rows: overload shedding + seeded chaos with
        # mid-stream crash recovery (see module docstring)
        jobs.append(("overload_dense_gqa", lambda: bench_overload(
            "overload_dense_gqa", _tiny("qwen3-32b"), n_slots=8,
            block_steps=8, cache_len=64, n_requests=48, prompt_len=8,
            max_new=24, overload_xs=(2.0, 4.0))))
        jobs.append(("chaos_dense_gqa", lambda: bench_chaos(
            "chaos_dense_gqa", _tiny("qwen3-32b"), n_slots=8,
            block_steps=8, cache_len=64, n_requests=16, prompt_len=8,
            max_new=24, crash_after_block=3)))
        jobs.append(("chaos_ssm_mamba", lambda: bench_chaos(
            "chaos_ssm_mamba", _tiny("falcon-mamba-7b"), n_slots=8,
            block_steps=8, cache_len=64, n_requests=16, prompt_len=8,
            max_new=24, crash_after_block=3)))
    if args.only:
        jobs = [(n, fn) for n, fn in jobs if args.only in n]
        if not jobs:
            print(f"--only {args.only!r} matches no bench rows")
            return
    rows = [fn() for _, fn in jobs]
    results = {
        "bench": "serve_continuous_batching",
        "backend": jax.default_backend(),
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
