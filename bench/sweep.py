"""Find the knee of a serving cell: the highest offered rate that the
engine sustains with the cell's lengths.  One process, one engine, one
warm-up; each rate runs the cell's traffic for ``--seconds``.  The
benchmark's cells offer a fixed rate; this is how it was chosen.

    python3 bench/sweep.py --workload <name> --rates 3.5,8 [--seconds 120]

Per rate it prints one JSON line.  ``served_tokens_per_s`` is the decode
blocks' output between ``--settle`` seconds and the last admission (or
the window's end), so at a rate over the knee, where every slot stays
held, it is the engine's capacity, and ``knee_req_per_s`` is that
capacity over the mix's mean answer.  Under the knee it follows the
offered load, and the queue wait of the first and the last third of the
arrivals stays level.  Give windows several times a long request's
duration.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402
from benchlib import BENCH, CHECKOUT, load_json, load_module, traffic  # noqa


def served_rate(block_log, t_a: float, t_b: float):
    """Tokens the decode blocks emitted between the dispatches nearest
    ``t_a`` and ``t_b``, over the time between those dispatches."""
    t, tok = (np.asarray(x, float) for x in block_log)
    i, j = np.searchsorted(t, t_a), np.searchsorted(t, t_b, "right") - 1
    if j <= i:
        return None
    return float((tok[j] - tok[i]) / (t[j] - t[i]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=120.0)
    ap.add_argument("--settle", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    run.check_device(1)
    sys.path.insert(0, str(CHECKOUT / "src"))
    from repro.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = load_json(CHECKOUT / "BENCHMARK.json")
    cell = run.load_cell(bench, args.workload, args.seed, args.seconds)
    driver = load_module(BENCH / "drivers" / "serve.py")
    st = driver.setup(cell)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, arrivals=dict(cell.mix["arrivals"],
                                           rate_per_s=rate))
        stream = traffic.request_stream(mix, args.seconds, args.seed,
                                        cell.config["vocab_size"])
        st.requests = driver.requests(cell, stream)
        t0 = time.perf_counter()
        res = driver.window(st, args.seconds)
        rows = sorted(res["requests"], key=lambda r: r["arrival_s"])
        k = max(1, len(rows) // 3)
        wait = lambda rs: float(np.median([r["admitted_s"] - r["arrival_s"]
                                           for r in rs]))
        ttft = [r["first_token_s"] - r["arrival_s"] for r in rows]
        t_b = min(args.seconds, max(r["admitted_s"] for r in rows))
        served = served_rate(res["block_log"], args.settle, t_b)
        done = [r for r in rows if args.settle <= r["finished_s"] <= t_b]
        mean_answer = float(np.mean([s["max_new"] for s in stream]))
        print(json.dumps({
            "rate": rate, "requests": len(rows),
            "offered_tokens_per_s": rate * mean_answer,
            "served_tokens_per_s": served,
            "completed_per_s": len(done) / max(t_b - args.settle, 1e-9),
            "knee_req_per_s": served / mean_answer if served else None,
            "interval_s": [args.settle, t_b],
            "wait_p50_first_third_ms": 1e3 * wait(rows[:k]),
            "wait_p50_last_third_ms": 1e3 * wait(rows[-k:]),
            "ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50)),
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "drain_s": max(r["finished_s"] for r in rows)
            - rows[-1]["arrival_s"],
            "unfinished": res["failed"], **res["diag"],
            "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
