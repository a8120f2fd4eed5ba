"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  Everything about a cell is data: ``BENCHMARK.json``
names its configuration, traffic mix and metrics, and this script finds
``bench/configs/<config>.json``, ``bench/mixes/<traffic>.json``,
``bench/cells/<workload>.json`` (the limits its correctness check holds
the run to), ``bench/drivers/<mix driver>.py`` and
``bench/metrics/<metric>.py`` by those names.

A run: set-up (weights and inputs from ``--seed``, warm-up of every
shape the window uses, the first steps of a training cell), then the
measured window of ``--seconds`` seconds, then the comparison with the
plain reference.  With ``--trace 1`` the same window runs, and the driver
traces a slice of it with the JAX profiler (for serving, whole decode
blocks once the slots have filled); the per-layer metrics are read from
that slice.  The last
line of stdout is one JSON object; the numbers compared and their limits
are the last lines of stderr.  No TPU, too few chips, or a Pallas kernel
that would fall back to the reference or to interpret mode: exit 2 with
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import math
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parent))
from benchlib import BENCH, CHECKOUT, load_json, load_module  # noqa: E402

TRACE_DIR = CHECKOUT / ".bench" / "trace"


class NoChip(Exception):
    """The machine cannot run this cell as it must run: no result."""


def check_device(chips: int):
    import jax
    dev = jax.devices()
    if dev[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {dev[0].platform!r} "
                     f"({dev[0].device_kind}, {len(dev)} devices)")
    if len(dev) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(dev)}")


def load_cell(bench: dict, name: str, seed: int, seconds: float):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = load_json(BENCH / "configs" / f"{w['config']}.json")
    mix = load_json(BENCH / "mixes" / f"{w['traffic']}.json")
    limits = load_json(BENCH / "cells" / f"{name}.json")["limits"]
    return SimpleNamespace(name=name, workload=w, config=config, mix=mix,
                           limits=limits, seed=seed, seconds=seconds,
                           reference=load_module(
                               BENCH / "configs" / f"{config['reference']}.py"))


def metrics_for(bench: dict, kind: str, workload: str):
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def main(argv=None, *, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(CHECKOUT / "BENCHMARK.json")
    cell = load_cell(bench, args.workload, args.seed, args.seconds)
    driver = load_module(BENCH / "drivers" / f"{cell.mix['driver']}.py")

    import jax
    from benchlib.compile_log import CompileLog
    from benchlib.peaks import peaks_for
    try:
        if require_chip:
            check_device(cell.workload["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(CHECKOUT / "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": cell.workload["chips"]}
    log = CompileLog()

    state = driver.setup(cell)
    fallbacks = [k for k, ok in driver.kernels_compiled(state).items()
                 if not ok]
    if require_chip and fallbacks:
        print(f"bench: Pallas kernel not compiled for the chip: "
              f"{fallbacks}", file=sys.stderr)
        return 2
    compiles_setup = log.compiles
    setup_s = time.perf_counter() - T_START

    tracer = None
    if args.trace:
        from benchlib.trace import TracedSlice
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracer = TracedSlice(TRACE_DIR)
    window = driver.window(state, cell.seconds, tracer)
    if tracer is not None:
        tracer.stop()
        if not tracer.started:
            raise RuntimeError("the driver traced no slice of the window")
    compiles_window = log.compiles - compiles_setup
    device["memory_peak_bytes"] = int(max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in dev[:device["count"]]))

    run = SimpleNamespace(cell=cell, setup_s=setup_s, window=window,
                          peaks=peaks_for(device["kind"]) if args.trace
                          else None, trace=None)
    result_metrics, breakdown = {}, None
    if args.trace:
        from benchlib import trace as tr
        run.trace = tr.reduce(tr.find_xplane(TRACE_DIR), device["count"])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()
        kinds = "per_layer"
    else:
        kinds = "end_to_end"
    for m in metrics_for(bench, kinds, cell.name):
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            result_metrics[m["name"]] = {"value": float(value),
                                         "unit": m["unit"]}

    compared = driver.check(state, window)
    for c in compared.values():       # JSON has no NaN: a NaN fails as huge
        if not math.isfinite(c["value"]):
            c["value"] = sys.float_info.max
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    out = {"correct": correct,
           "attempted": window["attempted"], "failed": window["failed"],
           "metrics": result_metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    print(f"bench: setup_s={setup_s!r} compiles_setup={compiles_setup} "
          f"compiles_window={compiles_window} cache_hits={log.cache_hits} "
          + " ".join(f"{k}={v!r}" for k, v in window.get("diag", {}).items()),
          file=sys.stderr)
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
