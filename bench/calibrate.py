"""Readings that set a cell's correctness limits, on the chip at the
cell's own size: per seed, the program's compared numbers, the
control's (the plain reference in float8, the precision below the
configuration's bf16) and, for a training cell, each planted fault's.
The benchmark's own runs never run this.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 [--seconds 10]

One process for all seeds; each prints one JSON line.  The window is
only as long as the cell's longest requests need (training needs none).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402
from benchlib import BENCH, CHECKOUT, load_json, load_module  # noqa: E402


def main(argv=None, *, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    bench = load_json(CHECKOUT / "BENCHMARK.json")
    import jax
    if require_chip:
        try:
            run.check_device(1)
        except run.NoChip as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 2
    sys.path.insert(0, str(CHECKOUT / "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = run.load_cell(bench, args.workload, seed, args.seconds)
        driver = load_module(BENCH / "drivers" / f"{cell.mix['driver']}.py")
        st = driver.setup(cell)
        ok = all(driver.kernels_compiled(st).values())
        res = (driver.window(st, args.seconds) if args.seconds > 0
               else {"attempted": 0, "failed": 0})
        out = driver.calibrate(st, res)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "kernels_compiled": ok, "readings": out,
                          "seconds": time.perf_counter() - t0}), flush=True)
        del st
    return 0


if __name__ == "__main__":
    sys.exit(main())
