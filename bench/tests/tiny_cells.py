"""A throwaway copy of the benchmark with a tiny cell added as data
files only (configurations, mixes, limits and BENCHMARK.json entries),
for the CPU tests: a new cell needs no edit of an existing file."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TINY = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab_size": 512, "max_seq_len": 256,
        "rope_theta": 10000.0, "norm_eps": 1e-05, "reduced": [],
        "source": "test", "reference": "dense_ref"}
SERVE_LIMITS = {"logit_gap": 0.01}


def _dump(path: Path, obj) -> None:
    path.write_text(json.dumps(obj, indent=1))


def make_checkout(root: Path) -> Path:
    """Copy ``bench/`` under ``root``, link the program's ``src``, and add
    the cell ``serve.tiny`` by new files alone."""
    root.mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(REPO / "src", root / "src")
    b = root / "bench"
    _dump(b / "configs" / "smollm-tiny.json",
          dict(TINY, registry="smollm-135m", dtype="bfloat16",
               tie_embeddings=True))
    srv = json.loads((b / "mixes" / "serve-decode-heavy.json").read_text())
    srv["engine"].update(n_slots=4, cache_len=128, block_steps=4,
                         deadline_after_window_s=10)
    srv["arrivals"]["rate_per_s"] = 4.0
    srv["prompt_len"].update(median=16, min=8, max=40, distinct=6)
    srv["max_new"].update(median=12, min=4, max=40)
    srv["check"]["sample_requests"] = 3
    srv["trace"] = {"after_s": 0.5, "blocks": 4}
    _dump(b / "mixes" / "serve-tiny.json", srv)
    _dump(b / "cells" / "serve.tiny.json", {"limits": SERVE_LIMITS})
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append(
        {"name": "smollm-tiny", "source": "test", "reduced": [], "why": "test",
         "file": "bench/configs/smollm-tiny.json"})
    spec["workloads"].append(
        {"name": "serve.tiny", "config": "smollm-tiny",
         "traffic": "serve-tiny", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and m["workloads"][0].startswith("serve."):
            m["workloads"].append("serve.tiny")
    # one per-layer metric that is a new file of the copy alone
    spec["per_layer"].append(
        {"name": "serve.block_dispatches", "unit": "blocks", "better": "lower",
         "source": "program_counter", "layer": "serving engine",
         "moves": "tpot_p95_ms", "workloads": ["serve.tiny"]})
    (b / "metrics" / "serve.block_dispatches.py").write_text(
        'def read(run):\n    return run.window["stats"]["block_dispatches"]\n')
    _dump(root / "BENCHMARK.json", spec)
    return root


def cpu_env(root: Path) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"))
    env.pop("XLA_FLAGS", None)
    return env


def python(root: Path, code: str, timeout: float = 600):
    """Run ``code`` in a fresh process on the CPU, from the copy's root,
    with the copy's ``bench`` and ``src`` on the path."""
    head = (f"import sys; sys.path.insert(0, {str(root / 'bench')!r}); "
            f"sys.path.insert(0, {str(root / 'src')!r})\n")
    return subprocess.run([sys.executable, "-c", head + code], cwd=root,
                          env=cpu_env(root), capture_output=True, text=True,
                          timeout=timeout)


def run_cell(root: Path, argv, *, prelude: str = "",
             require_chip: bool = False, timeout: float = 600):
    """Run ``bench/run.py`` of the copy in a fresh process on the CPU,
    without its look for a chip unless ``require_chip``; ``prelude``
    runs first."""
    return python(root, f"{prelude}\nimport run; sys.exit(run.main("
                        f"{list(argv)!r}, require_chip={require_chip}))",
                  timeout)


# Runs several cases in one process, each with one fault planted in the
# program, and prints one JSON line per case: {"case", "rc", "result"}.
CASES_SCRIPT = """
import contextlib, io, json, run
def case(name, argv, plant=None):
    undo = plant() if plant else None
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(argv, require_chip=False)
    if undo:
        undo()
    lines = buf.getvalue().strip().splitlines()
    print(json.dumps({"case": name, "rc": rc,
                      "result": json.loads(lines[-1]) if lines else None}),
          flush=True)
"""


def cases(out: str) -> dict:
    rows = [json.loads(l) for l in out.splitlines() if l.startswith('{"case"')]
    return {r["case"]: r for r in rows}


def last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])
