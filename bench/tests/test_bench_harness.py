"""The chip benchmark's harness on the CPU: refusals without a chip, a
new cell added by data files alone, and the shape of the result line."""
import json
import os
import subprocess
import sys

import pytest

import tiny_cells

REPO = tiny_cells.REPO
KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_cells.make_checkout(tmp_path_factory.mktemp("bench"))


def test_no_tpu_exits_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "serve.smollm-135m.decode-heavy", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_only_is_refused(tmp_path):
    """A directory with only BENCHMARK.json and bench/: no program."""
    import shutil
    shutil.copytree(REPO / "bench", tmp_path / "bench")
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "serve.smollm-135m.decode-heavy", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_kernel_fallback_exits_without_result(checkout):
    """On the CPU the decode attention runs the jnp reference: with the
    chip look passed, the kernel check still refuses the run."""
    p = tiny_cells.run_cell(
        checkout, ["--workload", "serve.tiny", "--seed", "3",
                   "--seconds", "1"],
        prelude="import run; run.check_device = lambda chips: None",
        require_chip=True)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "not compiled for the chip" in p.stderr


def test_new_cells_from_files_alone(checkout):
    for path in (REPO / "bench").rglob("*"):
        if path.is_file() and "tests" not in path.parts \
                and "__pycache__" not in path.parts:
            rel = path.relative_to(REPO)
            assert (checkout / rel).read_bytes() == path.read_bytes(), rel
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    name = "serve.tiny"
    p = tiny_cells.run_cell(checkout, ["--workload", name, "--seed",
                                       str(2 ** 31 + 11), "--seconds", "2"])
    assert p.returncode == 0, p.stderr[-3000:]
    out = tiny_cells.last_json(p.stdout)
    assert list(out) == KEYS
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in spec["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(out["metrics"]) == want
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    # the compared numbers close stderr, each beside its limit
    tail = p.stderr.strip().splitlines()[-len(out["compared"]):]
    assert all(l.startswith("compared ") and " limit " in l for l in tail)


FAKE_TRACE = """
import jax, run
from benchlib import peaks, trace as tr
from benchlib.trace import Event
peaks.PEAKS["cpu"] = peaks.PEAKS["TPU v5 lite"]
jax.profiler.start_trace = lambda *a, **k: None
jax.profiler.stop_trace = lambda: None
tr.find_xplane = lambda d: "unused"
tr.reduce = lambda path, n: tr.reduce_planes({
    "/host:CPU": {"t": [Event("bench.window", 0, 2e9)]},
    "/device:TPU:0": {
        tr.OPS_LINE: [Event("k " + tr.KERNEL_TAG, 1e8, 2e8),
                      Event("fusion", 3e8, 9e8)],
        tr.MODULES_LINE: [Event("jit__unknown(1)", 5e7, 1e9)]}}, n)
"""


def test_traced_line_has_breakdown_and_device_times(checkout):
    p = tiny_cells.run_cell(
        checkout, ["--workload", "serve.tiny", "--seed", "5",
                   "--seconds", "2", "--trace", "1"], prelude=FAKE_TRACE)
    assert p.returncode == 0, p.stderr[-3000:]
    out = tiny_cells.last_json(p.stdout)
    assert list(out) == KEYS[:-1] + ["breakdown", "compared"]
    assert out["device"]["window_s"] == pytest.approx(2.0)
    assert out["device"]["busy_s"] == pytest.approx(0.7)
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["per_layer"]
            if "serve.tiny" in m.get("workloads", ["serve.tiny"])}
    assert set(out["metrics"]) <= want
    assert out["metrics"]["serve.device_idle_share"]["value"] == \
        pytest.approx(65.0)
    # a per-layer metric added as a new file and a new entry alone
    assert out["metrics"]["serve.block_dispatches"]["value"] > 0
