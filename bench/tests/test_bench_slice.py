"""The traced slice of a serving run (CPU): the block hook's token
accounting on a stand-in engine and on a tiny real one, the readers that
read only the slice, and the knee sweep's rate arithmetic."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

import tiny_cells

sys.path.insert(0, str(tiny_cells.BENCH))
from benchlib import BENCH, counts, load_module  # noqa: E402
from benchlib import trace as tr  # noqa: E402
from benchlib.trace import Event  # noqa: E402

serve = load_module(BENCH / "drivers" / "serve.py")


class Recorder:
    def __init__(self):
        self.calls = []

    def start(self):
        self.calls.append("start")

    def stop(self):
        self.calls.append("stop")


def test_block_hook_counts_the_slice_tokens():
    """Request 0 decodes through the slice, 1 is admitted inside it (its
    first token is the prefill's), 2 finishes before it ends."""
    recs = {0: SimpleNamespace(tokens=[1] * 5), 2: SimpleNamespace(tokens=[1])}
    eng = SimpleNamespace(stats={"block_tokens": 10},
                          _sched=SimpleNamespace(records=recs))
    rec = Recorder()
    hook = serve.BlockHook(eng, t0=-100.0, tracer=rec, after_s=0.0, blocks=2)
    block = hook.wrap(lambda *a: "ran")
    assert block() == "ran" and rec.calls == ["start"]
    recs[0].tokens += [1] * 4                   # decoded 5..8
    recs[1] = SimpleNamespace(tokens=[1])       # admitted: prefill token
    recs[2].tokens += [1] * 3                   # decoded 1..3, finished
    eng.stats["block_tokens"] += 7
    block()
    recs[0].tokens += [1] * 4                   # decoded 9..12
    recs[1].tokens += [1] * 4                   # decoded 1..4
    eng.stats["block_tokens"] += 8
    block()                                     # the third dispatch ends it
    assert rec.calls == ["start", "stop"]
    hook.close()
    assert rec.calls == ["start", "stop"]
    rows = {0: {"prompt_len": 100}, 1: {"prompt_len": 10},
            2: {"prompt_len": 50}}
    sl = hook.traced(rows)
    assert sl["decode_tokens"] == 15 == len(sl["contexts"])
    assert sorted(sl["contexts"]) == sorted(
        [100 + j for j in range(5, 13)] + [10 + j for j in range(1, 5)]
        + [50 + j for j in range(1, 4)])
    assert sl["blocks"] == 2 and sl["t0"] < sl["t1"]


def test_readers_read_only_the_slice():
    cfg = {"d_model": 8, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
           "head_dim": 2, "d_ff": 16, "vocab_size": 32}
    peaks = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8}
    trace = tr.reduce_planes({
        "/host:CPU": {"t": [Event("bench.window", 0, 2e9)]},
        "/device:TPU:0": {
            tr.OPS_LINE: [Event("k " + tr.KERNEL_TAG, 1e8, 3e8)],
            tr.MODULES_LINE: [Event("jit__unknown(1)", 5e7, 1.05e9)]}}, 1)
    reqs = [{"arrival_s": a, "admitted_s": a + w} for a, w in
            ((1.0, 9.0), (10.5, 0.2), (11.0, 0.4), (11.5, 0.6), (19.0, 5.0))]
    run = SimpleNamespace(
        cell=SimpleNamespace(config=cfg), peaks=peaks, trace=trace,
        window={"requests": reqs, "traced": {
            "t0": 10.0, "t1": 20.0, "contexts": [4, 5, 6]}})
    read = lambda m: load_module(BENCH / "metrics" / f"{m}.py").read(run)
    assert read("serve.queue_wait_p50_ms") == pytest.approx(400.0)
    flops = sum(counts.decode_token_flops(cfg, c) for c in (4, 5, 6))
    assert read("serve.decode_step_mfu") == pytest.approx(
        100 * flops / (1.0 * 1e9))
    f, b = counts.decode_attention_cost(cfg, [4, 5, 6])
    assert read("serve.decode_attn_roofline") == pytest.approx(
        100 * max(f / 1e9, b / 1e8) / 0.2)
    run.window["traced"] = None
    assert all(read(m) is None for m in (
        "serve.queue_wait_p50_ms", "serve.decode_step_mfu",
        "serve.decode_attn_roofline"))


def test_sweep_served_rate():
    sweep = load_module(BENCH / "sweep.py")
    log = ([0.0, 1.0, 2.0, 3.0, 4.0], [0, 100, 250, 400, 500])
    assert sweep.served_rate(log, 1.0, 3.5) == pytest.approx(150.0)
    assert sweep.served_rate(log, 0.5, 4.0) == pytest.approx(400 / 3)
    assert sweep.served_rate(log, 3.5, 3.9) is None


SLICE = """
import json, run
from benchlib import BENCH, load_json, load_module
class Rec:
    calls = []
    def start(self): self.calls.append("start")
    def stop(self): self.calls.append("stop")
bench = load_json("BENCHMARK.json")
cell = run.load_cell(bench, "serve.tiny", 31, 3.0)
drv = load_module(BENCH / "drivers" / "serve.py")
st = drv.setup(cell)
w = drv.window(st, 3.0, Rec())
sl = w["traced"]
print(json.dumps({"calls": Rec.calls, "blocks": sl["blocks"],
                  "tokens": sl["decode_tokens"], "n": len(sl["contexts"]),
                  "t": [sl["t0"], sl["t1"]], "failed": w["failed"]}))
"""


def test_traced_slice_on_a_tiny_engine(tmp_path):
    root = tiny_cells.make_checkout(tmp_path / "ck")
    p = tiny_cells.python(root, SLICE)
    assert p.returncode == 0, p.stderr[-3000:]
    out = tiny_cells.last_json(p.stdout)
    spec = json.loads((root / "bench" / "mixes" / "serve-tiny.json")
                      .read_text())["trace"]
    assert out["calls"] == ["start", "stop"]
    assert out["blocks"] == spec["blocks"] and out["failed"] == 0
    assert out["tokens"] == out["n"] > 0
    assert min(spec["after_s"], 1.5) <= out["t"][0] < out["t"][1]
