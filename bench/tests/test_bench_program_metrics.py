"""The readers of the serving engine's own record and program names
(CPU): on hand-built runs, on a trace recorded before the program named
its programs, and on a tiny engine's traced slice."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

import tiny_cells

sys.path.insert(0, str(tiny_cells.BENCH))
from benchlib import BENCH, load_module  # noqa: E402
from benchlib import trace as tr  # noqa: E402
from benchlib.trace import Event  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "serve_tiny.xplane.pb"
SPAN_METRICS = ("serve.host_ms_per_block", "serve.block_period_max_ms",
                "serve.block_period_max_host_ms",
                "serve.block_period_max_offcpu_ms")
MS = 1_000_000


def read(metric, run):
    return load_module(BENCH / "metrics" / f"{metric}.py").read(run)


def record(i, period_ms, *, wait=0, idle=0, cpu=0, host=(0, 0, 0)):
    """Block ``i``'s record: ``host`` is (admit, dispatch, bookkeep), all
    in ms."""
    admit, dispatch, bookkeep = host
    return SimpleNamespace(
        block=i, period_ns=period_ms * MS, wait_ns=wait * MS,
        idle_ns=idle * MS, cpu_ns=cpu * MS, admit_ns=admit * MS,
        dispatch_ns=dispatch * MS, bookkeep_ns=bookkeep * MS)


def hand_built_run(traced=True):
    """Six blocks dispatched a second apart; the traced slice covers
    2.1-3.05 s, so the periods of blocks 2 and 3 overlap it.  Block 2
    holds the longest period and block 4 the longest outside the slice,
    of which 20 ms idle, 480 ms waited, 100 ms host and 30 ms on the
    CPU."""
    blocks = [record(0, 500, wait=495, cpu=4, host=(1, 1, 2)),
              record(1, 510, wait=505, cpu=4, host=(1, 1, 3)),
              record(2, 2000, wait=505, cpu=5, host=(1, 1, 3)),
              record(3, 520, wait=505, cpu=4, host=(1, 1, 3)),
              record(4, 600, wait=480, idle=20, cpu=30, host=(5, 1, 4)),
              record(5, 505, wait=500, cpu=4, host=(1, 1, 2))]
    stats = {"last_serve": {"blocks": blocks, "totals": {"blocks": 6}}}
    return SimpleNamespace(window={
        "stats": stats, "block_log": ([0.0, 1.0, 2.0, 3.0, 4.0, 5.0],
                                      [0] * 6),
        "traced": {"t0": 2.1, "t1": 3.05} if traced else None})


def test_span_readers_leave_out_the_slice_blocks():
    run = hand_built_run()
    # host work of blocks 0, 1, 4, 5: 4, 5, 10, 4 ms
    assert read("serve.host_ms_per_block", run) == pytest.approx(5.75)
    assert read("serve.block_period_max_ms", run) == pytest.approx(580.0)
    assert read("serve.block_period_max_host_ms", run) == pytest.approx(100.0)
    assert read("serve.block_period_max_offcpu_ms", run) == \
        pytest.approx(70.0)
    # with no slice every block counts, block 2's long period too
    run = hand_built_run(traced=False)
    assert read("serve.block_period_max_ms", run) == pytest.approx(2000.0)
    assert read("serve.block_period_max_host_ms", run) == \
        pytest.approx(1495.0)


@pytest.mark.parametrize("case", ["no_record", "no_blocks", "unmatched"])
def test_span_readers_without_a_record(case):
    run = hand_built_run()
    log = run.window["stats"]["last_serve"]
    if case == "no_record":           # a program that keeps no record
        del run.window["stats"]["last_serve"]
    elif case == "no_blocks":
        log["blocks"], log["totals"]["blocks"] = [], 0
    else:                             # the driver saw another count
        run.window["block_log"][0].append(6.0)
    assert all(read(m, run) is None for m in SPAN_METRICS)


def modules_run(modules):
    return SimpleNamespace(trace=tr.reduce_planes({
        "/host:CPU": {"t": [Event("bench.window", 0, 1000)]},
        "/device:TPU:0": {tr.OPS_LINE: [], tr.MODULES_LINE: modules}}, 1))


def test_prefill_share_on_named_programs():
    run = modules_run([Event("jit_serve_admit(7)", 10, 30),
                       Event("jit_serve_decode_block(3)", 40, 400),
                       Event("jit_serve_admit(8)", 400, 420),
                       Event("jit_serve_decode_block(3)", 430, 790),
                       Event("jit_serve_admit(7)", 1100, 1200)])  # after
    assert read("serve.prefill_device_share", run) == \
        pytest.approx(100 * 40 / 760)
    run = modules_run([Event("jit_serve_decode_block(3)", 40, 400)])
    assert read("serve.prefill_device_share", run) == 0.0


@pytest.mark.parametrize("modules", [
    [], [Event("jit__unknown(1)", 40, 400), Event("jit__admit_impl(2)", 0, 9)],
    [Event("jit_serve_admit(7)", 10, 30)]], ids=["none", "unnamed", "no_block"])
def test_prefill_share_without_a_named_block(modules):
    assert read("serve.prefill_device_share", modules_run(modules)) is None


def test_prefill_share_on_a_trace_from_before_the_names():
    run = SimpleNamespace(trace=tr.reduce(RECORDED, 1,
                                          spans=("bench.serve",
                                                 "bench.serve")))
    assert run.trace.modules                 # it has programs, unnamed
    assert read("serve.prefill_device_share", run) is None


SLICE = """
import json, run
from types import SimpleNamespace
from benchlib import BENCH, load_json, load_module
class Rec:
    def start(self): pass
    def stop(self): pass
bench = load_json("BENCHMARK.json")
cell = run.load_cell(bench, "serve.tiny", 2**31 + 77, 3.0)
drv = load_module(BENCH / "drivers" / "serve.py")
st = drv.setup(cell)
w = drv.window(st, 3.0, Rec())
r = SimpleNamespace(window=w)
print(json.dumps({m: load_module(BENCH / "metrics" / f"{m}.py").read(r)
                  for m in %r}))
"""


def test_span_readers_on_a_tiny_engine(tmp_path):
    root = tiny_cells.make_checkout(tmp_path / "ck")
    p = tiny_cells.python(root, SLICE % (SPAN_METRICS,))
    assert p.returncode == 0, p.stderr[-3000:]
    out = tiny_cells.last_json(p.stdout)
    assert all(out[m] is not None for m in SPAN_METRICS), out
    period, host, off = (out[m] for m in SPAN_METRICS[1:])
    assert 0 <= off <= host <= period
    assert 0 < out["serve.host_ms_per_block"] < period


def test_new_metrics_are_listed_for_the_cell():
    spec = json.loads((tiny_cells.REPO / "BENCHMARK.json").read_text())
    names = {m["name"]: m for m in spec["per_layer"]}
    for m in SPAN_METRICS + ("serve.prefill_device_share",):
        assert names[m]["workloads"] == ["serve.smollm-135m.decode-heavy"]
        assert (BENCH / "metrics" / f"{m}.py").is_file()
