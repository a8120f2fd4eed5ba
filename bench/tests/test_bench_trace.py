"""Trace reduction of the chip benchmark on a hand-built trace with known
intervals and on a small trace recorded on a TPU v5e (CPU)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from benchlib import trace as tr  # noqa: E402
from benchlib.trace import Event  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "serve_tiny.xplane.pb"


def hand_built():
    host = {"python": [Event("bench.window", 100, 1100),
                       Event("bench.block", 120, 560),
                       Event("PjitFunction(step)", 130, 140),
                       Event("bench.block", 700, 1000)]}
    dev0 = {tr.OPS_LINE: [Event("fusion.1", 150, 350),
                          Event("decode_kernel", 300, 500),   # overlaps
                          Event("decode_kernel", 800, 900),
                          Event("fusion.2", 1200, 1300)],     # after window
            tr.MODULES_LINE: [Event("jit_block(1)", 90, 140),    # starts before
                              Event("jit_block(1)", 140, 520),
                              Event("jit__admit_impl(2)", 790, 910)]}
    dev1 = {tr.OPS_LINE: [Event("fusion.1", 100, 1100)], tr.MODULES_LINE: []}
    return {"/host:CPU": host, "/device:TPU:0": dev0, "/device:TPU:1": dev1}


def test_interval_helpers():
    assert tr.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert tr.clip([(0, 5), (8, 12), (20, 30)], 2, 10) == [(2, 5), (8, 10)]
    assert tr.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]


def test_hand_built_one_chip():
    r = tr.reduce_planes(hand_built(), 1)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(450e-9)          # 150-500 and 800-900
    assert r.kernel_seconds("decode_kernel") == pytest.approx(300e-9)
    assert r.kernel_count("decode_kernel") == 2
    runs = r.program_runs(r"^jit_block\(")
    assert [(e.start, e.end) for e in runs] == [(140, 520)]
    holding = r.programs_holding("decode_kernel")
    assert sorted(e.name for e in holding) == ["jit__admit_impl(2)",
                                               "jit_block(1)"]
    assert r.op_seconds_by_name["fusion.1"] == pytest.approx(200e-9)
    b = r.breakdown()
    assert b["device_ops"][0] == ["decode_kernel", pytest.approx(300e-9)]
    # gaps: 500-800 (300 ns, inside no block span), 900-1100 (bench.block
    # until 1000, midpoint 1000), 100-150 (midpoint in bench.block)
    assert [g[1] for g in b["idle_gaps"]] == pytest.approx(
        [300e-9, 200e-9, 50e-9])
    assert [g[0] for g in b["idle_gaps"]] == [
        "bench.window", "bench.block", "bench.block"]


def test_hand_built_averages_chips():
    r = tr.reduce_planes(hand_built(), 2)
    assert r.busy_s == pytest.approx((450e-9 + 1000e-9) / 2)


def test_short_names_tag_pallas_kernels():
    assert tr.short_name("%fusion.3 = f32[4]{0} fusion(%a), kind=kLoop") \
        == "fusion.3"
    k = tr.short_name('%closed_call.18 = bf16[8]{0} custom-call(%a), '
                      'custom_call_target="tpu_custom_call"')
    assert k == "closed_call.18 [tpu_custom_call]"


def test_no_window_span_is_an_error():
    planes = hand_built()
    planes["/host:CPU"]["python"] = planes["/host:CPU"]["python"][1:]
    with pytest.raises(ValueError):
        tr.reduce_planes(planes, 1)


def test_recorded_tpu_trace():
    r = tr.reduce(RECORDED, 1, spans=("bench.serve", "bench.serve"))
    assert 0 < r.busy_s < r.window_s
    # the Pallas decode attention, and the decode blocks that run it
    assert r.kernel_count() == 48 and r.kernel_seconds() > 0
    assert {e.name.split("(")[0] for e in r.programs_holding()} \
        == {"jit__unknown"}
    b = r.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
