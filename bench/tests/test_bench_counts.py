"""Counting functions, the peaks table and the traffic generator of the
chip benchmark, against hand counts at tiny sizes (CPU)."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from benchlib import counts, peaks, traffic  # noqa: E402

S = {"d_model": 8, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 2, "d_ff": 16, "vocab_size": 32}


def test_layer_params_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x16, down 16x8
    assert counts.layer_matmul_params(S) == 64 + 32 + 32 + 64 + 3 * 128


def test_decode_token_flops_by_hand():
    # 2 * (2 layers * 576 + tied head 32 * 8) + 2 layers * 4*4*2 * ctx
    assert counts.decode_token_flops(S, 5) == 2 * (1152 + 256) + 2 * 32 * 5


def test_decode_attention_cost_by_hand():
    flops, nbytes = counts.decode_attention_cost(S, [3, 5])
    assert flops == 2 * 4 * 4 * 2 * 8          # L * 4 H dh * (3 + 5)
    # per layer: K and V of 8 positions (2 heads x 2) + q and out (4 x 2)
    assert nbytes == 2 * 2 * (2 * 2 * 2 * 8 + 2 * 4 * 2 * 2)


def test_roofline_picks_the_binding_bound():
    p = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_seconds(1000, 10, p) == (10.0, "compute")
    assert counts.roofline_seconds(10, 1000, p) == (100.0, "memory")


def test_peaks_known_and_unknown_kind():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


MIX = {"arrivals": {"rate_per_s": 20.0},
       "prompt_len": {"kind": "lognormal", "median": 256, "sigma": 0.8,
                      "min": 32, "max": 1024, "distinct": 64},
       "max_new": {"kind": "lognormal", "median": 256, "sigma": 0.6,
                   "min": 64, "max": 1000}}


def test_stream_same_schedule_for_every_seed():
    """Every seed gets the same multiset of gaps and lengths, in an order
    of its own; one seed gives one stream."""
    a = traffic.request_stream(MIX, 10, 1, 1000)
    b = traffic.request_stream(MIX, 10, 2 ** 31 + 7, 1000)
    assert len(a) == len(b) == 200
    sched = lambda s: [(r["arrival_s"], len(r["prompt"]), r["max_new"])
                       for r in s]
    assert sched(a) != sched(b)
    for k in range(1, 3):
        assert sorted(x[k] for x in sched(a)) == sorted(x[k] for x in sched(b))
    # the gaps are the same quantiles; the first of each order is dropped
    # (the first request arrives at 0)
    from collections import Counter
    gaps = lambda s: Counter(np.round(np.diff([x[0] for x in sched(s)]), 9))
    assert sum((gaps(a) - gaps(b)).values()) <= 1
    assert not all(np.array_equal(x["prompt"], y["prompt"])
                   for x, y in zip(a, b))
    c = traffic.request_stream(MIX, 10, 1, 1000)
    assert sched(c) == sched(a)
    assert all(np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, c))


def test_stream_lengths_stay_in_the_distinct_set():
    s = traffic.request_stream(MIX, 10, 3, 1000)
    allowed = set(traffic.distinct_set(MIX["prompt_len"]).tolist())
    lens = {len(r["prompt"]) for r in s}
    assert lens <= allowed and len(allowed) <= 64
    assert min(lens) >= 32 and max(lens) <= 1024
    assert all(64 <= r["max_new"] <= 1000 for r in s)
    assert all(len(r["prompt"]) + r["max_new"] + 1 <= 2048 for r in s)
    arr = [r["arrival_s"] for r in s]
    assert arr == sorted(arr) and arr[0] == 0.0
    assert 9.0 < arr[-1] < 10.5
