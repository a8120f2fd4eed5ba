"""One general generator for the benchmark's traffic, driven by a mix
file's parameters.

Every seed gets the same multiset of sizes and gaps, in another order:
lengths and gaps are the distribution's quantiles at (i + 1/2) / n, and
the seed permutes them and draws the token ids.  So two seeds do the same
amount of work, and the spread between runs is the system's, not the
draw's.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n values of the distribution ``spec`` at the midpoints of n equal
    probability bins, clipped to [min, max] and rounded for integer
    specs.  Kinds: ``lognormal`` (median, sigma), ``exponential``
    (mean), ``constant`` (value)."""
    q = (np.arange(n) + 0.5) / n
    kind = spec["kind"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "exponential":
        x = -spec["mean"] * np.log1p(-q)
    elif kind == "constant":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown distribution kind {kind!r}")
    x = np.clip(x, spec.get("min", -math.inf), spec.get("max", math.inf))
    return np.rint(x).astype(np.int64) if spec.get("integer", True) else x


def distinct_set(spec: dict) -> np.ndarray:
    """The ``distinct`` values a length may take: the distribution's own
    quantiles, so the set is the same for every seed."""
    return np.unique(quantiles(spec, spec["distinct"]))


def snap(values: np.ndarray, allowed: np.ndarray) -> np.ndarray:
    """Each value to the nearest allowed one on a log scale."""
    lv, la = np.log(values)[:, None], np.log(allowed)[None, :]
    return allowed[np.abs(lv - la).argmin(axis=1)]


def request_stream(mix: dict, seconds: float, seed: int, vocab: int):
    """Open-loop requests of a serving mix over ``seconds`` seconds.

    -> list of dicts {rid, arrival_s, prompt (int32 array), max_new}.
    The count is ``rate * seconds``; arrival gaps are the exponential
    quantiles of the rate, prompt and output lengths the quantiles of
    their distributions (prompt lengths snapped to the distinct set),
    each list permuted independently by ``seed``, which also draws the
    prompts' token ids."""
    rate = float(mix["arrivals"]["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(seed)
    gaps = quantiles({"kind": "exponential", "mean": 1.0 / rate,
                      "integer": False}, n)
    plen = quantiles(mix["prompt_len"], n)
    if mix["prompt_len"].get("distinct"):
        plen = snap(plen, distinct_set(mix["prompt_len"]))
    new = quantiles(mix["max_new"], n)
    gaps, plen, new = rng.permutation(gaps), rng.permutation(plen), \
        rng.permutation(new)
    arrivals = np.cumsum(gaps) - gaps[0]        # the first arrives at 0
    return [{"rid": i, "arrival_s": float(arrivals[i]),
             "prompt": rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
             "max_new": int(new[i])} for i in range(n)]
