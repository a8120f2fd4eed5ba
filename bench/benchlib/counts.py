"""Operations and bytes the algorithms need, computed from shapes alone.

Every count here is of what the mathematics requires, not of what an
implementation happens to do: no padding, no recomputation, no reads of
cache positions a token does not attend to.  ``sizes`` is a
configuration file's dict (``d_model``, ``n_layers``, ``n_heads``,
``n_kv_heads``, ``head_dim``, ``d_ff``, ``vocab_size``).
"""
from __future__ import annotations

from typing import Iterable


def linear_shapes(s: dict) -> dict:
    """(d_in, d_out) of every matmul weight of one dense decoder layer."""
    d, h, kv, dh, f = (s["d_model"], s["n_heads"], s["n_kv_heads"],
                       s["head_dim"], s["d_ff"])
    return {"wq": (d, h * dh), "wk": (d, kv * dh), "wv": (d, kv * dh),
            "wo": (h * dh, d), "gate": (d, f), "up": (d, f), "down": (f, d)}


def layer_matmul_params(s: dict) -> int:
    return sum(i * o for i, o in linear_shapes(s).values())


def attention_flops(s: dict, n_pairs: int) -> int:
    """QK^T and PV over ``n_pairs`` (query, key) pairs in one layer."""
    return 4 * s["n_heads"] * s["head_dim"] * n_pairs


# ----------------------------------------------------------------------
# serving
def decode_token_flops(s: dict, context: int) -> int:
    """One decoded token: 2 flops per weight (every layer and the LM head,
    which is the tied embedding where there is one) plus attention over
    the token's own ``context`` positions, itself included."""
    head = s["vocab_size"] * s["d_model"]
    return (2 * (s["n_layers"] * layer_matmul_params(s) + head)
            + s["n_layers"] * attention_flops(s, context))


def decode_attention_cost(s: dict, contexts: Iterable[int],
                          dtype_bytes: int = 2) -> tuple:
    """(flops, bytes) the decode attention needs for tokens whose own
    contexts are ``contexts`` (positions attended, itself included), over
    all layers: q in, the K and V of those positions, the output out."""
    L, h, kv, dh = (s["n_layers"], s["n_heads"], s["n_kv_heads"],
                    s["head_dim"])
    n = total = 0
    for c in contexts:
        n += 1
        total += c
    flops = L * attention_flops(s, total)
    nbytes = L * dtype_bytes * (2 * kv * dh * total + 2 * h * dh * n)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """-> (least seconds, bound) where bound is 'compute' or 'memory'."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
