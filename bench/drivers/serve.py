"""Serving lane: open-loop traffic through ``ServeEngine.serve``, as the
users of a chat or reasoning endpoint feel it.

Set-up makes the weights on the device in one call from the seed (the
plain reference's initialiser, in the configuration's dtype), builds the
engine, generates the request stream from the mix and the seed, and
warms up every shape the stream uses: one admission per distinct prompt
length and the fused decode block.  The window is one ``serve()`` call
over the whole stream, drain included; requests are timed from their
scheduled arrival.  A traced run serves the same stream and traces a
slice of whole decode blocks once the slots have filled (the mix's
``trace``).  The check samples finished requests (the longest among
them) and runs the plain reference over each prompt with its served
tokens: the widest gap by which a served token's logit lies below the
reference's best logit at that position.
"""
from __future__ import annotations

import gc
import time
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import model_config, traffic


def make_params(cell):
    """The weights, on the device, in one jitted call from the seed."""
    init = partial(cell.reference.init_params, s=cell.config,
                   dtype=jnp.dtype(cell.config["dtype"]))
    return jax.block_until_ready(jax.jit(init)(jax.random.PRNGKey(cell.seed)))


def requests(cell, stream):
    from repro.serve.scheduler import Request
    return [Request(rid=r["rid"], tokens=tuple(int(t) for t in r["prompt"]),
                    arrival_s=r["arrival_s"], max_new=r["max_new"])
            for r in stream]


def setup(cell):
    from repro.serve.engine import ServeConfig, ServeEngine
    from repro.serve.scheduler import Request
    e = cell.mix["engine"]
    params = make_params(cell)
    # a completion deadline far past the window only ends a run whose
    # requests never finish; no sound request comes near it
    eng = ServeEngine(params, model_config(cell.config), ServeConfig(
        n_slots=e["n_slots"], cache_len=e["cache_len"],
        block_steps=e["block_steps"], temperature=0.0, seed=cell.seed,
        attn_backend=e["attn_backend"],
        deadline_s=cell.seconds + e["deadline_after_window_s"]))
    stream = traffic.request_stream(cell.mix, cell.seconds, cell.seed,
                                    cell.config["vocab_size"])
    lengths = sorted({len(r["prompt"]) for r in stream})
    rng = np.random.default_rng(cell.seed)
    warm = [Request(rid=len(stream) + i, max_new=2, tokens=tuple(
                int(t) for t in rng.integers(0, cell.config["vocab_size"], n)))
            for i, n in enumerate(lengths)]
    eng.serve(warm)
    jax.block_until_ready(eng.state)
    return SimpleNamespace(cell=cell, eng=eng, params=params,
                           requests=requests(cell, stream))


def kernels_compiled(st) -> dict:
    return {"decode_attention": st.eng.attn_backend == "pallas"
            and not st.eng.attn_interpret}


class BlockHook:
    """Wraps the engine's fused decode block for one ``serve()`` call.

    At each dispatch it notes the host time since ``t0``.  Given a
    ``tracer`` it traces a slice of whole blocks: from the first dispatch
    at or after ``after_s`` for ``blocks`` blocks.  At both ends of the
    slice it notes every request's token count, so the tokens the
    slice's blocks decoded, and the context of each, are known exactly.
    """

    def __init__(self, eng, t0: float, tracer=None, after_s: float = 0.0,
                 blocks: int = 0):
        self.eng, self.t0, self.tracer = eng, t0, tracer
        self.after_s, self.blocks = after_s, blocks
        self.times: list = []             # dispatch times
        self.tokens: list = []            # tokens decoded before each
        self.first = None                 # index of the first traced block
        self.ends = []                    # (time, block_tokens, counts)

    def wrap(self, block):
        def dispatch(*args):
            self._at_dispatch()
            return block(*args)
        return dispatch

    def _mark(self):
        recs = self.eng._sched.records
        self.ends.append((time.perf_counter() - self.t0,
                          self.eng.stats["block_tokens"],
                          {rid: len(r.tokens) for rid, r in recs.items()}))

    def _at_dispatch(self):
        n = len(self.times)
        self.times.append(time.perf_counter() - self.t0)
        self.tokens.append(self.eng.stats["block_tokens"])
        if self.tracer is None:
            return
        if self.first is None and self.times[-1] >= self.after_s:
            self.first = n
            self.tracer.start()
            self._mark()
        elif self.first is not None and n == self.first + self.blocks:
            self.close()

    def close(self):
        """End the slice (at the latest when ``serve()`` returns)."""
        if self.first is not None and len(self.ends) == 1:
            self._mark()
            self.tracer.stop()

    def traced(self, rows_by_rid) -> dict:
        """The slice: its host span and the contexts of the tokens its
        blocks decoded (token j >= 1 of a request with prompt p is decoded
        at context p + j; token 0 comes from the admission's prefill)."""
        if len(self.ends) < 2:
            return None
        (t0, tok0, n0), (t1, tok1, n1) = self.ends
        contexts = [rows_by_rid[rid]["prompt_len"] + j
                    for rid, n in n1.items()
                    for j in range(max(n0.get(rid, 0), 1), n)]
        return {"t0": t0, "t1": t1, "contexts": contexts,
                "decode_tokens": tok1 - tok0,
                "blocks": min(len(self.times) - self.first, self.blocks)}


def window(st, seconds: float, tracer=None) -> dict:
    spec = st.cell.mix["trace"]
    t0 = time.perf_counter()
    hook = BlockHook(st.eng, t0, tracer,
                     after_s=min(spec["after_s"], seconds / 2),
                     blocks=spec["blocks"])
    get_block = st.eng._get_block
    st.eng._get_block = lambda plan: hook.wrap(get_block(plan))
    try:
        recs = st.eng.serve(st.requests, sync_ttft=False)
    finally:
        hook.close()
        del st.eng._get_block
    rows = {rid: {"arrival_s": r.request.arrival_s,
                  "admitted_s": r.admitted_s,
                  "first_token_s": r.first_token_s,
                  "finished_s": r.finished_s,
                  "prompt_len": len(r.request.tokens),
                  "tokens": list(r.tokens), "prompt": r.request.tokens,
                  "completed": r.state == "completed"
                  and len(r.tokens) == r.request.max_new}
            for rid, r in recs.items()}
    failed = sum(not r["completed"] for r in rows.values())
    period = np.diff(hook.times) * 1e3
    diag = {"blocks": len(hook.times)}
    if len(period):
        diag.update(block_period_ms_p50=float(np.median(period)),
                    block_period_ms_p95=float(np.percentile(period, 95)),
                    block_period_ms_max=float(period.max()))
    return {"requests": list(rows.values()), "attempted": len(rows),
            "failed": failed, "stats": dict(st.eng.stats),
            "traced": hook.traced(rows) if tracer is not None else None,
            "diag": diag, "block_log": (hook.times, hook.tokens)}


# ----------------------------------------------------------------------
def sample(rows, n: int, seed: int) -> list:
    """``n`` finished requests drawn from the seed, the longest among
    them."""
    done = [r for r in rows if r["completed"]]
    if not done:
        return []
    longest = max(done, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def make_gaps(reference, sizes, length: int, control: str = ""):
    """-> jitted f(params, seq, served, start, n) -> (length,) gaps.
    ``seq`` is the prompt and the served tokens but the last, zero-padded
    to ``length``; served token j sits in ``served[j]`` and is predicted
    from position ``start + j - 1``.  Entry j of the result is the f32
    reference's best logit there minus that token's logit; with
    ``control`` the token is instead the one the ``control`` precision
    puts first.  Entries from ``n`` on hold 0."""
    ein = reference.make_einsum("f32")

    def f(params, seq, served, start, n):
        lg = reference.logits(ein, params, seq[None], sizes)[0]
        pos = jnp.arange(length)
        src = jnp.clip(start + pos - 1, 0, length - 1)
        rows = lg[src]
        if control:
            ctl = reference.logits(reference.make_einsum(control), params,
                                   seq[None], sizes)[0]
            tok = ctl[src].argmax(-1)
        else:
            tok = served
        gap = rows.max(-1) - jnp.take_along_axis(rows, tok[:, None], -1)[:, 0]
        return jnp.where(pos < n, gap, 0.0)

    return jax.jit(f)


def widest_gap(cell, params, picked, control: str = "") -> float:
    """Widest gap over every served token of the ``picked`` requests."""
    length = cell.mix["engine"]["cache_len"]
    f = make_gaps(cell.reference, cell.config, length, control=control)
    worst = 0.0
    for r in picked:
        seq, served = np.zeros(length, np.int32), np.zeros(length, np.int32)
        full = list(r["prompt"]) + r["tokens"][:-1]
        seq[:len(full)] = full
        served[:len(r["tokens"])] = r["tokens"]
        g = f(params, jnp.asarray(seq), jnp.asarray(served), r["prompt_len"],
              len(r["tokens"]))
        worst = max(worst, float(jnp.max(g)))
    return worst


def _picked(st, window_result):
    st.eng = None                     # free the cache pool before the reference
    gc.collect()
    return sample(window_result["requests"],
                  st.cell.mix["check"]["sample_requests"], st.cell.seed)


def check(st, window_result) -> dict:
    picked = _picked(st, window_result)
    with jax.default_matmul_precision("highest"):
        gap = (widest_gap(st.cell, st.params, picked) if picked
               else float("nan"))
    return {"logit_gap": {"value": gap,
                          "limit": st.cell.limits["logit_gap"]},
            "unfinished": {"value": float(window_result["failed"]),
                           "limit": 0.0}}


def calibrate(st, window_result) -> dict:
    """Readings that set the limit: the program's widest gap and the
    control's (the token float8 puts first, read by the float32
    reference) over the same sampled requests."""
    picked = _picked(st, window_result)
    with jax.default_matmul_precision("highest"):
        return {"program": {"logit_gap": widest_gap(st.cell, st.params,
                                                    picked)},
                "control": {"logit_gap": widest_gap(st.cell, st.params,
                                                    picked, "fp8")},
                "unfinished": window_result["failed"],
                "served_tokens": sum(len(r["tokens"]) for r in picked)}
