"""The serving engine's own tracing: the per-block record of each call
(``stats["last_serve"]``), the ``serve.*`` host spans a profiler session
records, and the names of its compiled programs."""
import dataclasses
import glob

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.serve import (ServeConfig, ServeEngine, naive_generate,
                         poisson_requests)
from repro.serve import engine as E
from repro.serve.faults import FaultPlan

KEY = jax.random.PRNGKey(0)
SCFG = ServeConfig(n_slots=3, cache_len=64, block_steps=4, max_new_tokens=10)
PHASES = ("admit_ns", "dispatch_ns", "wait_ns", "bookkeep_ns", "idle_ns")


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("fedmm-small").with_(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256, dtype="float32")
    return cfg, T.init_params(KEY, cfg)


def _stream(cfg, n=7, gap_s=0.02, seed=11, late_s=0.0):
    """``n`` requests, staggered so that later ones are admitted between
    blocks; with ``late_s`` the last arrives then, after the others have
    finished, so that the engine idles."""
    reqs = poisson_requests(n, 0.0, prompt_len=8,
                            vocab_size=cfg.vocab_size, seed=seed)
    reqs = [dataclasses.replace(r, arrival_s=gap_s * i)
            for i, r in enumerate(reqs)]
    if late_s:
        reqs[-1] = dataclasses.replace(reqs[-1], arrival_s=late_s)
    return reqs


def _check_record(log: dict, stats_before: dict, stats: dict):
    blocks, tot = list(log["blocks"]), log["totals"]
    n = stats["block_dispatches"] - stats_before["block_dispatches"]
    assert n > 0 and [r.block for r in blocks] == list(range(n))
    assert tot["blocks"] == n
    assert all(a.t_s < b.t_s for a, b in zip(blocks, blocks[1:]))
    for r in blocks + [log["lead"]]:
        assert all(getattr(r, f) >= 0 for f in PHASES + ("cpu_ns", "gc_ns"))
        assert sum(getattr(r, f) for f in PHASES) <= r.period_ns
        # CPU time of the host's own work is at most its wall time
        assert r.cpu_ns <= r.period_ns - r.wait_ns - r.idle_ns
        assert r.gc_ns <= r.period_ns
    assert all(r.live_slots >= 1 for r in blocks)
    admits = stats["admit_dispatches"] - stats_before["admit_dispatches"]
    assert log["lead"].admits + sum(r.admits for r in blocks) \
        == tot["admits"] == admits
    for f in PHASES + ("period_ns", "cpu_ns", "gc_ns", "live_slots"):
        assert tot[f] == getattr(log["lead"], f) + sum(
            getattr(r, f) for r in blocks)


@pytest.mark.parametrize("sync_ttft", [False, True])
def test_one_record_per_block(tiny, sync_ttft):
    cfg, params = tiny
    eng = ServeEngine(params, cfg, SCFG)
    reqs = _stream(cfg, late_s=1.0)
    eng.serve(reqs[:1])                      # compile outside the call
    before = dict(eng.stats)
    recs = eng.serve(reqs, sync_ttft=sync_ttft)
    assert all(r.state == "completed" for r in recs.values())
    log = eng.stats["last_serve"]
    _check_record(log, before, eng.stats)
    assert log["totals"]["idle_ns"] > 0          # the staggered arrivals
    if sync_ttft:                 # the first-token waits count as waits
        assert log["totals"]["wait_ns"] > sum(
            r.wait_ns for r in log["blocks"])


def test_last_serve_resets_while_counters_keep_counting(tiny):
    cfg, params = tiny
    eng = ServeEngine(params, cfg, SCFG)
    assert eng.stats["last_serve"] is None
    first_before = dict(eng.stats)
    eng.serve(_stream(cfg, n=2, gap_s=0.0))
    first = eng.stats["last_serve"]
    _check_record(first, first_before, eng.stats)
    second_before = dict(eng.stats)
    eng.serve(_stream(cfg, n=5, seed=3))
    second = eng.stats["last_serve"]
    assert second is not first
    _check_record(second, second_before, eng.stats)
    assert eng.stats["block_dispatches"] == (first["totals"]["blocks"]
                                             + second["totals"]["blocks"])
    assert eng.stats["admit_dispatches"] == (first["totals"]["admits"]
                                             + second["totals"]["admits"])


def test_record_keeps_the_last_blocks(tiny, monkeypatch):
    cfg, params = tiny
    monkeypatch.setattr(E, "MAX_BLOCK_RECORDS", 2)
    eng = ServeEngine(params, cfg, SCFG)
    eng.serve(_stream(cfg))
    log = eng.stats["last_serve"]
    n = log["totals"]["blocks"]
    assert n > 2 and [r.block for r in log["blocks"]] == [n - 2, n - 1]
    assert n == eng.stats["block_dispatches"]


def test_host_delay_is_host_time_off_the_cpu(tiny):
    """A chaos host delay before block 1 sleeps in block 0's period,
    outside every span: host time, and off the CPU."""
    cfg, params = tiny
    delay = 0.2
    eng = ServeEngine(params, cfg, SCFG)
    eng.serve(_stream(cfg, n=3, gap_s=0.0),
              fault_plan=FaultPlan(delay_blocks=(1,), delay_s=delay))
    r = eng.stats["last_serve"]["blocks"][0]
    host = r.period_ns - r.wait_ns - r.idle_ns
    assert host >= delay * 1e9
    assert host - r.cpu_ns >= 0.9 * delay * 1e9
    assert sum(getattr(r, f) for f in PHASES) < r.period_ns - delay * 1e9


def test_program_names(tiny):
    cfg, params = tiny
    eng = ServeEngine(params, cfg, SCFG)
    block = eng._get_block(None).lower(
        eng.params, eng.state, jnp.zeros((SCFG.n_slots,), bool))
    assert block.as_text().startswith("module @jit_serve_decode_block ")
    admit = eng._admit.lower(
        eng.params, eng.state, {"tokens": jnp.zeros((1, 8), jnp.int32)},
        KEY, jnp.asarray(4, jnp.int32), jnp.asarray(0, jnp.int32))
    assert admit.as_text().startswith("module @jit_serve_admit ")


def _host_spans(log_dir) -> list:
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for p in data.planes if p.name.startswith("/host")
            for ln in p.lines for e in ln.events
            if e.name.startswith("serve.")]


def test_profiler_records_the_spans(tiny, tmp_path):
    """Under a profiler session the host thread holds a ``serve.block``
    span per block with its dispatch, wait and bookkeeping nested in it,
    and one ``serve.admit`` per admission; the served tokens are those
    of the per-token loop."""
    cfg, params = tiny
    reqs = _stream(cfg)
    eng = ServeEngine(params, cfg, SCFG)
    eng.serve(reqs[:1])                      # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        recs = eng.serve(reqs)
    want = naive_generate(params, cfg, reqs,
                          dataclasses.replace(SCFG, n_slots=1))
    assert all(recs[r.rid].tokens == want[r.rid].tokens for r in reqs)
    spans = _host_spans(tmp_path)
    log = eng.stats["last_serve"]
    blocks = [s for s in spans if s[0] == "serve.block"]
    assert len(blocks) == log["totals"]["blocks"]
    assert [s[3]["block"] for s in blocks] == list(range(len(blocks)))
    assert [s[3]["live_slots"] for s in blocks] == [
        r.live_slots for r in log["blocks"]]
    for name in ("dispatch", "wait", "bookkeep"):
        kids = [s for s in spans if s[0] == f"serve.block.{name}"]
        assert len(kids) == len(blocks), name
        for (_, s0, e0, _), (_, s1, e1, _) in zip(blocks, kids):
            assert s0 <= s1 <= e1 <= e0, name
    admits = [s for s in spans if s[0] == "serve.admit"]
    assert sorted(s[3]["rid"] for s in admits) == sorted(r.rid for r in reqs)
    assert all(s[3]["prompt_len"] == 8 for s in admits)
    assert len(admits) == log["totals"]["admits"]
