"""Continuous-batching decode engine: fused decode blocks over a donated
slot-stacked cache pool, with a resilience layer.

The legacy loop (``examples/serve_decode.py``) pays one jit dispatch plus
a blocking host readback per decoded token and head-of-line blocks the
whole batch on its slowest sequence.  This engine applies the round
engine's idioms to serving:

  - the S request slots live in ONE slot-stacked cache pool
    (``serve.pool``) with per-slot positions, ``active`` / ``stopped``
    masks, a per-slot token budget, and the last sampled token — all
    device-resident and DONATED to the compiled step, so pool buffers
    alias across blocks like round state aliases across rounds;
  - ``M = block_steps`` decode steps are fused into one jitted
    ``lax.scan`` (``_block_impl``): greedy/temperature sampling and
    stop-token accounting run ON DEVICE in the carry, tokens accumulate
    into an (M, S) device buffer, and the host pays exactly one dispatch
    and one readback per M tokens-per-slot — the serving analogue of
    ``RoundEngine.run_block``;
  - new requests are admitted MID-DECODE: prefill runs as its own
    compiled call (per prompt length), and the resulting single-request
    cache is scattered into a free slot (``scatter_slot``) without
    touching in-flight slots or recompiling anything;
  - stopped slots keep riding the batched step with a frozen position
    (``step_mask``): their cache writes land on a dead slot that the
    next admission overwrites, so no gather/compact is needed.

Resilience (PR 8) — every guard rides the compiled block; host logic
runs only at block boundaries, so the 1-dispatch-per-M-tokens structure
survives every failure mode:

  - ON-DEVICE OUTPUT GUARDS: per-slot fault flags carried in the scan
    (the serving analogue of the federation quarantine guard) trip on
    non-finite decode logits and on runaway token repetition; a tripped
    slot is frozen on device — the faulty token is never emitted — and
    the flag comes back in the block's single readback;
  - HOST WATCHDOG at block boundaries: slots past their completion
    deadline are cancelled via a ``cancel`` mask folded into the next
    block dispatch (``timed_out``), and slots making no progress for
    ``stall_blocks`` consecutive blocks are reclaimed as stuck;
  - RETRY WITH BACKOFF: faulted/stuck requests requeue through the
    scheduler's retry lane (re-prefilled from the prompt) up to
    ``max_attempts`` admissions, then land in the terminal ``failed``
    state;
  - ADMISSION CONTROL: the scheduler sheds queued requests past their
    TTFT deadline and beyond ``queue_cap`` at every boundary, bounding
    queue latency under overload (see ``serve.scheduler``);
  - SNAPSHOT/RESUME: ``snapshot()`` serialises the whole device state
    (cache pool, per-slot positions and budgets, RNG key, fault flags,
    global step counter) through ``repro.checkpoint`` with the
    scheduler in the JSON meta; ``ServeEngine.resume`` + a
    ``resume_serve()`` call continue a killed stream, bit-identical for
    already-admitted slots;
  - CHAOS: ``serve(fault_plan=...)`` injects a deterministic seeded
    fault schedule (``serve.faults``) — NaN-poisoned logits, silent
    slot freezes, host delays, and a simulated mid-stream crash.

``naive_generate`` keeps the legacy per-token loop alive as the oracle
and the benchmark baseline: one dispatch + one blocking argmax readback
per token, batches run head-of-line until every member finishes.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import time
from dataclasses import dataclass
from functools import partial, wraps
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_checkpoint, read_meta, save_checkpoint
from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.serve import faults as F
from repro.serve.pool import init_pool_cache, scatter_slot
from repro.serve.scheduler import FifoScheduler, Request, RequestRecord
from repro.tracing import Span

Array = jax.Array


@dataclass(frozen=True)
class ServeConfig:
    """Serving engine knobs.  ``max_new_tokens`` counts ALL generated
    tokens including the one sampled from the prefill logits.
    ``stop_token < 0`` disables early stopping.  ``temperature == 0`` is
    greedy.  ``attn_backend``: 'reference' (blockwise jnp), 'pallas'
    (``kernels.decode_attention``; interpret mode off-TPU), or 'auto'
    (pallas on TPU, reference elsewhere).

    SLO / resilience knobs (None / 0 disables each):

    - ``queue_cap``: max arrived-but-unadmitted requests held; newest
      beyond the cap are shed at block boundaries (bounded queue).
    - ``ttft_deadline_s`` / ``deadline_s``: default first-token and
      completion deadlines relative to arrival (per-request fields on
      ``Request`` override them).
    - ``max_attempts``: admissions per request before a faulted/stuck
      request becomes terminal ``failed``; ``retry_backoff_s`` delays
      each re-admission.
    - ``stall_blocks``: consecutive zero-progress blocks before the
      watchdog reclaims a slot as stuck (0 = watchdog off).
    - ``guard_nonfinite``: trip the on-device fault flag on non-finite
      decode logits instead of emitting a garbage token.
    - ``max_repeat``: trip the fault flag after this many CONSECUTIVE
      identical tokens from one slot (0 = off).
    """
    n_slots: int = 8
    cache_len: int = 128
    block_steps: int = 8
    max_new_tokens: int = 32
    stop_token: int = -1
    temperature: float = 0.0
    seed: int = 0
    attn_backend: str = "reference"
    queue_cap: Optional[int] = None
    ttft_deadline_s: Optional[float] = None
    deadline_s: Optional[float] = None
    max_attempts: int = 2
    retry_backoff_s: float = 0.0
    stall_blocks: int = 0
    guard_nonfinite: bool = True
    max_repeat: int = 0


def _resolve_backend(name: str):
    """-> (backend, interpret) for decode_step_slots."""
    if name not in ("auto", "reference", "pallas"):
        raise ValueError(f"unknown attn_backend {name!r}; expected auto | "
                         f"reference | pallas")
    on_tpu = jax.default_backend() == "tpu"
    if name == "auto":
        name = "pallas" if on_tpu else "reference"
    return name, name == "pallas" and not on_tpu


BlockRecord = collections.namedtuple("BlockRecord", (
    "block", "t_s", "period_ns", "admit_ns", "dispatch_ns", "wait_ns",
    "bookkeep_ns", "idle_ns", "cpu_ns", "gc_ns", "live_slots", "admits"))
MAX_BLOCK_RECORDS = 65536      # about nine hours of 0.5 s blocks


class BlockLog:
    """The per-block record of one ``serve()`` / ``resume_serve()`` call,
    which the engine keeps as ``stats["last_serve"]`` (``record``):

    - ``blocks``: a ``BlockRecord`` per decode block, the last
      ``MAX_BLOCK_RECORDS`` of them: ``block`` (its index in the call),
      ``t_s`` (its dispatch, seconds since the call started),
      ``period_ns`` (to the next dispatch, or to the end of the call for
      the last block), the wall ns within that period of the spans
      ``serve.admit``, ``serve.block.dispatch``, ``serve.block.wait``,
      ``serve.block.bookkeep`` and ``serve.idle``, ``cpu_ns`` (the
      serving thread's CPU time over the period outside ``wait`` and
      ``idle``, as fine as ``time.thread_time_ns`` steps), ``gc_ns`` (time in garbage collection), ``live_slots``
      and ``admits`` (admissions in the period);
    - ``lead``: the same for the part of the call before the first
      dispatch (``block`` -1);
    - ``totals``: ``blocks`` and each summed field over the whole call.

    The phases are disjoint, so they add up to at most the period; the
    rest is host time outside the spans (the scheduler's calls)."""

    SUMMED = BlockRecord._fields[2:]

    def __init__(self):
        self.record = {"blocks": collections.deque(maxlen=MAX_BLOCK_RECORDS),
                       "lead": None,
                       "totals": dict.fromkeys(("blocks",) + self.SUMMED, 0)}
        self._t0 = self._wall0 = time.perf_counter_ns()
        self._cpu0 = time.thread_time_ns()
        self._block, self._t_s, self._gc0 = -1, 0.0, 0
        self._open = dict.fromkeys(self.SUMMED, 0)
        gc.callbacks.append(self._gc)

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc0 = time.perf_counter_ns()
        else:
            self._open["gc_ns"] += time.perf_counter_ns() - self._gc0

    def add(self, field: str, span: Span, on_cpu: bool = True) -> None:
        """Count ``span``'s wall time under ``field``; a span that is not
        the host's own work (``on_cpu=False``) leaves ``cpu_ns`` too."""
        self._open[field] += span.wall_ns
        if not on_cpu:
            self._open["cpu_ns"] -= span.cpu_ns

    def admitted(self, span: Span, waited: Optional[Span]) -> None:
        self._open["admits"] += 1
        self._open["admit_ns"] += span.wall_ns
        if waited is not None:              # the first-token wait
            self._open["admit_ns"] -= waited.wall_ns
            self.add("wait_ns", waited, on_cpu=False)

    def _close(self):
        # the period's CPU reads lie inside its wall reads, so its CPU
        # time exceeds its wall time by no more than the CPU clock's step
        cpu = time.thread_time_ns()
        wall = time.perf_counter_ns()
        rec = BlockRecord(self._block, self._t_s, **dict(
            self._open, period_ns=wall - self._wall0,
            cpu_ns=self._open["cpu_ns"] + cpu - self._cpu0))
        if rec.block < 0:
            self.record["lead"] = rec
        else:
            self.record["blocks"].append(rec)
        totals = self.record["totals"]
        totals["blocks"] = self._block + 1
        for k in self.SUMMED:
            totals[k] += getattr(rec, k)
        return wall, time.thread_time_ns()

    def dispatch(self, live_slots: int) -> int:
        """A decode block is dispatched: close the open record and open
        the block's.  -> the block's index in the call."""
        self._wall0, self._cpu0 = self._close()
        self._block += 1
        self._t_s = (self._wall0 - self._t0) * 1e-9
        self._open = dict.fromkeys(self.SUMMED, 0)
        self._open["live_slots"] = live_slots
        return self._block

    def close(self) -> None:
        self._close()
        gc.callbacks.remove(self._gc)


def _named(fn, name: str):
    """``fn`` under ``name``, which ``jax.jit`` gives its program
    (``jit_<name>``) and a profiler trace shows."""
    @wraps(fn)
    def named(*args):
        return fn(*args)
    named.__name__ = named.__qualname__ = name
    return named


class ServeEngine:
    """Continuous-batching engine for one model family.

    Usage::

        eng = ServeEngine(params, cfg, ServeConfig(n_slots=8))
        records = eng.serve(requests)        # scheduler.Request list
        records[rid].tokens                  # generated ids, stop incl.
        records[rid].state                   # terminal state (see
                                             # scheduler.TERMINAL_STATES)

    ``eng.stats`` counts compiled-call dispatches and blocking host
    readbacks by kind over the engine's life; the benchmark derives
    dispatches-per-token and host-syncs-per-token from it instead of
    asserting constants.  ``stats["last_serve"]`` holds the per-block
    record of the latest ``serve()`` / ``resume_serve()`` call
    (``BlockLog``), timed by the ``serve.*`` host spans that a profiler
    session also records.  The decode block and the admission run as
    the programs ``jit_serve_decode_block`` and ``jit_serve_admit``.
    """

    def __init__(self, params, cfg: ModelConfig, scfg: ServeConfig,
                 rt: Optional[T.Runtime] = None):
        if scfg.n_slots <= 0:
            raise ValueError(f"n_slots must be positive, got "
                             f"{scfg.n_slots}")
        if cfg.sliding_window:
            eff = min(scfg.cache_len, cfg.sliding_window)
            if eff < cfg.sliding_window:
                raise ValueError(
                    f"cache_len {scfg.cache_len} smaller than the sliding "
                    f"window {cfg.sliding_window}: the pool ring would not "
                    f"match prefill's ring packing")
        self.params = params
        self.cfg = cfg
        self.scfg = scfg
        self.rt = rt or T.Runtime()
        # public, so a chip check can see a silent fallback to the
        # reference or to interpret mode
        self.attn_backend, self.attn_interpret = _resolve_backend(
            scfg.attn_backend)
        self.state = self._init_state()
        self._block_fns: Dict[Optional[F.FaultPlan], callable] = {}
        self._admit = jax.jit(_named(self._admit_impl, "serve_admit"),
                              donate_argnums=(1,))
        self._resume_sched: Optional[FifoScheduler] = None
        self._blocks_done = 0
        self.stats = {"block_dispatches": 0, "block_syncs": 0,
                      "block_tokens": 0, "admit_dispatches": 0,
                      "request_reads": 0, "faults_detected": 0,
                      "stalls_detected": 0, "snapshot_writes": 0,
                      "last_serve": None}

    # ------------------------------------------------------------------
    def _init_state(self) -> dict:
        s = self.scfg.n_slots
        return {
            "cache": init_pool_cache(self.cfg, s, self.scfg.cache_len,
                                     self.rt),
            "active": jnp.zeros((s,), bool),
            "stopped": jnp.ones((s,), bool),
            "last_tok": jnp.zeros((s, 1), jnp.int32),
            "n_emitted": jnp.zeros((s,), jnp.int32),
            "max_new": jnp.full((s,), self.scfg.max_new_tokens, jnp.int32),
            "key": jax.random.PRNGKey(self.scfg.seed),
            # resilience carry: per-slot fault flags (the serving
            # quarantine guard), consecutive-repeat run lengths, and the
            # GLOBAL decode-step counter the chaos schedule indexes
            "fault": jnp.zeros((s,), bool),
            "rep_run": jnp.zeros((s,), jnp.int32),
            "t": jnp.zeros((), jnp.int32),
        }

    def _sample(self, logits: Array, key: Array) -> Array:
        """(S, V) float logits -> (S,) int32 next tokens, on device."""
        if self.scfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits.astype(jnp.float32) / self.scfg.temperature,
            axis=-1).astype(jnp.int32)

    # ------------------------------------------------------------------
    def _admit_impl(self, params, state: dict, batch: dict, key: Array,
                    max_new: Array, slot: Array):
        """Prefill + first-token sampling + slot scatter, fused into ONE
        compiled call per admission (compiled once per prompt length).
        The first token lands in ``last_tok[slot]``; the host reads it
        lazily — admission costs zero blocking syncs.  The slot's fault
        flag and repeat counter reset here; non-finite PREFILL logits
        set the flag immediately so the first block boundary retries
        instead of streaming garbage."""
        logits, req_cache = T.prefill(params, batch, self.cfg, self.rt,
                                      cache_len=self.scfg.cache_len)
        last = logits[:, -1, :]
        first = self._sample(last, key)[0]
        stop = self.scfg.stop_token
        bad0 = (~jnp.isfinite(last.astype(jnp.float32)).all()
                if self.scfg.guard_nonfinite else jnp.asarray(False))
        first_stopped = bad0 | (max_new <= 1) | (first == stop if stop >= 0
                                                 else False)
        cache = scatter_slot(state["cache"], req_cache, slot)
        return dict(
            state,
            cache=cache,
            active=state["active"].at[slot].set(True),
            stopped=state["stopped"].at[slot].set(first_stopped),
            last_tok=state["last_tok"].at[slot, 0].set(first),
            n_emitted=state["n_emitted"].at[slot].set(1),
            max_new=state["max_new"].at[slot].set(max_new),
            fault=state["fault"].at[slot].set(bad0),
            rep_run=state["rep_run"].at[slot].set(0),
        )

    def _block_impl(self, plan: Optional[F.FaultPlan], params, state: dict,
                    cancel: Array):
        """M fused decode steps: sampling, stop accounting, and the
        output guards all in the scan carry; one (M, S) token buffer
        comes back per dispatch.  ``cancel`` (S,) bool freezes
        deadline-expired slots on device without an extra dispatch.
        ``plan`` is a STATIC chaos schedule (None = clean)."""
        stop = self.scfg.stop_token
        max_rep = self.scfg.max_repeat
        n_slots = self.scfg.n_slots
        state = dict(state, stopped=state["stopped"] | cancel)

        def step(st, _):
            running = st["active"] & ~st["stopped"]
            frozen = F.freeze_mask(plan, st["t"], n_slots)
            if frozen is not None:
                running = running & ~frozen
            logits, cache = T.decode_step_slots(
                params, st["cache"], {"tokens": st["last_tok"]}, self.cfg,
                self.rt, step_mask=running, attn_backend=self.attn_backend,
                attn_interpret=self.attn_interpret)
            lg = F.poison_logits(plan, st["t"], logits[:, 0, :])
            key, sub = jax.random.split(st["key"])
            tok = self._sample(lg, sub)
            # output guards: a tripped slot freezes and its token is
            # never emitted — the host retries from the prompt instead
            if self.scfg.guard_nonfinite:
                bad = running & ~jnp.isfinite(
                    lg.astype(jnp.float32)).all(axis=-1)
            else:
                bad = jnp.zeros_like(running)
            ok = running & ~bad
            same = tok == st["last_tok"][:, 0]
            rep_run = jnp.where(ok, jnp.where(same, st["rep_run"] + 1, 0),
                                st["rep_run"])
            if max_rep > 0:
                bad = bad | (ok & (rep_run >= max_rep))
            good = running & ~bad
            tok = jnp.where(good, tok, st["last_tok"][:, 0])
            n_emitted = st["n_emitted"] + good.astype(jnp.int32)
            hit_stop = (tok == stop) if stop >= 0 else jnp.zeros_like(good)
            exhausted = n_emitted >= st["max_new"]
            stopped = st["stopped"] | (good & (hit_stop | exhausted)) | bad
            st = dict(st, cache=cache, last_tok=tok[:, None],
                      n_emitted=n_emitted, stopped=stopped, key=key,
                      fault=st["fault"] | bad, rep_run=rep_run,
                      t=st["t"] + 1)
            return st, (tok, good)

        state, (toks, emitted) = jax.lax.scan(
            step, state, None, length=self.scfg.block_steps)
        return state, toks, emitted

    def _get_block(self, plan: Optional[F.FaultPlan]):
        """One compilation per distinct device-visible fault schedule;
        host-only plans (delays/crash) share the clean compilation."""
        key = None if plan is None or plan.device_silent else plan
        if key not in self._block_fns:
            self._block_fns[key] = jax.jit(
                _named(partial(self._block_impl, key), "serve_decode_block"),
                donate_argnums=(1,))
        return self._block_fns[key]

    # ------------------------------------------------------------------
    def _admit_request(self, req: Request, rec: RequestRecord,
                       sync_ttft: bool, now) -> Optional[Span]:
        """Dispatch one admission; -> the first-token wait's span with
        ``sync_ttft``, else None."""
        scfg = self.scfg
        max_new = req.max_new if req.max_new is not None \
            else scfg.max_new_tokens
        if not self.cfg.sliding_window and self.cfg.family != "ssm":
            need = len(req.tokens) + max_new + 1
            if need > scfg.cache_len:
                raise ValueError(f"request {req.rid}: prompt+max_new "
                                 f"{need} exceeds cache_len {scfg.cache_len}")
        batch = {"tokens": jnp.asarray(req.tokens, jnp.int32)[None]}
        for name, arr in req.extras:
            batch[name] = jnp.asarray(arr)[None]
        key = jax.random.fold_in(jax.random.PRNGKey(scfg.seed + 1), req.rid)
        self.state = self._admit(self.params, self.state, batch, key,
                                 jnp.asarray(max_new, jnp.int32),
                                 jnp.asarray(rec.slot, jnp.int32))
        self.stats["admit_dispatches"] += 1
        first = self.state["last_tok"][rec.slot, 0]
        rec.tokens.append(first)           # device scalar; resolved lazily
        if not sync_ttft:
            return None
        with Span("serve.block.wait", rid=req.rid) as waited:
            first.block_until_ready()
        self.stats["request_reads"] += 1
        rec.first_token_s = now()
        return waited

    def serve(self, requests: List[Request], *, sync_ttft: bool = False,
              fault_plan: Optional[F.FaultPlan] = None,
              snapshot_path: Optional[str] = None,
              snapshot_every_blocks: int = 0) -> Dict[int, RequestRecord]:
        """Run a request stream to completion with continuous batching.

        Admission happens between decode blocks: arrived requests fill
        free slots (prefill + scatter), then one fused M-step block runs
        and its (M, S) token buffer is read back — the only blocking
        host sync in the decode path.  With ``sync_ttft`` the engine
        additionally blocks on each request's first token to timestamp
        TTFT (a per-REQUEST sync, used by the latency benchmark).

        ``fault_plan`` injects the chaos schedule (``serve.faults``);
        ``snapshot_path`` + ``snapshot_every_blocks=N`` write a
        restore-compatible serve snapshot every N blocks, so a crash —
        real or simulated — loses at most N blocks of decode work.
        """
        scfg = self.scfg
        sched = FifoScheduler(requests, scfg.n_slots,
                              queue_cap=scfg.queue_cap,
                              ttft_deadline_s=scfg.ttft_deadline_s,
                              deadline_s=scfg.deadline_s)
        self._blocks_done = 0        # block indices are per-stream; only
        # resume_serve continues a restored counter (chaos schedules and
        # snapshot steps index it)
        return self._run(sched, sync_ttft=sync_ttft, fault_plan=fault_plan,
                         snapshot_path=snapshot_path,
                         snapshot_every_blocks=snapshot_every_blocks)

    def resume_serve(self, *, sync_ttft: bool = False,
                     fault_plan: Optional[F.FaultPlan] = None,
                     snapshot_path: Optional[str] = None,
                     snapshot_every_blocks: int = 0
                     ) -> Dict[int, RequestRecord]:
        """Continue the stream restored by :meth:`resume`: unfinished
        requests run to a terminal state (already-admitted slots resume
        bit-identically from the snapshot's device state).  Wall-clock
        SLO timestamps restart from the resume instant — crash recovery
        prioritises completing work over latency bookkeeping."""
        if self._resume_sched is None:
            raise RuntimeError("no restored stream: construct the engine "
                               "with ServeEngine.resume(path, ...) first")
        sched, self._resume_sched = self._resume_sched, None
        return self._run(sched, sync_ttft=sync_ttft, fault_plan=fault_plan,
                         snapshot_path=snapshot_path,
                         snapshot_every_blocks=snapshot_every_blocks)

    def _run(self, sched: FifoScheduler, *, sync_ttft: bool,
             fault_plan: Optional[F.FaultPlan],
             snapshot_path: Optional[str],
             snapshot_every_blocks: int) -> Dict[int, RequestRecord]:
        scfg = self.scfg
        block = self._get_block(fault_plan)
        self._sched = sched
        stall = [0] * scfg.n_slots
        log = BlockLog()
        self.stats["last_serve"] = log.record
        t0 = time.perf_counter()

        def now():
            return time.perf_counter() - t0

        try:
            while not sched.done:
                sched.shed_expired(now())
                while sched.admissible(now()):
                    req, slot = sched.pop(now())
                    stall[slot] = 0
                    with Span("serve.admit", rid=req.rid, slot=slot,
                              prompt_len=len(req.tokens)) as sp:
                        waited = self._admit_request(
                            req, sched.records[req.rid], sync_ttft, now)
                    log.admitted(sp, waited)
                    # a request that stops at its first token never decodes
                    if (req.max_new or scfg.max_new_tokens) <= 1:
                        rec = sched.records[req.rid]
                        if rec.first_token_s is None:
                            rec.first_token_s = now()
                        sched.release(slot, now())
                busy = [s for s, rid in enumerate(sched.slot_rid)
                        if rid is not None]
                if not busy:
                    nr = sched.next_ready()
                    if nr is None:
                        break
                    wait = nr - now()
                    if wait > 0:
                        with Span("serve.idle") as sp:
                            time.sleep(wait)
                        log.add("idle_ns", sp, on_cpu=False)
                    continue
                if (fault_plan is not None and fault_plan.delay_s > 0
                        and self._blocks_done in fault_plan.delay_blocks):
                    time.sleep(fault_plan.delay_s)
                # watchdog, part 1: deadline-expired slots are cancelled ON
                # DEVICE by the block dispatch itself (no extra dispatch)
                cancel = np.zeros((scfg.n_slots,), bool)
                t_check = now()
                for s in busy:
                    if t_check > sched.abs_deadline(sched.slot_rid[s]):
                        cancel[s] = True
                n = log.dispatch(len(busy))
                with Span("serve.block", block=n, live_slots=len(busy)):
                    with Span("serve.block.dispatch") as sp:
                        self.state, toks, emitted = block(
                            self.params, self.state, jnp.asarray(cancel))
                    log.add("dispatch_ns", sp)
                    self.stats["block_dispatches"] += 1
                    # ONE readback per block: tokens, emission mask, stop
                    # and fault flags
                    with Span("serve.block.wait") as sp:
                        readback = jax.device_get(
                            (toks, emitted, self.state["stopped"],
                             self.state["fault"]))
                    log.add("wait_ns", sp, on_cpu=False)
                    self.stats["block_syncs"] += 1
                    with Span("serve.block.bookkeep") as sp:
                        self._bookkeep(sched, busy, cancel, readback, stall,
                                       now())
                        self._blocks_done += 1
                        if (snapshot_path and snapshot_every_blocks > 0
                                and self._blocks_done
                                % snapshot_every_blocks == 0):
                            self.snapshot(snapshot_path, sched)
                    log.add("bookkeep_ns", sp)
                if (fault_plan is not None
                        and fault_plan.crash_after_block >= 0
                        and self._blocks_done - 1
                        == fault_plan.crash_after_block):
                    raise F.SimulatedCrash(
                        f"fault plan killed the engine after block "
                        f"{fault_plan.crash_after_block}"
                        + (f"; resume from {snapshot_path!r}"
                           if snapshot_path else ""))
        finally:
            log.close()
        for rec in sched.records.values():      # resolve lazy first tokens
            rec.tokens = [int(t) for t in rec.tokens]
        return sched.records

    def _bookkeep(self, sched: FifoScheduler, busy: List[int],
                  cancel: np.ndarray, readback, stall: List[int],
                  t_block: float) -> None:
        """A block's host work per live slot: append its tokens, release
        finished and cancelled slots, retry faulted ones, and run the
        stall watchdog."""
        toks_h, emitted_h, stopped_h, fault_h = readback
        for s in busy:
            rec = sched.records[sched.slot_rid[s]]
            if cancel[s]:
                sched.release(s, t_block, state="timed_out")
                continue
            new = toks_h[emitted_h[:, s], s]
            rec.tokens.extend(int(t) for t in new)
            self.stats["block_tokens"] += int(emitted_h[:, s].sum())
            if rec.first_token_s is None and len(rec.tokens) > 0:
                rec.first_token_s = t_block
            if fault_h[s]:
                rec.faults += 1
                self.stats["faults_detected"] += 1
                self._retry_or_fail(sched, s, t_block)
            elif stopped_h[s]:
                sched.release(s, t_block)
            elif self.scfg.stall_blocks > 0 and not emitted_h[:, s].any():
                # watchdog, part 2: a live slot that emitted nothing
                stall[s] += 1
                if stall[s] >= self.scfg.stall_blocks:
                    stall[s] = 0
                    self.stats["stalls_detected"] += 1
                    self._retry_or_fail(sched, s, t_block)
            else:
                stall[s] = 0

    def _retry_or_fail(self, sched: FifoScheduler, slot: int,
                       now_s: float) -> None:
        """Reclaim a faulted/stuck slot: requeue with backoff while the
        attempt budget lasts, else terminal ``failed``."""
        rid = sched.slot_rid[slot]
        if sched.records[rid].attempts < self.scfg.max_attempts:
            sched.requeue(slot, now_s + self.scfg.retry_backoff_s)
        else:
            sched.release(slot, now_s, state="failed")

    # ----------------------------------------------------- persistence
    def snapshot(self, path: str,
                 sched: Optional[FifoScheduler] = None) -> None:
        """Serialise the full serve state through ``repro.checkpoint``:
        the device pool (cache, per-slot positions, budgets, RNG key,
        fault flags, global step counter) as the checkpoint tree and the
        scheduler + ``ServeConfig`` in the JSON meta.  Atomic like every
        checkpoint write; a crash mid-save never corrupts the previous
        snapshot."""
        sched = sched if sched is not None else self._sched
        for rec in sched.records.values():      # resolve lazy device scalars
            rec.tokens = [int(t) for t in rec.tokens]
        meta = {
            "kind": "serve_snapshot",
            "serve_config": dataclasses.asdict(self.scfg),
            "model_family": self.cfg.family,
            "scheduler": sched.to_meta(),
            "blocks_done": self._blocks_done,
        }
        save_checkpoint(path, jax.device_get(self.state),
                        step=self._blocks_done, meta=meta)
        self.stats["snapshot_writes"] += 1

    @classmethod
    def resume(cls, path: str, params, cfg: ModelConfig,
               rt: Optional[T.Runtime] = None) -> "ServeEngine":
        """Rebuild an engine from a serve snapshot (``CheckpointError``
        on a truncated/corrupt file, ``ValueError`` on a snapshot from a
        different serve/model configuration).  Follow with
        :meth:`resume_serve` to run the restored stream to completion."""
        meta = read_meta(path)
        if meta.get("kind") != "serve_snapshot":
            raise ValueError(f"{path!r} is not a serve snapshot "
                             f"(kind={meta.get('kind')!r})")
        if meta["model_family"] != cfg.family:
            raise ValueError(
                f"snapshot {path!r} was taken from a {meta['model_family']!r}"
                f" model, cannot restore into {cfg.family!r}")
        scfg = ServeConfig(**meta["serve_config"])
        eng = cls(params, cfg, scfg, rt)
        state, step = load_checkpoint(path, eng.state)
        eng.state = state
        eng._blocks_done = int(step)
        eng._resume_sched = FifoScheduler.from_meta(meta["scheduler"])
        return eng


# ======================================================================
# Module-level jits (cfg / rt / cache_len static) so repeated
# naive_generate calls — warm-up then timed — share compilations.
@partial(jax.jit, static_argnums=(2, 3, 4))
def _naive_prefill(params, batch, cfg, rt, cache_len):
    return T.prefill(params, batch, cfg, rt, cache_len=cache_len)


@partial(jax.jit, static_argnums=(3, 4))
def _naive_decode(params, cache, tok, cfg, rt):
    return T.decode_step(params, cache, {"tokens": tok}, cfg, rt)


def naive_generate(params, cfg: ModelConfig, requests: List[Request],
                   scfg: ServeConfig, rt: Optional[T.Runtime] = None,
                   stats: Optional[dict] = None) -> Dict[int, RequestRecord]:
    """The legacy per-token loop, kept as oracle + benchmark baseline.

    Requests run in arrival order in fixed batches of ``n_slots`` (all
    prompts in a batch must share one length — the loop cannot pack);
    every decoded token pays one jit dispatch plus one blocking host
    readback (argmax + stop check on the host), and a batch runs until
    EVERY member finishes (head-of-line blocking), exactly the structure
    the continuous-batching engine removes.  Greedy only.
    """
    rt = rt or T.Runtime()
    stats = stats if stats is not None else {}
    stats.setdefault("decode_dispatches", 0)
    stats.setdefault("host_syncs", 0)
    stats.setdefault("decode_tokens", 0)
    stats.setdefault("prefill_dispatches", 0)

    def prefill_j(p, b):
        return _naive_prefill(p, b, cfg, rt, scfg.cache_len)

    def decode_j(p, c, t):
        return _naive_decode(p, c, t, cfg, rt)

    records = {r.rid: RequestRecord(request=r) for r in requests}
    order = sorted(requests, key=lambda r: r.arrival_s)
    t0 = time.perf_counter()
    for i in range(0, len(order), scfg.n_slots):
        group = order[i:i + scfg.n_slots]
        plens = {len(r.tokens) for r in group}
        assert len(plens) == 1, "naive baseline needs equal prompt lengths"
        batch = {"tokens": jnp.asarray([r.tokens for r in group],
                                       jnp.int32)}
        logits, cache = prefill_j(params, batch)
        stats["prefill_dispatches"] += 1
        tok = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1),
                         np.int32)                       # host sync
        stats["host_syncs"] += 1
        t_first = time.perf_counter() - t0
        budgets = [r.max_new if r.max_new is not None
                   else scfg.max_new_tokens for r in group]
        outs = [[int(t)] for t in tok]
        done = [budgets[j] <= 1 or
                (scfg.stop_token >= 0 and int(tok[j]) == scfg.stop_token)
                for j in range(len(group))]
        for j, r in enumerate(group):
            records[r.rid].first_token_s = t_first
            records[r.rid].slot = j
        # head-of-line: the whole batch keeps stepping until ALL are done
        dev_tok = jnp.asarray(tok)[:, None]
        while not all(done):
            logits, cache = decode_j(params, cache, dev_tok)
            stats["decode_dispatches"] += 1
            tok = np.asarray(jnp.argmax(logits[:, 0, :], axis=-1),
                             np.int32)                   # per-token sync
            stats["host_syncs"] += 1
            for j in range(len(group)):
                if done[j]:
                    continue
                outs[j].append(int(tok[j]))
                stats["decode_tokens"] += 1
                if ((scfg.stop_token >= 0 and int(tok[j]) == scfg.stop_token)
                        or len(outs[j]) >= budgets[j]):
                    done[j] = True
            dev_tok = jnp.asarray(tok)[:, None]
        t_done = time.perf_counter() - t0
        for j, r in enumerate(group):
            records[r.rid].tokens = outs[j]
            records[r.rid].finished_s = t_done
            records[r.rid].state = "completed"
    return records
