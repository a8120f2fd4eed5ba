"""Serving example: continuous batching by default, the legacy batched
loop behind ``--legacy``.

Default path drives ``repro.serve.ServeEngine``: a slot-stacked cache
pool (ring-buffer SWA, MLA latents, SSM states — whatever the family
needs), requests admitted mid-decode into free slots, and decode fused
into M-step blocks (one jit dispatch + one host readback per M tokens
per slot, sampling and stop accounting on device).

    PYTHONPATH=src python examples/serve_decode.py --arch falcon-mamba-7b
    PYTHONPATH=src python examples/serve_decode.py --requests 12 --rate 8
    PYTHONPATH=src python examples/serve_decode.py --legacy

``--legacy`` runs the pre-engine loop on one fixed batch; its argmax is
folded into the jitted decode step (the host never touches per-token
logits) and the loop stays fully async until the final readback.

Failure modes and SLOs
----------------------
Every request ends in EXACTLY ONE terminal state, and each state maps
to one resilience mechanism:

* ``shed`` — admission control dropped it before it held a slot.
  ``--ttft-deadline`` sheds queued requests that can no longer get a
  first token in time; ``--queue-cap`` bounds how many arrived requests
  may wait (newest are rejected first).  Under overload, goodput
  degrades gracefully instead of every request going late together.
* ``timed_out`` — its completion deadline (``--deadline``, seconds
  after arrival) expired mid-decode.  The watchdog folds a cancel mask
  into the NEXT block dispatch (no extra dispatch: still one compiled
  call per M tokens) and reclaims the slot at the boundary.
* ``failed`` — a device fault exhausted its retry budget.  The fused
  block carries per-slot fault flags: non-finite logits and runaway
  repetition (``--max-repeat``) trip ON DEVICE and surface in the
  block's single readback; a frozen slot that stops emitting trips the
  host stall watchdog after ``--stall-blocks`` zero-progress blocks.
  Faulted requests requeue through a retry lane (``--max-attempts``,
  ``--retry-backoff``) and re-prefill from the prompt — a token derived
  from poisoned logits is never emitted.
* ``completed`` — and, greedy decoding being deterministic, its tokens
  are bit-identical to a fault-free run's.

``--chaos SEED`` turns on the deterministic fault harness
(:func:`repro.serve.seeded_plan`): NaN-poisoned decode steps, frozen
slots, and host-side block delays on a seeded schedule, so every
mechanism above can be watched firing.  ``--snapshot PATH
--snapshot-every N`` persists engine + scheduler state through the
checkpoint module every N blocks; after a crash, ``--resume PATH``
restores and finishes the unfinished requests (admitted slots resume
bit-identically — the RNG key rides the snapshot).

    python examples/serve_decode.py --requests 16 --rate 200 \
        --ttft-deadline 0.05 --queue-cap 8 --deadline 2.0
    python examples/serve_decode.py --chaos 7 --max-attempts 3 \
        --stall-blocks 2 --snapshot /tmp/serve.npz --snapshot-every 4
    python examples/serve_decode.py --resume /tmp/serve.npz
"""
import argparse
import statistics
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduced
from repro.models import transformer as T
from repro.serve import (ServeConfig, ServeEngine, poisson_requests,
                         seeded_plan, state_counts)


def run_legacy(cfg, params, key, args):
    """One fixed batch, one token per jitted step — no admission, no
    early stop, head-of-line by construction."""
    b, s = args.batch, args.prompt_len
    # independent streams per input: never reuse one key across draws
    k_tok, k_img, k_aud = (jax.random.fold_in(key, i) for i in range(3))
    batch = {"tokens": jax.random.randint(k_tok, (b, s), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            k_img, (b, cfg.n_image_tokens, cfg.image_embed_dim))
    if cfg.family == "audio":
        batch["enc_embeds"] = jax.random.normal(
            k_aud, (b, cfg.encoder_seq_len, cfg.encoder_embed_dim))

    t0 = time.time()
    logits, cache = jax.jit(
        lambda p, bt: T.prefill(p, bt, cfg,
                                cache_len=s + cfg.n_image_tokens
                                + args.new_tokens + 8))(params, batch)
    print(f"prefill {b}x{s} [{cfg.family}] in {time.time()-t0:.1f}s "
          f"(cache leaves: {len(jax.tree.leaves(cache))})")

    # argmax INSIDE the jitted step: the host schedules M async steps and
    # reads tokens once at the end, instead of a logits readback per token
    @jax.jit
    def decode(p, c, t):
        lg, c = T.decode_step(p, c, {"tokens": t}, cfg)
        return jnp.argmax(lg, axis=-1).astype(jnp.int32), c, lg

    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for _ in range(args.new_tokens):
        tok, cache, logits = decode(params, cache, tok)
        out.append(tok)
    toks = jax.device_get(jnp.concatenate(out, axis=1))   # the one sync
    dt = (time.time() - t0) / args.new_tokens
    print(f"decoded {args.new_tokens} tokens/seq @ {dt*1e3:.1f} ms/step "
          f"(greedy): {toks[0, :12].tolist()}...")
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    print("ok: finite logits, cache len =", int(cache["len"]))


def run_engine(cfg, params, args):
    scfg = ServeConfig(n_slots=args.slots, cache_len=args.cache_len,
                       block_steps=args.block_steps,
                       max_new_tokens=args.new_tokens,
                       queue_cap=args.queue_cap,
                       ttft_deadline_s=args.ttft_deadline,
                       deadline_s=args.deadline,
                       max_attempts=args.max_attempts,
                       retry_backoff_s=args.retry_backoff,
                       stall_blocks=args.stall_blocks,
                       max_repeat=args.max_repeat)
    if args.resume:
        eng = ServeEngine.resume(args.resume, params, cfg)
        t0 = time.time()
        recs = eng.resume_serve()
        _report(cfg, eng, recs, time.time() - t0, args)
        return
    reqs = poisson_requests(args.requests, args.rate,
                            prompt_len=args.prompt_len,
                            vocab_size=cfg.vocab_size, seed=0)
    if cfg.family in ("vlm", "audio"):    # per-request modality inputs
        import dataclasses
        name, shape = (("image_embeds",
                        (cfg.n_image_tokens, cfg.image_embed_dim))
                       if cfg.family == "vlm" else
                       ("enc_embeds",
                        (cfg.encoder_seq_len, cfg.encoder_embed_dim)))
        reqs = [dataclasses.replace(r, extras=(
            (name, jax.random.normal(jax.random.fold_in(
                jax.random.PRNGKey(7), r.rid), shape)),))
                for r in reqs]
    plan = None
    if args.chaos >= 0:
        plan = seeded_plan(args.chaos, n_steps=args.requests
                           * args.new_tokens, n_slots=args.slots,
                           nan_rate=0.05, freeze_rate=0.02,
                           delay_rate=0.05, delay_s=0.002)
    eng = ServeEngine(params, cfg, scfg)
    t0 = time.time()
    recs = eng.serve(reqs, sync_ttft=args.rate > 0, fault_plan=plan,
                     snapshot_path=args.snapshot,
                     snapshot_every_blocks=args.snapshot_every)
    _report(cfg, eng, recs, time.time() - t0, args)


def _report(cfg, eng, recs, wall, args):
    toks = sum(len(r.tokens) for r in recs.values())
    print(f"[{cfg.family}] served {len(recs)} requests / {toks} tokens in "
          f"{wall:.1f}s ({toks/wall:.0f} tok/s) over {args.slots} slots")
    print(f"  dispatch structure: {eng.stats['block_dispatches']} block "
          f"dispatches, {eng.stats['block_syncs']} readbacks for "
          f"{eng.stats['block_tokens']} decoded tokens "
          f"(M={args.block_steps})")
    counts = state_counts(recs)
    print(f"  terminal states: {counts}; device faults "
          f"{eng.stats['faults_detected']}, stalls "
          f"{eng.stats['stalls_detected']}, retries "
          f"{sum(r.retries for r in recs.values())}, snapshots "
          f"{eng.stats['snapshot_writes']}")
    ttfts = [r.ttft_s for r in recs.values() if r.ttft_s is not None]
    if args.rate > 0 and ttfts:
        print(f"  ttft p50 {1e3*statistics.median(ttfts):.0f} ms over "
              f"Poisson arrivals at {args.rate:g} req/s")
    rid = min(recs)
    print(f"  request {rid}: {recs[rid].tokens[:12]}...")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-32b")
    ap.add_argument("--legacy", action="store_true",
                    help="pre-engine fixed-batch loop")
    ap.add_argument("--batch", type=int, default=4,
                    help="legacy loop batch size")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate, req/s (0 = all at t=0)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--block-steps", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=192)
    slo = ap.add_argument_group("SLOs / resilience (see module docstring)")
    slo.add_argument("--ttft-deadline", type=float, default=None,
                     help="shed queued requests past this first-token "
                          "deadline (s after arrival)")
    slo.add_argument("--deadline", type=float, default=None,
                     help="cancel decoding requests past this completion "
                          "deadline (s after arrival)")
    slo.add_argument("--queue-cap", type=int, default=None,
                     help="bound on arrived requests allowed to wait")
    slo.add_argument("--max-attempts", type=int, default=2,
                     help="admissions per request before terminal failure")
    slo.add_argument("--retry-backoff", type=float, default=0.0,
                     help="seconds a faulted request waits before retry")
    slo.add_argument("--stall-blocks", type=int, default=0,
                     help="zero-progress blocks before the stall watchdog "
                          "reclaims a slot (0 = off)")
    slo.add_argument("--max-repeat", type=int, default=0,
                     help="on-device runaway-repetition guard threshold "
                          "(0 = off)")
    slo.add_argument("--chaos", type=int, default=-1, metavar="SEED",
                     help="enable the seeded fault-injection harness")
    slo.add_argument("--snapshot", default=None, metavar="PATH",
                     help="write crash-recoverable serve snapshots here")
    slo.add_argument("--snapshot-every", type=int, default=4,
                     help="blocks between snapshots")
    slo.add_argument("--resume", default=None, metavar="PATH",
                     help="restore a serve snapshot and finish its "
                          "unfinished requests")
    args = ap.parse_args()

    cfg = reduced(get_config(args.arch))
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    if args.legacy:
        run_legacy(cfg, params, jax.random.fold_in(key, 1), args)
    else:
        run_engine(cfg, params, args)


if __name__ == "__main__":
    enable_compile_cache()
    main()
