"""Bring-up check: the federation round and the serving engine on one TPU.

Run from the root of a checkout, on a machine with a TPU:

    python3 chip_smoke.py             # device, federation, serve (1 chip)
    python3 chip_smoke.py --chips 4   # sharded federation vs one device

Phases (one process; it holds the chip for its whole life):

- device: the first JAX device must be a TPU.  There is no CPU fallback.
- federation: fedmm-small at full width (12 layers, d=768, bf16), 4 nodes
  of 4 modalities, GeoDoRA with precision aggregation, 4 rounds as two
  fused 2-round blocks with the metric tap.  Checks: finite losses,
  weights and CKA; CKA in [0, 1]; a tap for every round; the Gram runs as
  a compiled Pallas kernel (``tpu_custom_call``, not interpret mode); and
  round 1 agrees with a block run from the same start on the reference
  Gram.
- serve: ``ServeEngine`` at smollm-135m's published config (random
  weights from ``--seed``), 8 slots, cache_len 1024, 16 requests of mixed
  prompt lengths, once with the Pallas decode attention and once with the
  reference.  Checks: every request ``completed`` at its first attempt
  with no fault; the Pallas block holds ``tpu_custom_call``; the two
  backends give the same greedy tokens or one decode step's logits agree.
- ``--chips 4`` runs only the sharded federation (fedmm-small width, 2
  layers, f32) on a 4-device ("data",) mesh with 8 nodes, against the
  single-device engine in the same process, and checks from the shards
  that the node axis is spread over all four devices.

Each phase prints one line: its name, ``compile_s`` (XLA compile or
persistent-cache load, summed from JAX's compile events), ``run_s`` (the
rest of the phase's wall time: tracing, host work and device execution)
and its checks.  The last line of stdout is the JSON result
``{"ok": true, "device": {...}}``, printed only when every check passed.
Timings are bring-up evidence, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SRC = Path(__file__).resolve().parent / "src"

FED_MODALITIES = ("image", "text", "genetics", "tabular")
FED_ROUNDS, FED_BLOCK = 4, 2
# round 1, Pallas Gram vs reference Gram, each in its own fused block
# from the same start.  Round-1 losses and precision weights come from
# the local epochs, before the server step, in two programs that differ
# only in the Gram: they should agree to rounding.  (A fused block and a
# single-round program do not: bf16 differences grow over 10 local AdamW
# steps, and on the chip round-1 task loss was 0.051 in the block vs
# 0.028 in a single round.)  Later rounds see different consensus Grams
# and are not compared.  The CKA is built
# from the Grams: the reference Gram is a plain f32 matmul, which XLA
# runs as one bf16 pass on the TPU (2^-8 ~ 4e-3 relative per entry),
# and CKA, a normalised inner product of Grams, averages that down; a
# wrong kernel (unnormalised rows, a misplaced tile) moves it by O(0.1).
FED_LOSS_RTOL, FED_LOSS_ATOL = 1e-3, 1e-5
FED_CKA_ATOL = 1e-2

SERVE_PROMPT_LENS = (32, 128, 512)
SERVE_MAX_NEW = (16, 24, 32, 48)
SERVE_REQUESTS = 16
# relative L2 distance of one decode step's logits, Pallas vs reference:
# the reference accumulates attention in bf16 (the cache dtype) and the
# kernel in f32; a bf16 rounding is 2^-8 ~ 0.4% relative and 30 residual
# layers compound it, while a wrong mask or head mapping gives O(1)
SERVE_LOGITS_RTOL = 5e-2

# sharded vs single-device federation: the same per-node math, but one
# node per device per bucket instead of a vmapped stack lets XLA tile the
# model differently, and the server step's sums become psums.  In bf16
# two correct programs of this engine drift apart over AdamW's local
# steps (on the chip, round-1 task loss was 0.051 in a fused block and
# 0.028 in a single-round program), so the comparison runs in f32 with
# full-precision matmuls, where that drift stays far below 1e-3; a node
# on the wrong device or a missing psum moves the weights by O(0.1)
MESH_RTOL = 1e-3


class CompileLog:
    """Sums JAX's compile events (XLA compiles and persistent-cache
    loads) between resets."""

    def __init__(self):
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def reset(self) -> None:
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def run_phase(log: CompileLog, name: str, fn, *args) -> bool:
    """Run one phase; print its line; return whether every check passed.
    Exceptions propagate: a phase that raises fails the script."""
    log.reset()
    t0 = time.perf_counter()
    checks, info = fn(*args)
    wall = time.perf_counter() - t0
    ok = all(checks.values())
    print(f"[{name}] {'PASS' if ok else 'FAIL'} compile_s={log.seconds!r} "
          f"run_s={wall - log.seconds!r} compiles={log.compiles} "
          f"cache_hits={log.cache_hits} checks="
          + ",".join(f"{k}:{'ok' if v else 'FAIL'}"
                     for k, v in checks.items())
          + " " + json.dumps(info, default=float), flush=True)
    return ok


def _finite(x) -> bool:
    return bool(np.all(np.isfinite(np.asarray(x, np.float64))))


def _close(a, b, rtol, atol=0.0) -> bool:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return bool(np.all(np.abs(a - b) <= atol + rtol * np.abs(b)))


# ----------------------------------------------------------------------
def federation_phase(cfg, seed: int):
    from repro.core.federation import Federation, FederationConfig
    fcfg = FederationConfig(n_nodes=4, modalities=FED_MODALITIES,
                            method="geodora", aggregation="precision",
                            seed=seed)
    fed = Federation(fcfg, cfg)
    taps = []
    hist = fed.run_rounds(FED_ROUNDS, block_size=FED_BLOCK, tap=taps.append)
    # the block program the jit ran, compiled again from the live state
    t0 = time.perf_counter()
    block = fed.engine.block_fn(FED_BLOCK, tap=taps.append)
    program = block.lower(fed._trains, fed._opts, fed._keys, fed.gbar,
                          fed._server_m, fed._staticss,
                          (None,) * len(fed._trains)).compile().as_text()
    program_s = time.perf_counter() - t0
    first = hist[0]
    ref = Federation(fcfg, cfg, gram_backend="reference").run_rounds(
        FED_BLOCK, block_size=FED_BLOCK, tap=lambda m: None)[0]
    ckas = [h["cross_node_cka"] for h in hist]
    checks = {
        "gram_pallas_compiled": (fed.engine.gram_backend == "pallas"
                                 and not fed.engine.gram_interpret),
        "tpu_custom_call": "tpu_custom_call" in program,
        "tap_every_round": len(taps) == FED_ROUNDS,
        "finite": all(_finite([h["task_loss"], h["geo_loss"],
                               h["cross_node_cka"]])
                      and _finite(h["weights"]) for h in hist),
        "cka_in_unit": all(0.0 <= c <= 1.0 for c in ckas),
        "ref_losses": _close([first["task_loss"], first["geo_loss"]],
                             [ref["task_loss"], ref["geo_loss"]],
                             FED_LOSS_RTOL, FED_LOSS_ATOL),
        "ref_weights": _close(first["weights"], ref["weights"],
                              FED_LOSS_RTOL, FED_LOSS_ATOL),
        "ref_cka": _close(first["cross_node_cka"], ref["cross_node_cka"],
                          0.0, FED_CKA_ATOL),
    }
    info = {
        "task_loss": [h["task_loss"] for h in hist],
        "geo_loss": [h["geo_loss"] for h in hist],
        "cka": ckas,
        "weights_r4": hist[-1]["weights"],
        "program_check_s": program_s,
        "round1": {name: {k: r[k] for k in ("task_loss", "geo_loss",
                                            "cross_node_cka", "weights")}
                   for name, r in (("pallas", first), ("reference", ref))},
    }
    return checks, info


# ----------------------------------------------------------------------
def _requests(vocab: int, seed: int):
    from repro.serve.scheduler import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i, tokens=tuple(int(t) for t in rng.integers(
                        0, vocab, SERVE_PROMPT_LENS[i % len(
                            SERVE_PROMPT_LENS)])),
                    max_new=SERVE_MAX_NEW[i % len(SERVE_MAX_NEW)])
            for i in range(SERVE_REQUESTS)]


def serve_phase(cfg, seed: int):
    from repro.models import transformer as T
    from repro.serve.engine import ServeConfig, ServeEngine
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    requests = _requests(cfg.vocab_size, seed)
    checks, info, runs = {}, {}, {}
    for backend in ("pallas", "reference"):
        eng = ServeEngine(params, cfg, ServeConfig(
            n_slots=8, cache_len=1024, block_steps=8, seed=seed,
            attn_backend=backend))
        recs = eng.serve(requests)
        runs[backend] = (eng, recs)
        checks[f"{backend}_completed"] = all(
            r.state == "completed" and r.attempts == 1
            and len(r.tokens) == r.request.max_new for r in recs.values())
        checks[f"{backend}_no_faults"] = (
            eng.stats["faults_detected"] == 0
            and eng.stats["stalls_detected"] == 0)
        info[f"{backend}_stats"] = {k: eng.stats[k] for k in (
            "block_dispatches", "block_tokens", "admit_dispatches")}
    eng, recs = runs["pallas"]
    checks["pallas_on_chip"] = (eng.attn_backend == "pallas"
                                and not eng.attn_interpret)
    cancel = jnp.zeros((eng.scfg.n_slots,), bool)
    program = eng._get_block(None).lower(
        eng.params, eng.state, cancel).compile().as_text()
    checks["tpu_custom_call"] = "tpu_custom_call" in program

    # one decode step from the same filled pool through both backends
    ref_eng, ref_recs = runs["reference"]
    state = ref_eng.state

    def step_logits(backend):
        e = runs[backend][0]
        fn = jax.jit(lambda p, c, t: T.decode_step_slots(
            p, c, {"tokens": t}, cfg, step_mask=state["active"],
            attn_backend=e.attn_backend, attn_interpret=e.attn_interpret
        )[0][:, 0].astype(jnp.float32))
        return np.asarray(fn(params, state["cache"], state["last_tok"]))

    lp, lr = step_logits("pallas"), step_logits("reference")
    rel = float(np.linalg.norm(lp - lr) / np.linalg.norm(lr))
    same = sum(recs[i].tokens == ref_recs[i].tokens for i in recs)
    checks["pallas_matches_reference"] = (same == len(recs)
                                          or rel <= SERVE_LOGITS_RTOL)
    checks["logits_finite"] = _finite(lp) and _finite(lr)
    info.update(requests_with_same_tokens=same, step_logits_rel_l2=rel,
                step_argmax_agree=int((lp.argmax(-1) == lr.argmax(-1)).sum()))
    return checks, info


# ----------------------------------------------------------------------
def mesh_phase(cfg, seed: int, n_dev: int):
    from repro.core.federation import Federation, FederationConfig
    mesh = jax.make_mesh((n_dev,), ("data",))
    # two width buckets of 4 nodes: one node of each per device
    fcfg = FederationConfig(n_nodes=2 * n_dev, modalities=("image", "text"),
                            method="geodora", aggregation="precision",
                            seed=seed)
    with jax.default_matmul_precision("highest"):
        f_mesh = Federation(fcfg, cfg, mesh=mesh)
        h_mesh = f_mesh.run_rounds(2, block_size=2)
        h_one = Federation(fcfg, cfg).run_rounds(2, block_size=2)
    spread = []
    for bucket in f_mesh._trains:
        for leaf in jax.tree.leaves(bucket):
            rows = sorted((s.index[0].start or 0, s.device.id)
                          for s in leaf.addressable_shards)
            spread.append(len({d for _, d in rows}) == n_dev
                          and len({r for r, _ in rows}) == n_dev)
    keys = ("task_loss", "geo_loss", "cross_node_cka")
    checks = {
        "gram_pallas_compiled": (f_mesh.engine.gram_backend == "pallas"
                                 and not f_mesh.engine.gram_interpret),
        "nodes_on_all_devices": bool(spread) and all(spread),
        "finite": all(_finite([h[k] for k in keys]) and _finite(h["weights"])
                      for h in h_mesh),
        "matches_single_device": all(
            _close([a[k] for k in keys], [b[k] for k in keys], MESH_RTOL,
                   MESH_RTOL)
            and _close(a["weights"], b["weights"], MESH_RTOL, MESH_RTOL)
            for a, b in zip(h_mesh, h_one)),
    }
    leaf = jax.tree.leaves(f_mesh._trains[0])[0]
    info = {
        "buckets": [len(b) for b in f_mesh._buckets],
        "bucket0_leaf0_shards": [
            [s.device.id, s.index[0].start, s.index[0].stop]
            for s in leaf.addressable_shards],
        "mesh": {k: [h[k] for h in h_mesh] for k in keys},
        "single": {k: [h[k] for h in h_one] for k in keys},
        "max_weight_diff": max(
            float(np.max(np.abs(np.subtract(a["weights"], b["weights"]))))
            for a, b in zip(h_mesh, h_one)),
    }
    return checks, info


# ----------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded federation on a "
                         "4-device mesh and its single-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU; JAX found platform "
              f"{device['platform']!r} ({device['kind']}, "
              f"{device['count']} devices)", file=sys.stderr)
        return 2
    if len(dev) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(dev)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(SRC))
    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config
    cache_dir = enable_compile_cache()
    log = CompileLog()
    print(f"[device] PASS platform={device['platform']} "
          f"kind={device['kind']!r} count={device['count']} "
          f"jax={jax.__version__} cache_dir={cache_dir}", flush=True)

    fed_cfg = get_config("fedmm-small")
    if args.chips == 4:
        # depth cut to 2 layers at full width, in f32 (see MESH_RTOL): the
        # mesh and its collectives are what this path checks
        ok = run_phase(log, "mesh4", mesh_phase,
                       fed_cfg.with_(n_layers=2, dtype="float32"),
                       args.seed, 4)
    else:
        ok = run_phase(log, "federation", federation_phase, fed_cfg,
                       args.seed)
        ok = run_phase(log, "serve", serve_phase,
                       get_config("smollm-135m"), args.seed) and ok
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
