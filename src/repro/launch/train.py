"""Federated LM training driver on the shared node-stacked round engine.

The full protocol at mesh scale, built on ``repro.core.engine.RoundEngine``
— the same engine that powers ``repro.core.federation.Federation``: node
trainables/opt-states carry a leading node axis, E local steps run as a
scanned vmap with ZERO cross-node communication, and each round closes with
the server step (consensus Gram + LAP precision weighting + side-car
averaging) inside the SAME compiled call.

With ``--block-size M > 1`` the driver fuses M whole rounds into one
donated dispatch (``engine.run_block``: lax.scan over the round body):
batches for a block are leaf-stacked host-side into one (M, E, K, B, S)
tensor and shipped as a single async transfer, the NEXT block's batches are
staged while the current block is in flight (double buffering), and
per-round metrics stream back through an ``io_callback`` tap — the host
never blocks between blocks, so dispatches and blocking syncs drop to 1/M
per round.  ``--block-size 1`` is the exact legacy per-round path;
``--block-size auto`` measures the host dispatch overhead once at startup
(the first two rounds run per-round and are timed) and picks M so host
work stays under 5% of round time.  ``--server-momentum`` enables
FedOpt-style momentum on the averaged side-cars in the engine's server
step.  ``--warmup-rounds N`` turns on a warmup+cosine LR schedule keyed on
the GLOBAL round counter the engine threads through the scan carry, so the
schedule advances across fused blocks without re-jitting.

Partial participation (``--participation uniform --cohort-size C``,
``--participation dropout --dropout-rate p``, or ``precision``): each
round's reporting cohort is sampled ON DEVICE from a carried sampler
state, so sampling composes with the fused blocks; non-reporting nodes
carry their state through untouched and the server averages over exactly
the cohort.  Communication per round is low-rank-sized — the paper's
efficiency claim, printed per round.

  PYTHONPATH=src python -m repro.launch.train --arch fedmm-small \
      --rounds 8 --block-size 4 --local-steps 4 --batch 8 --seq 128 \
      --participation uniform --cohort-size 2 --tiny
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core import cka as cka_mod
from repro.core import lora as lora_mod
from repro.core import participation as part_mod
from repro.core.engine import EngineConfig, RoundEngine, auto_block_size
from repro.data.pipeline import BlockStager, SyntheticLMStream
from repro.models import transformer as T
from repro.models.common import cross_entropy_loss
from repro.optim.adamw import AdamW, warmup_cosine


def _broadcast_tree(tree, k):
    return jax.tree.map(
        lambda x: None if x is None else
        jnp.broadcast_to(x, (k,) + x.shape).copy(), tree,
        is_leaf=lambda x: x is None)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="fedmm-small")
    ap.add_argument("--method", default="geodora",
                    choices=["geolora", "geodora", "fedavg_full"])
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)     # per node
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--anchors", type=int, default=16)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--lambda-geo", type=float, default=1.0)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--block-size", default="1",
                    help="fuse M rounds per dispatch (1 = legacy "
                         "per-round; 'auto' measures dispatch overhead at "
                         "startup and picks M for < 5%% host work)")
    ap.add_argument("--server-momentum", type=float, default=None,
                    help="server-side FedOpt momentum on the averaged "
                         "side-cars (off when unset)")
    ap.add_argument("--participation", default="full",
                    choices=["full", "uniform", "precision", "dropout",
                             "async"],
                    help="per-round cohort sampling strategy ('async' "
                         "turns on the buffered staleness-aware protocol: "
                         "nodes report after a sampled lag, may crash and "
                         "rejoin, and the server staleness-weights "
                         "whatever landed this round)")
    ap.add_argument("--cohort-size", type=int, default=None,
                    help="nodes sampled per round (uniform / precision)")
    ap.add_argument("--dropout-rate", type=float, default=0.25,
                    help="per-node straggler probability (dropout)")
    ap.add_argument("--participation-seed", type=int, default=0)
    ap.add_argument("--lag-dist", default="fixed",
                    choices=["fixed", "geometric"],
                    help="async: per-report lag distribution")
    ap.add_argument("--lag", type=int, default=1,
                    help="async: fixed lag in rounds (lag 0 = deliver "
                         "the same round, i.e. synchronous timing)")
    ap.add_argument("--lag-p", type=float, default=0.5,
                    help="async: geometric lag success probability")
    ap.add_argument("--max-lag", type=int, default=4,
                    help="async: lag draws are clipped to this many rounds")
    ap.add_argument("--crash-rate", type=float, default=0.0,
                    help="async: per-round probability an online node "
                         "crashes (losing its in-flight report)")
    ap.add_argument("--rejoin-rate", type=float, default=0.5,
                    help="async: per-round probability a crashed node "
                         "rejoins")
    ap.add_argument("--transient-rate", type=float, default=0.0,
                    help="async: per-round probability an idle node "
                         "transiently fails to start a report")
    ap.add_argument("--staleness", default="poly",
                    choices=["poly", "cutoff"],
                    help="async: staleness schedule on report weights "
                         "(poly: (1+lag)^-alpha; cutoff: hard drop past "
                         "--max-staleness)")
    ap.add_argument("--staleness-alpha", type=float, default=1.0,
                    help="async: exponent of the poly staleness schedule")
    ap.add_argument("--max-staleness", type=int, default=None,
                    help="async: reports older than this many rounds get "
                         "zero aggregation weight")
    ap.add_argument("--quarantine-norm", type=float, default=1e6,
                    help="async: reports with non-finite values or an "
                         "update norm above this are quarantined (zero "
                         "contribution, per-node counter bumped)")
    ap.add_argument("--poison-nodes", default="",
                    help="async fault injection: comma-separated node ids "
                         "whose reports are corrupted to NaN on device "
                         "(exercises the quarantine guard)")
    ap.add_argument("--warmup-rounds", type=int, default=0,
                    help="> 0 turns on warmup+cosine LR over GLOBAL "
                         "rounds (threaded through the fused-block carry)")
    ap.add_argument("--tiny", action="store_true",
                    help="shrink the model for CPU smoke runs")
    ap.add_argument("--precision-weighting", action="store_true",
                    default=True)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.tiny:
        cfg = cfg.with_(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                        head_dim=32, d_ff=256, vocab_size=512,
                        dtype="float32")
    k_nodes = args.nodes
    key = jax.random.PRNGKey(0)
    rt = T.Runtime()

    params = T.init_params(key, cfg)
    if args.method != "fedavg_full":
        spec = lora_mod.LoRASpec(rank=args.rank,
                                 dora=(args.method == "geodora"))
        params = lora_mod.attach_lora(jax.random.fold_in(key, 1), params,
                                      spec)
        mask = lora_mod.trainable_mask(params)
    else:
        mask = jax.tree.map(lambda _: True, params)
    trainable, frozen = lora_mod.partition(params, mask)
    round_sched = (warmup_cosine(args.warmup_rounds, max(args.rounds, 1))
                   if args.warmup_rounds > 0 else None)
    opt = AdamW(lr=args.lr, grad_clip=1.0, round_schedule=round_sched)
    poison = tuple(int(x) for x in args.poison_nodes.split(",") if x.strip())
    plan = part_mod.normalize(part_mod.ParticipationPlan(
        strategy=args.participation, cohort_size=args.cohort_size,
        dropout_rate=args.dropout_rate, seed=args.participation_seed,
        lag_dist=args.lag_dist, lag=args.lag, lag_p=args.lag_p,
        max_lag=args.max_lag, crash_rate=args.crash_rate,
        rejoin_rate=args.rejoin_rate, transient_rate=args.transient_rate,
        staleness=args.staleness, staleness_alpha=args.staleness_alpha,
        max_staleness=args.max_staleness,
        quarantine_norm=args.quarantine_norm, poison_nodes=poison))

    anchors = jax.random.randint(jax.random.fold_in(key, 2),
                                 (args.anchors, args.seq), 0, cfg.vocab_size)
    lambda_geo = args.lambda_geo

    def local_step(train_k, opt_k, key_k, gbar, _statics, batch):
        def loss_fn(tr):
            p = lora_mod.combine(tr, frozen)
            logits, aux = T.forward(p, {"tokens": batch["tokens"]}, cfg, rt)
            task = cross_entropy_loss(logits, batch["labels"])
            _, a_aux = T.forward(p, {"tokens": anchors}, cfg, rt)
            gram = cka_mod.cosine_gram(a_aux["pooled"])
            geo = 1.0 - cka_mod.cka(gram, gbar)
            return task + lambda_geo * geo, \
                (task, geo, aux["pooled"], a_aux["pooled"])
        grads, (task, geo, pooled, pooled_a) = \
            jax.grad(loss_fn, has_aux=True)(train_k)
        new_train, new_opt = opt.update(grads, opt_k, train_k)
        return new_train, new_opt, key_k, {
            "task": task, "geo": geo,
            "pooled": pooled, "pooled_a": pooled_a}

    # LM nodes have no node-local adapters: every trainable leaf is shipped
    # and every node shares one width — a single engine bucket
    shipped = jax.tree.map(lambda p: None if p is None else True,
                           trainable, is_leaf=lambda x: x is None)
    engine = RoundEngine(
        EngineConfig(n_nodes=k_nodes, local_steps=args.local_steps,
                     aggregation=("precision" if args.precision_weighting
                                  else "uniform"),
                     server_momentum=args.server_momentum),
        opt, local_step, (shipped,))

    node_train = (_broadcast_tree(trainable, k_nodes),)
    node_opt = (jax.vmap(opt.init)(node_train[0]),)
    node_keys = (jax.random.split(jax.random.fold_in(key, 3), k_nodes),)
    gbar = jnp.eye(args.anchors)
    server_m = engine.init_server_state(node_train)

    part_state = (engine.init_async_state(node_train, plan,
                                          gram_side=args.anchors)
                  if plan is not None and plan.strategy == "async"
                  else part_mod.init_state(plan, k_nodes))
    streams = [iter(SyntheticLMStream(cfg.vocab_size, args.seq, args.batch,
                                      seed=100 + i)) for i in range(k_nodes)]
    up_bytes = lora_mod.param_bytes(trainable) + args.anchors ** 2 * 4
    full_bytes = lora_mod.param_bytes(lora_mod.combine(trainable, frozen))
    t0 = time.time()
    rnd_counter = [0]

    def cohort_of(metrics, r=None):
        if "cohort_size" not in metrics:
            return k_nodes
        c = metrics["cohort_size"] if r is None else metrics["cohort_size"][r]
        return max(int(round(float(c))), 1)

    def round_task(metrics, r=None):
        t = (metrics["scalars"]["task"] if r is None
             else metrics["scalars"]["task"][r])
        return float(jnp.sum(t)) / cohort_of(metrics, r)

    def log_round(metrics):
        rnd = rnd_counter[0]
        rnd_counter[0] += 1
        scalars, c = metrics["scalars"], cohort_of(metrics)
        cohort = f" cohort={c}/{k_nodes}" if "cohort_size" in metrics else ""
        if "n_delivered" in metrics:
            qs = [int(round(float(x))) for x in metrics["quarantined"]]
            cohort += (f" delivered={float(metrics['n_delivered']):.0f}"
                       + (f" quarantined={qs}" if any(qs) else ""))
        print(f"round {rnd}: task={float(jnp.sum(scalars['task']))/c:.4f} "
              f"geo={float(jnp.sum(scalars['geo']))/c:.4f} "
              f"xcka={float(metrics['cross_node_cka']):.3f} "
              f"w={[round(float(x), 3) for x in metrics['weights']]}"
              f"{cohort} "
              f"uplink={up_bytes/1e6:.3f}MB vs full {full_bytes/1e6:.1f}MB "
              f"({100 * (1 - up_bytes / full_bytes):.2f}% saved) "
              f"[{time.time()-t0:.0f}s]", flush=True)

    def stage_round():
        step_batches = []
        for _ in range(args.local_steps):
            per_node = [next(s) for s in streams]
            step_batches.append(jax.tree.map(
                lambda *xs: jnp.stack(xs), *per_node))
        return jax.tree.map(lambda *xs: jnp.stack(xs), *step_batches)

    # round state as a mutable list so the per-round and fused paths share
    # it (the participation sampler state rides along when a plan is on)
    state = [node_train, node_opt, node_keys, gbar, server_m]
    if plan is not None:
        state.append(part_state)
    round_fn = engine.part_round_fn(plan) if plan else engine.round_fn

    def run_one(batches):
        out = round_fn(*state, (None,), (batches,))
        state[:] = out[:-1]
        return out[-1]

    auto = str(args.block_size) == "auto"
    block_size = 1 if auto else int(args.block_size)
    last_metrics = None
    rounds_left = args.rounds
    if rounds_left <= 0:
        return 0.0
    if auto:
        # measure ONCE at startup: round 0 pays compilation (warmup),
        # round 1 times the async dispatch (host work) vs the full round,
        # and M is picked so host work < 5% of round time under M-blocks
        last_metrics = run_one(stage_round())
        log_round(last_metrics)
        rounds_left -= 1
        if rounds_left > 0:
            batches = stage_round()
            t0m = time.perf_counter()
            last_metrics = run_one(batches)
            t_dispatch = time.perf_counter() - t0m
            jax.block_until_ready(last_metrics)
            t_round = time.perf_counter() - t0m
            block_size = auto_block_size(t_dispatch, t_round)
            print(f"[auto] dispatch={t_dispatch*1e3:.2f}ms "
                  f"round={t_round*1e3:.2f}ms -> block size M={block_size}",
                  flush=True)
            log_round(last_metrics)
            rounds_left -= 1
    if rounds_left > 0 and block_size <= 1:
        # legacy per-round path: one dispatch and one host sync per round
        for _ in range(rounds_left):
            last_metrics = run_one(stage_round())
            log_round(last_metrics)
        final_task = round_task(last_metrics)
    elif rounds_left > 0:
        # fused blocks: M rounds per donated dispatch, metrics streamed via
        # the io_callback tap, next block's batches staged while the current
        # block is in flight — no block_until_ready anywhere in the loop
        stager = BlockStager(streams, args.local_steps, block_size)
        next_batches = stager.next_block(min(block_size, rounds_left))
        while rounds_left > 0:
            m = min(block_size, rounds_left)
            batches = next_batches
            new_state, last_metrics = engine.run_block(
                tuple(state), m, statics=(None,), batches=(batches,),
                tap=log_round, plan=plan)
            state[:] = list(new_state)
            rounds_left -= m
            if rounds_left > 0:         # double buffer: stage block N+1
                next_batches = stager.next_block(
                    min(block_size, rounds_left))
        # the ONLY host sync of the whole run: materialise the last round's
        # task loss, then drain the tap callbacks (metric readback alone
        # does not wait for the io_callback thread — without the barrier
        # the last round's log lines can be lost at process exit)
        final_task = round_task(last_metrics, r=-1)
        jax.effects_barrier()
    else:
        final_task = round_task(last_metrics)
    return final_task


if __name__ == "__main__":
    enable_compile_cache()
    main()
