"""Width-bucketed node-stacked federation round engine.

The paper's protocol is embarrassingly parallel across nodes: K clients run
E local steps with zero cross-node communication, then a low-rank server
step (consensus Gram, LAP precision weights, side-car averaging) closes the
round.  This module executes that structure as ONE compiled program instead
of K x E separate jit dispatches:

  - per-node trainables / opt states / RNG keys are stacked along a leading
    node axis.  Heterogeneous tokenizer widths are grouped into W *width
    buckets* by the caller: each bucket stacks only the nodes whose
    adapters share a (padded) width, so a 192-wide tabular node never pays
    the w^2 compute of the 2048-wide text bucket.  Bucket membership is
    static, so the W per-bucket sub-programs are stitched by a plain Python
    loop at trace time — the round is still a single jit dispatch;
  - within a bucket, ``jax.vmap`` maps the caller's ``local_step`` across
    the node axis and ``jax.lax.scan`` runs the E local steps
    (zero-padding to the bucket width is exact: padded rows see zero
    inputs, receive zero gradients, and stay zero under AdamW);
  - the server step (Gram consensus + precision weights + shipped-side-car
    averaging + broadcast) runs once on the bucket-concatenated pooled
    activations, in the same program — shipped side-cars have identical
    shapes in every bucket, so the cross-bucket average is a per-bucket
    partial sum followed by a broadcast back into each bucket;
  - round-state buffers (trainables, opt states, RNG keys, consensus Gram)
    are DONATED to the compiled round (``donate_argnums``), so round N's
    outputs alias round N+1's inputs and peak round-state memory stays at
    ~1x instead of 2x at large K;
  - with ``mesh=...`` each bucket's node axis is mapped onto the mesh batch
    axes via ``shard_map`` and the server step becomes ``psum`` /
    ``all_gather`` collectives whose payload is low-rank-sized (the paper's
    communication claim, now visible as the program's only cross-slice
    traffic);
  - ``run_block(state, M)`` fuses M whole rounds into ONE dispatch: the
    round body above becomes the body of a ``jax.lax.scan`` over rounds, the
    carry is (trains, opts, keys, gbar, server-opt state) and is donated, so
    at production round rates the host pays one dispatch and zero blocking
    syncs per M rounds instead of one each per round.  Per-round batches are
    either pre-staged as an (M, E, K, ...) leaf-stacked tensor scanned over,
    or drawn on-device from the carried RNG streams (``batches=None``);
    per-round metrics accumulate into (M, ...) device buffers returned at
    block end, with an optional ``io_callback`` tap that streams each
    round's metrics to a host logger without forcing a sync (ordered on a
    single host; unordered per-host on a mesh, each payload carrying its
    round index, so pods are never serialised by the log stream);
  - a ``ParticipationPlan`` (``repro.core.participation``) threads sampled
    cohorts and straggler masks through all of the above: the cohort is
    drawn ON DEVICE from a carried sampler state (part of the donated
    round/block carry, so it composes with the fused scan and
    checkpoints), static-cohort strategies GATHER the cohort rows into
    compact per-bucket states so local-epoch compute scales with the
    cohort size C instead of K, the dropout/straggler path masks state
    updates so non-reporters carry through untouched, and the server step
    (consensus Gram, LAP precisions, side-car average, FedAvgM) runs over
    exactly the reporting cohort.  ``participation=full`` never touches
    any of this — it routes to the unchanged legacy program.

The engine is workload-agnostic: ``local_step`` owns the loss (multimodal
classification in ``core.federation``, LM fine-tuning in ``launch.train``,
the one-local-step FedSGD form in ``launch.steps``); the engine owns
batching, the round loop, and the server math.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import aggregation as agg
from repro.core import cka as cka_mod
from repro.core import participation as part_mod
from repro.core import uncertainty as unc

Array = jax.Array


def auto_block_size(dispatch_s: float, round_s: float, *,
                    target: float = 0.05, cap: int = 64) -> int:
    """Pick the fused-block size M from measured host dispatch overhead:
    the per-round host work under M-round blocks is ~``dispatch_s / M``,
    so the smallest M with ``dispatch_s / M < target * round_s`` keeps
    host work under ``target`` (default 5%) of round time.  Clamped to
    [1, cap]; degenerate measurements (zero/negative round time) take the
    cap.  Drivers measure once at startup (``--block-size auto``)."""
    if round_s <= 0 or dispatch_s <= 0:
        return cap if round_s <= 0 else 1
    import math
    m = math.ceil(dispatch_s / (target * round_s))
    return max(1, min(int(m), cap))

# local_step(train, opt_state, key, gbar, statics, batch)
#   -> (train, opt_state, key, aux)
# where aux holds per-node "pooled" (B, D) and "pooled_a" (Ba, D) plus any
# scalar metrics; train/opt_state/statics/batch are the PER-NODE slices.
LocalStep = Callable[..., Tuple[Any, Any, Array, dict]]


@dataclass(frozen=True)
class EngineConfig:
    n_nodes: int
    local_steps: int
    aggregation: str = "precision"     # precision | uniform
    center_cka: bool = False
    # width buckets: per-bucket node counts (sum == n_nodes).  () means a
    # single bucket of all n_nodes (the homogeneous / legacy-padded layout).
    bucket_sizes: Tuple[int, ...] = ()
    # canonical node id of each engine row (bucket-concatenated order);
    # () means identity.  Metrics are returned in CANONICAL node order.
    node_perm: Tuple[int, ...] = ()
    # donate round-state buffers (train/opt/keys/gbar) to the compiled
    # round so outputs alias inputs (halves peak round-state memory).
    donate: bool = True
    # Gram backend for the server step: "auto" (Pallas on TPU, reference
    # elsewhere), "reference" (core.cka), or "pallas" (kernels.gram; runs
    # in interpreter mode off-TPU so it stays testable on CPU).
    gram_backend: str = "auto"
    # server-side FedOpt: momentum coefficient applied to the round's
    # pseudo-gradient (broadcast value of the previous round minus the
    # precision-weighted average) before re-broadcasting.  ``None`` disables
    # the feature entirely (exact legacy server step, no extra carried
    # state); 0.0 keeps the state but reduces to the plain average.
    server_momentum: Optional[float] = None


def pad_axis(x: Array, width: int, axis: int = -1) -> Array:
    """Zero-pad ``axis`` of ``x`` up to ``width`` (no-op when already there).
    Zero padding keeps the padded program exactly equivalent: padded input
    columns are zero, so padded weight rows get zero gradients and never
    leave zero under moment-based optimizers without weight decay."""
    n = x.shape[axis]
    if n == width:
        return x
    if n > width:
        raise ValueError(f"axis {axis} has {n} > target width {width}")
    pads = [(0, 0)] * x.ndim
    pads[axis if axis >= 0 else x.ndim + axis] = (0, width - n)
    return jnp.pad(x, pads)


def stack_nodes(trees) -> Any:
    """Stack structurally identical per-node pytrees along a new leading
    node axis (``None`` placeholder leaves pass through)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def masked_select(mask: Array, new_tree, old_tree):
    """Per-row state selection under a participation mask: rows with
    ``mask > 0`` take the advanced value, other rows carry the old one
    through untouched.  Works on whole pytrees (or bare arrays) whose
    leaves lead with the node-row axis — what makes a straggler's round a
    no-op on every piece of its state."""
    def sel(new, old):
        m = mask.reshape((mask.shape[0],) + (1,) * (new.ndim - 1)) > 0
        return jnp.where(m, new, old)
    return jax.tree.map(sel, new_tree, old_tree)


def _as_buckets(x) -> tuple:
    return x if isinstance(x, tuple) else (x,)


def _safe_tap(fn, *args):
    """Host side of every engine ``io_callback`` tap: an exception in the
    user's callback (disk-full during an in-block checkpoint, a logger
    bug) is LOGGED AND DROPPED instead of propagating into the runtime
    and killing the in-flight block — taps are observability, never
    control flow."""
    try:
        fn(*args)
    except Exception:
        import logging
        logging.getLogger("repro.engine").exception(
            "engine tap callback raised; payload dropped")


class RoundEngine:
    """One federated round as a single compiled function.

    State layout: the round state is a TUPLE of per-bucket pytrees.  Every
    leaf of ``trains[b]`` / ``opts[b]`` carries a leading node axis of the
    bucket's size; ``keys[b]`` is (k_b, 2) uint32; ``gbar`` is the
    replicated consensus Gram shared by all buckets.  ``round_fn(trains,
    opts, keys, gbar, statics, batches)`` returns ``(trains, opts, keys,
    gbar, metrics)`` where ``metrics = {"scalars": {name: (K,)},
    "weights": (K,), "cross_node_cka": ()}`` — per-node entries in
    CANONICAL node order (the engine un-permutes the bucket layout).

    ``batches[b]`` is either ``None`` (the local step samples its own data
    from the carried RNG keys) or a pytree with leading (E, k_b, ...) axes
    scanned over the local steps.  ``statics[b]`` is a per-node constant
    pytree (leading k_b axis) vmapped alongside the state — anchor tokens,
    modality maps, corrupt/bridge masks.

    Shipped side-car leaves must have identical shapes in every bucket
    (only node-LOCAL leaves — the W_mk adapters — may differ in width),
    which is what lets the server average run across buckets.

    Single-bucket callers pass 1-tuples (a bare pytree is auto-wrapped for
    the shipped mask only; state must always be tuples).
    """

    def __init__(self, ecfg: EngineConfig, opt, local_step: LocalStep,
                 shipped_masks, *, mesh=None, jit: bool = True):
        self.ecfg = ecfg
        self.opt = opt
        self.local_step = local_step
        self.shipped_masks = _as_buckets(shipped_masks)
        self.bucket_sizes = ecfg.bucket_sizes or (ecfg.n_nodes,)
        self.n_buckets = len(self.bucket_sizes)
        if sum(self.bucket_sizes) != ecfg.n_nodes:
            raise ValueError(f"bucket_sizes {self.bucket_sizes} do not sum "
                             f"to n_nodes={ecfg.n_nodes}")
        if len(self.shipped_masks) != self.n_buckets:
            raise ValueError(f"{len(self.shipped_masks)} shipped masks for "
                             f"{self.n_buckets} buckets")
        perm = ecfg.node_perm or tuple(range(ecfg.n_nodes))
        if sorted(perm) != list(range(ecfg.n_nodes)):
            raise ValueError(f"node_perm {perm} is not a permutation")
        inv = [0] * ecfg.n_nodes
        for row, node in enumerate(perm):
            inv[node] = row
        # identity permutations skip the gather entirely
        self._inv_perm = (None if tuple(perm) == tuple(range(ecfg.n_nodes))
                          else tuple(inv))
        self.mesh = mesh
        if ecfg.gram_backend not in ("auto", "reference", "pallas"):
            raise ValueError(f"unknown gram_backend {ecfg.gram_backend!r}; "
                             f"expected auto | reference | pallas")
        # the resolved backend and whether the kernel runs interpreted are
        # public, so a chip check can see a silent fallback
        on_tpu = jax.default_backend() == "tpu"
        self.gram_backend = ecfg.gram_backend
        if self.gram_backend == "auto":
            self.gram_backend = "pallas" if on_tpu else "reference"
        self.gram_interpret = self.gram_backend == "pallas" and not on_tpu
        # canonical node ids per bucket (row order) and the row offset of
        # each bucket — the participation sampler's group layout
        groups, offs, off = [], [], 0
        for kb in self.bucket_sizes:
            groups.append(tuple(perm[off:off + kb]))
            offs.append(off)
            off += kb
        self._groups = tuple(groups)
        self._bucket_offsets = tuple(offs)
        donate = (0, 1, 2, 3, 4) if ecfg.donate else ()
        self._block_cache = {}
        self._part_cache = {}
        self._tap_holders = {}
        if mesh is None:
            # jit=False leaves round_fn as the plain round body, for callers
            # that inline the round into their own compilation boundary
            # (launch.steps owns jit/shardings/donation itself)
            self.round_fn = (jax.jit(self._round, donate_argnums=donate)
                             if jit else self._round)
        else:
            from repro.launch.mesh import batch_axes
            from repro.launch.mesh import n_nodes as mesh_shards
            self._axes = batch_axes(mesh)
            n_shards = mesh_shards(mesh)
            if not self._axes:
                raise ValueError("mesh has no batch axes to map nodes onto")
            for b, kb in enumerate(self.bucket_sizes):
                if kb % n_shards:
                    raise ValueError(
                        f"bucket {b} has {kb} nodes, not divisible by the "
                        f"{n_shards} mesh batch slices {self._axes}")
            self.round_fn = (jax.jit(self._round_sharded,
                                     donate_argnums=donate)
                             if jit else self._round_sharded)

    # ------------------------------------------------------------------
    def _grams_of(self, pooled_a: Array) -> Array:
        """(K, Ba, D) -> (K, Ba, Ba) anchor Grams, dispatched by backend:
        the MXU-tiled Pallas kernel on TPU (interpret mode elsewhere, so
        the dispatch stays CPU-testable), the jnp reference otherwise."""
        if self.gram_backend == "pallas":
            from repro.kernels.gram import cosine_gram_pallas
            fn = functools.partial(cosine_gram_pallas,
                                   interpret=self.gram_interpret)
            return jax.vmap(fn)(pooled_a)
        return jax.vmap(cka_mod.cosine_gram)(pooled_a)

    def _unpermute(self, x: Array) -> Array:
        """Engine-row order (bucket-concatenated) -> canonical node order."""
        if self._inv_perm is None:
            return x
        return jnp.take(x, jnp.asarray(self._inv_perm), axis=0)

    # ------------------------------------------------------------------
    # server-side FedOpt (optional): momentum on the averaged side-cars
    def init_server_state(self, trains):
        """Zero FedOpt momentum tree, shaped like the shipped-leaf average
        (None at non-shipped leaves); ``None`` when the knob is off, so the
        legacy path carries no extra state."""
        if self.ecfg.server_momentum is None:
            return None
        none = lambda x: x is None
        return jax.tree.map(
            lambda l, m: (jnp.zeros(l.shape[1:], jnp.float32)
                          if (l is not None and m) else None),
            trains[0], self.shipped_masks[0], is_leaf=none)

    def _server_prev(self, trains):
        """The value the server broadcast LAST round: shipped rows are
        identical across nodes at round start, so row 0 of bucket 0 is the
        server's previous iterate (float32, None at non-shipped leaves)."""
        none = lambda x: x is None
        return jax.tree.map(
            lambda l, m: (l[0].astype(jnp.float32)
                          if (l is not None and m) else None),
            trains[0], self.shipped_masks[0], is_leaf=none)

    def _apply_server_momentum(self, prev, total, server_m):
        """FedAvgM server step: pseudo-gradient = prev - avg; momentum
        accumulates it and the server re-broadcasts prev - m.  With
        beta == 0 this reduces to broadcasting the plain average."""
        beta = float(self.ecfg.server_momentum)
        none = lambda x: x is None
        new_m = jax.tree.map(
            lambda sm, p, t: None if t is None else beta * sm + (p - t),
            server_m, prev, total, is_leaf=none)
        new_val = jax.tree.map(
            lambda p, m_: None if p is None else p - m_,
            prev, new_m, is_leaf=none)
        return new_m, new_val

    # ------------------------------------------------------------------
    def _local_epochs(self, train, opt_state, keys, gbar, statics, batches):
        """scan over E local steps of the vmapped per-node step; returns the
        advanced state plus the LAST step's aux (pooled / pooled_a /
        scalars) — what the server consumes, mirroring the sequential
        reference.  When the optimizer carries a global-round counter
        (``AdamW.round_schedule``), it is bumped here — once per round,
        only for the nodes whose epochs actually run, so participation
        masking/compaction skips non-reporting nodes' counters too."""
        if isinstance(opt_state, dict) and "round" in opt_state:
            opt_state = dict(opt_state, round=opt_state["round"] + 1)
        batch_axis = None if batches is None else 0

        def body(carry, xs):
            tr, op, ks = carry
            tr, op, ks, aux = jax.vmap(
                self.local_step, in_axes=(0, 0, 0, None, 0, batch_axis),
            )(tr, op, ks, gbar, statics, xs)
            return (tr, op, ks), aux

        (train, opt_state, keys), auxs = jax.lax.scan(
            body, (train, opt_state, keys), batches,
            length=self.ecfg.local_steps if batches is None else None)
        last = jax.tree.map(lambda a: a[-1], auxs)
        return train, opt_state, keys, last

    # ------------------------------------------------------------------
    def _round(self, trains, opts, keys, gbar, server_m, statics, batches):
        k = self.ecfg.n_nodes
        prev = None if server_m is None else self._server_prev(trains)
        trains, opts, keys = list(trains), list(opts), list(keys)
        lasts = []
        # static Python loop over buckets: W sub-vmaps, ONE compiled round
        for b in range(self.n_buckets):
            trains[b], opts[b], keys[b], last = self._local_epochs(
                trains[b], opts[b], keys[b], gbar, statics[b], batches[b])
            lasts.append(last)
        pooled = jnp.concatenate([l.pop("pooled") for l in lasts])
        pooled_a = jnp.concatenate([l.pop("pooled_a") for l in lasts])
        scalars = {name: jnp.concatenate([l[name] for l in lasts])
                   for name in lasts[0]}

        # ---- server (same program: no extra dispatch) ----
        grams = self._grams_of(pooled_a)
        new_gbar = cka_mod.consensus_gram(grams)
        if self.ecfg.aggregation == "precision":
            weights = unc.precision_weights(
                unc.batched_precisions(pooled, pooled_a))
        else:
            weights = jnp.full((k,), 1.0 / k, jnp.float32)
        if server_m is None:
            trains = agg.weighted_average_bucketed(
                tuple(trains), weights, self.shipped_masks,
                self.bucket_sizes)
        else:
            total = agg.bucketed_partial_sums(
                tuple(trains), weights, self.shipped_masks,
                self.bucket_sizes)
            server_m, new_val = self._apply_server_momentum(
                prev, total, server_m)
            trains = agg.broadcast_into_buckets(
                tuple(trains), self.shipped_masks, new_val)
        metrics = {
            "scalars": {name: self._unpermute(v)
                        for name, v in scalars.items()},
            "weights": self._unpermute(weights),
            "cross_node_cka": cka_mod.mean_offdiag_cka(
                grams, center=self.ecfg.center_cka),
        }
        return (tuple(trains), tuple(opts), tuple(keys), new_gbar, server_m,
                metrics)

    # ------------------------------------------------------------------
    # participation-aware round body (sampled cohorts / straggler masks).
    # Kept SEPARATE from ``_round`` so the full-participation path stays
    # byte-for-byte the pre-participation program (``participation=full``
    # is routed to ``round_fn`` and never traces this).
    def _round_part(self, plan, trains, opts, keys, gbar, server_m,
                    part_state, statics, batches):
        """One round under a ``ParticipationPlan``: the sampler draws this
        round's cohort from the carried ``part_state``, local epochs run
        only for (gather-compact) or are only KEPT for (masked) the
        reporting rows, and the whole server step — consensus Gram, LAP
        precisions, side-car average, FedAvgM — runs over the cohort.
        Non-reporting rows carry every piece of state (trainables, opt
        moments, RNG keys, round counters) through untouched, then receive
        the server broadcast like every other row."""
        k = self.ecfg.n_nodes
        prev = None if server_m is None else self._server_prev(trains)
        row_masks, cohort_rows, part_state = part_mod.sample_rows(
            plan, part_state, self._groups)
        compact = (plan.compact and part_mod.static_cohort(plan)
                   and cohort_rows is not None)
        trains, opts, keys = list(trains), list(opts), list(keys)
        offs = self._bucket_offsets

        if compact:
            # gather the cohort rows into compact (c_b, ...) states: local
            # epochs cost compute proportional to the cohort size C, not K
            comp_trains, comp_sizes, comp_masks = [], [], []
            lasts, rows_global = [], []
            for b in range(self.n_buckets):
                idx = cohort_rows[b]
                if int(idx.shape[0]) == 0:     # statically empty bucket
                    continue
                gat = lambda x: jnp.take(x, idx, axis=0)
                tr_c = jax.tree.map(gat, trains[b])
                op_c = jax.tree.map(gat, opts[b])
                ke_c = jnp.take(keys[b], idx, axis=0)
                st_c = (None if statics[b] is None
                        else jax.tree.map(gat, statics[b]))
                bt_c = (None if batches[b] is None
                        else jax.tree.map(
                            lambda x: jnp.take(x, idx, axis=1), batches[b]))
                tr_c, op_c, ke_c, last = self._local_epochs(
                    tr_c, op_c, ke_c, gbar, st_c, bt_c)
                # scatter the advanced cohort back; other rows untouched
                trains[b] = jax.tree.map(
                    lambda f, p: f.at[idx].set(p), trains[b], tr_c)
                opts[b] = jax.tree.map(
                    lambda f, p: f.at[idx].set(p), opts[b], op_c)
                keys[b] = keys[b].at[idx].set(ke_c)
                comp_trains.append(tr_c)
                comp_sizes.append(int(idx.shape[0]))
                comp_masks.append(self.shipped_masks[b])
                lasts.append(last)
                rows_global.append(offs[b] + idx)
            pooled = jnp.concatenate([l.pop("pooled") for l in lasts])
            pooled_a = jnp.concatenate([l.pop("pooled_a") for l in lasts])
            rows_cat = jnp.concatenate(rows_global)          # (C,) row ids
            c = int(rows_cat.shape[0])
            mask_rows = jnp.concatenate(row_masks)

            # ---- server over the cohort (same program) ----
            grams = self._grams_of(pooled_a)
            new_gbar = cka_mod.consensus_gram(grams)         # C rows only
            p_c = None
            if (self.ecfg.aggregation == "precision"
                    or plan.strategy == "precision"):
                p_c = unc.batched_precisions(pooled, pooled_a)
            if self.ecfg.aggregation == "precision":
                w_c = unc.precision_weights(p_c)
            else:
                w_c = jnp.full((c,), 1.0 / c, jnp.float32)
            total = agg.bucketed_partial_sums(
                tuple(comp_trains), w_c, tuple(comp_masks),
                tuple(comp_sizes))
            if server_m is not None:
                server_m, total = self._apply_server_momentum(
                    prev, total, server_m)
            trains = list(agg.broadcast_into_buckets(
                tuple(trains), self.shipped_masks, total))
            scatter = lambda v: jnp.zeros((k,), jnp.float32).at[
                rows_cat].set(v.astype(jnp.float32))
            scalars = {name: scatter(jnp.concatenate([l[name]
                                                      for l in lasts]))
                       for name in lasts[0]}
            weights_rows = scatter(w_c)
            xcka = cka_mod.mean_offdiag_cka(grams,
                                            center=self.ecfg.center_cka)
            if p_c is not None:
                part_state = part_mod.update_state(
                    plan, part_state, mask_rows, scatter(p_c))
        else:
            # masked path (dropout / opted-out compaction): every row
            # computes, only reporting rows' state advances — the update
            # selection is what makes a straggler's round a no-op
            lasts = []
            for b in range(self.n_buckets):
                tr2, op2, ke2, last = self._local_epochs(
                    trains[b], opts[b], keys[b], gbar, statics[b],
                    batches[b])
                mb = row_masks[b]
                trains[b] = masked_select(mb, tr2, trains[b])
                opts[b] = masked_select(mb, op2, opts[b])
                keys[b] = masked_select(mb, ke2, keys[b])
                lasts.append(last)
            pooled = jnp.concatenate([l.pop("pooled") for l in lasts])
            pooled_a = jnp.concatenate([l.pop("pooled_a") for l in lasts])
            mask_rows = jnp.concatenate(row_masks)

            grams = self._grams_of(pooled_a)
            new_gbar = cka_mod.consensus_gram(grams, mask=mask_rows)
            p_rows = None
            if (self.ecfg.aggregation == "precision"
                    or plan.strategy == "precision"):
                p_rows = unc.batched_precisions(pooled, pooled_a)
            if self.ecfg.aggregation == "precision":
                weights_rows = unc.masked_precision_weights(p_rows,
                                                            mask_rows)
            else:
                weights_rows = mask_rows / jnp.maximum(mask_rows.sum(),
                                                       1.0)
            if server_m is None:
                trains = list(agg.weighted_average_bucketed(
                    tuple(trains), weights_rows, self.shipped_masks,
                    self.bucket_sizes, part_mask=mask_rows))
            else:
                total = agg.bucketed_partial_sums(
                    tuple(trains), weights_rows, self.shipped_masks,
                    self.bucket_sizes)
                server_m, total = self._apply_server_momentum(
                    prev, total, server_m)
                trains = list(agg.broadcast_into_buckets(
                    tuple(trains), self.shipped_masks, total))
            scalars = {name: jnp.concatenate([l[name] for l in lasts])
                       * mask_rows for name in lasts[0]}
            xcka = cka_mod.mean_offdiag_cka(
                grams, center=self.ecfg.center_cka, mask=mask_rows)
            if p_rows is not None:
                part_state = part_mod.update_state(
                    plan, part_state, mask_rows, p_rows)

        metrics = {
            "scalars": {name: self._unpermute(v)
                        for name, v in scalars.items()},
            "weights": self._unpermute(weights_rows),
            "cross_node_cka": xcka,
            "participation": self._unpermute(mask_rows),
            "cohort_size": mask_rows.sum(),
        }
        return (tuple(trains), tuple(opts), tuple(keys), new_gbar,
                server_m, part_state, metrics)

    # ------------------------------------------------------------------
    # async (FedBuff-style) round body: buffered reports, staleness-
    # weighted precision averaging, quarantine guard.
    def _shipped_rows(self, trains):
        """(K,)-row stack of the SHIPPED side-car leaves across buckets
        (float32, ``None`` at non-shipped leaves): the payload layout of
        the async report buffer.  Shipped shapes are identical in every
        bucket, so the per-bucket node stacks concatenate along rows."""
        none = lambda x: x is None
        parts = [jax.tree.map(
            lambda l, m_: (l.astype(jnp.float32)
                           if (l is not None and m_) else None),
            tree, mask, is_leaf=none)
            for tree, mask in zip(trains, self.shipped_masks)]
        return jax.tree.map(
            lambda *ls: (None if ls[0] is None else jnp.concatenate(ls)),
            *parts, is_leaf=none)

    def init_async_state(self, trains, plan, gram_side: int):
        """Initial carried async state for ``plan``: the participation CTL
        arrays (RNG key, offline/countdown/lag/quarantined) plus the
        zeroed REPORT BUFFER — per-node shipped side-cars, anchor Gram
        panels, LAP precisions — shaped from ``trains``.  Rides the
        donated round/block carry and the checkpoint like every other
        piece of round state, so fused blocks and kill-and-resume compose
        with the async stream bit-identically."""
        plan = part_mod.normalize(plan)
        if plan is None or plan.strategy != "async":
            raise ValueError("init_async_state needs an async plan")
        k = self.ecfg.n_nodes
        none = lambda x: x is None
        buf = {
            "shipped": jax.tree.map(
                lambda l: None if l is None else jnp.zeros_like(l),
                self._shipped_rows(trains), is_leaf=none),
            "gram": jnp.zeros((k, gram_side, gram_side), jnp.float32),
            "prec": jnp.zeros((k,), jnp.float32),
        }
        return {"ctl": part_mod.init_state(plan, k), "buf": buf}

    def _async_server(self, plan, trains, start, lag_draw, shipped, grams,
                      prec, buf, ctl, gbar, prev, server_m):
        """The async server step on FULL (K,)-row report arrays: fault
        injection, the on-device quarantine guard, the buffer write, the
        staleness-weighted delivery average, and the broadcast.  Shared
        by the single-host and shard_map round bodies — the sharded path
        gathers its per-shard reports into replicated full arrays first,
        so the server math (and therefore the oracle equivalence) is
        identical on both.

        A round with no deliveries (or all deliveries staled out) keeps
        the previous broadcast value, consensus Gram and FedAvgM momentum
        — the protocol idles rather than collapsing toward zero."""
        k = self.ecfg.n_nodes
        none = lambda x: x is None

        # fault injection: poison_nodes' uplink reports (NEVER their local
        # state) are corrupted to NaN — the guard below must catch them
        rows = [i for g in self._groups for i in g]
        if plan.poison_nodes:
            pm = jnp.asarray([1.0 if i in plan.poison_nodes else 0.0
                              for i in rows], jnp.float32)
            nanify = lambda l: l + jnp.where(
                pm.reshape((k,) + (1,) * (l.ndim - 1)) > 0,
                jnp.float32(jnp.nan), jnp.float32(0.0))
            shipped = jax.tree.map(
                lambda l: None if l is None else nanify(l),
                shipped, is_leaf=none)
            grams, prec = nanify(grams), nanify(prec)

        # quarantine guard, ON DEVICE, before anything enters the buffer:
        # non-finite anywhere in the report, or an exploded side-car norm
        finite = jnp.ones((k,), bool)
        norm_sq = jnp.zeros((k,), jnp.float32)
        for leaf in jax.tree.leaves(shipped):
            flat = leaf.reshape(k, -1)
            finite &= jnp.isfinite(flat).all(axis=1)
            norm_sq += (flat.astype(jnp.float32) ** 2).sum(axis=1)
        finite &= jnp.isfinite(grams.reshape(k, -1)).all(axis=1)
        finite &= jnp.isfinite(prec.reshape(k, -1)).all(axis=1)
        qn = jnp.float32(plan.quarantine_norm)
        bad = ((~finite) | (norm_sq > qn * qn)).astype(jnp.float32)
        ok = start * (1.0 - bad)
        ctl = dict(ctl, quarantined=ctl["quarantined"]
                   + (start * bad).astype(jnp.int32))

        # buffer write at the ACCEPTED rows only (a rejected reporter
        # stays idle and retries next round; its old buffer slot is inert
        # because its countdown was never armed)
        sel = lambda new, old: jnp.where(
            ok.reshape((k,) + (1,) * (new.ndim - 1)) > 0, new, old)
        buf = {
            "shipped": jax.tree.map(
                lambda n, o: None if n is None else sel(n, o),
                shipped, buf["shipped"], is_leaf=none),
            "gram": sel(grams.astype(jnp.float32), buf["gram"]),
            "prec": sel(prec.astype(jnp.float32), buf["prec"]),
        }
        countdown = jnp.where(ok > 0, lag_draw, ctl["countdown"])
        lag = jnp.where(ok > 0, lag_draw, ctl["lag"])

        # delivery: reports whose lag expires THIS round, weighted by
        # precision * staleness factor and normalised over the deliveries
        delivered = (countdown == 0).astype(jnp.float32)
        f = unc.staleness_factor(lag, plan.staleness,
                                 plan.staleness_alpha, plan.max_staleness)
        fresh = delivered * (f > 0.0).astype(jnp.float32)
        base = (buf["prec"] if self.ecfg.aggregation == "precision"
                else jnp.ones((k,), jnp.float32))
        wn = unc.stale_precision_weights(
            base, lag, delivered, plan.staleness, plan.staleness_alpha,
            plan.max_staleness)
        any_del = wn.sum() > 0.0
        total = agg.weighted_average_reports(buf["shipped"], wn)
        pick = lambda t, p_: jnp.where(any_del, t, p_)
        if server_m is None:
            new_val = jax.tree.map(pick, total, prev)
        else:
            m2, v2 = self._apply_server_momentum(prev, total, server_m)
            server_m = jax.tree.map(pick, m2, server_m)
            new_val = jax.tree.map(pick, v2, prev)
        trains = list(agg.broadcast_into_buckets(
            tuple(trains), self.shipped_masks, new_val))
        new_gbar = cka_mod.consensus_gram(buf["gram"], mask=fresh,
                                          fallback=gbar)
        countdown = jnp.where(delivered > 0, jnp.int32(-1),
                              jnp.where(countdown > 0, countdown - 1,
                                        countdown))
        ctl = dict(ctl, countdown=countdown, lag=lag)
        server_metrics = {
            "weights": wn,
            "delivered": delivered,
            "staleness": jnp.where(delivered > 0, lag,
                                   jnp.int32(-1)).astype(jnp.float32),
            "quarantined": ctl["quarantined"].astype(jnp.float32),
            "n_delivered": delivered.sum(),
            "cross_node_cka": cka_mod.mean_offdiag_cka(
                buf["gram"], center=self.ecfg.center_cka, mask=fresh),
        }
        return trains, new_gbar, server_m, {"ctl": ctl, "buf": buf}, \
            server_metrics

    def _round_async(self, plan, trains, opts, keys, gbar, server_m,
                     part_state, statics, batches):
        """One async round: the carried lag-and-failure simulator decides
        which idle nodes START local work this round; starters' state
        advances (masked path — non-starters carry through untouched) and
        their reports enter the carried buffer through the quarantine
        guard with a drawn delivery lag; the server aggregates exactly
        the reports whose lag expires this round, staleness-weighted."""
        k = self.ecfg.n_nodes
        prev = self._server_prev(trains)
        ctl, buf = part_state["ctl"], part_state["buf"]
        start, lag_draw, ctl = part_mod.async_events(plan, ctl)
        trains, opts, keys = list(trains), list(opts), list(keys)
        lasts, off = [], 0
        for b in range(self.n_buckets):
            kb = self.bucket_sizes[b]
            mb = start[off:off + kb]
            off += kb
            tr2, op2, ke2, last = self._local_epochs(
                trains[b], opts[b], keys[b], gbar, statics[b], batches[b])
            trains[b] = masked_select(mb, tr2, trains[b])
            opts[b] = masked_select(mb, op2, opts[b])
            keys[b] = masked_select(mb, ke2, keys[b])
            lasts.append(last)
        pooled = jnp.concatenate([l.pop("pooled") for l in lasts])
        pooled_a = jnp.concatenate([l.pop("pooled_a") for l in lasts])
        scalars = {name: jnp.concatenate([l[name] for l in lasts]) * start
                   for name in lasts[0]}
        grams = self._grams_of(pooled_a)
        if self.ecfg.aggregation == "precision":
            prec = unc.batched_precisions(pooled, pooled_a)
        else:
            prec = jnp.ones((k,), jnp.float32)
        shipped = self._shipped_rows(trains)
        trains, new_gbar, server_m, part_state, srv = self._async_server(
            plan, trains, start, lag_draw, shipped, grams, prec, buf,
            ctl, gbar, prev, server_m)
        metrics = {
            "scalars": {name: self._unpermute(v)
                        for name, v in scalars.items()},
            "weights": self._unpermute(srv["weights"]),
            "cross_node_cka": srv["cross_node_cka"],
            "participation": self._unpermute(start),
            "cohort_size": start.sum(),
            "delivered": self._unpermute(srv["delivered"]),
            "staleness": self._unpermute(srv["staleness"]),
            "quarantined": self._unpermute(srv["quarantined"]),
            "n_delivered": srv["n_delivered"],
        }
        return (tuple(trains), tuple(opts), tuple(keys), new_gbar,
                server_m, part_state, metrics)

    def _round_sharded_async(self, plan, trains, opts, keys, gbar,
                             server_m, part_state, statics, batches):
        """Async on the shard_map path.  The CTL arrays and the report
        buffer are REPLICATED (every shard draws the identical event
        stream from the shared key and runs the identical full-K server
        step — replication is maintained because the math is
        deterministic); only the local epochs and per-node report
        computation are sharded, then per-bucket all_gathers reassemble
        the full (K, ...) report arrays.  Buffer replication costs
        side-car-sized memory per shard — acceptable because only
        SHIPPED (low-rank) leaves are buffered."""
        ax = self._axes
        mesh_shape = dict(self.mesh.shape)
        node_spec = P(ax)
        batch_specs = tuple(P() if b is None else P(None, ax)
                            for b in batches)

        def inner(trains, opts, keys, gbar, server_m, part_state, statics,
                  batches):
            k = self.ecfg.n_nodes
            prev = self._server_prev(trains)
            ctl, buf = part_state["ctl"], part_state["buf"]
            start, lag_draw, ctl = part_mod.async_events(plan, ctl)
            shard = jnp.zeros((), jnp.int32)
            for a in ax:
                shard = shard * mesh_shape[a] + jax.lax.axis_index(a)
            trains, opts, keys = list(trains), list(opts), list(keys)
            lasts, off = [], 0
            for b in range(self.n_buckets):
                kb = self.bucket_sizes[b]
                kb_l = keys[b].shape[0]
                sb = start[off:off + kb]
                off += kb
                mb = jax.lax.dynamic_slice(sb, (shard * kb_l,), (kb_l,))
                tr2, op2, ke2, last = self._local_epochs(
                    trains[b], opts[b], keys[b], gbar, statics[b],
                    batches[b])
                trains[b] = masked_select(mb, tr2, trains[b])
                opts[b] = masked_select(mb, op2, opts[b])
                keys[b] = masked_select(mb, ke2, keys[b])
                lasts.append(last)
            pooled = jnp.concatenate([l.pop("pooled") for l in lasts])
            pooled_a = jnp.concatenate([l.pop("pooled_a") for l in lasts])
            kb_loc = tuple(ks.shape[0] for ks in keys)
            k_loc = sum(kb_loc)

            grams_loc = self._grams_of(pooled_a)
            if self.ecfg.aggregation == "precision":
                prec_loc = unc.batched_precisions(pooled, pooled_a)
            else:
                prec_loc = jnp.ones((k_loc,), jnp.float32)
            shipped_loc = self._shipped_rows(trains)

            gather = functools.partial(jax.lax.all_gather, axis_name=ax,
                                       axis=0, tiled=True)

            def gather_cat(v_loc):
                off2, parts = 0, []
                for kbl in kb_loc:
                    parts.append(gather(v_loc[off2:off2 + kbl]))
                    off2 += kbl
                return jnp.concatenate(parts)

            none = lambda x: x is None
            shipped = jax.tree.map(
                lambda l: None if l is None else gather_cat(l),
                shipped_loc, is_leaf=none)
            grams = gather_cat(grams_loc)
            prec = gather_cat(prec_loc)
            scalars = {name: gather_cat(jnp.concatenate(
                [l[name] for l in lasts])) * start for name in lasts[0]}

            trains, new_gbar, server_m, part_state, srv = \
                self._async_server(plan, trains, start, lag_draw, shipped,
                                   grams, prec, buf, ctl, gbar, prev,
                                   server_m)
            metrics = {
                "scalars": {name: self._unpermute(v)
                            for name, v in scalars.items()},
                "weights": self._unpermute(srv["weights"]),
                "cross_node_cka": srv["cross_node_cka"],
                "participation": self._unpermute(start),
                "cohort_size": start.sum(),
                "delivered": self._unpermute(srv["delivered"]),
                "staleness": self._unpermute(srv["staleness"]),
                "quarantined": self._unpermute(srv["quarantined"]),
                "n_delivered": srv["n_delivered"],
            }
            return (tuple(trains), tuple(opts), tuple(keys), new_gbar,
                    server_m, part_state, metrics)

        return _shard_map(
            inner, mesh=self.mesh,
            in_specs=(node_spec, node_spec, node_spec, P(), P(), P(),
                      node_spec, batch_specs),
            out_specs=(node_spec, node_spec, node_spec, P(), P(), P(),
                       P()),
        )(trains, opts, keys, gbar, server_m, part_state, statics, batches)

    # ------------------------------------------------------------------
    def _round_sharded(self, trains, opts, keys, gbar, server_m, statics,
                       batches):
        """shard_map path: each bucket's node axis split over the mesh
        batch axes; the server step's cross-slice traffic is exactly the
        protocol's uplink (Grams + precisions + shipped side-cars)."""
        ax = self._axes
        k = self.ecfg.n_nodes
        node_spec = P(ax)
        batch_specs = tuple(P() if b is None else P(None, ax)
                            for b in batches)

        def inner(trains, opts, keys, gbar, server_m, statics, batches):
            prev = None if server_m is None else self._server_prev(trains)
            trains, opts, keys = list(trains), list(opts), list(keys)
            lasts = []
            for b in range(self.n_buckets):
                trains[b], opts[b], keys[b], last = self._local_epochs(
                    trains[b], opts[b], keys[b], gbar,
                    statics[b], batches[b])
                lasts.append(last)
            pooled = jnp.concatenate([l.pop("pooled") for l in lasts])
            pooled_a = jnp.concatenate([l.pop("pooled_a") for l in lasts])
            scalars = {name: jnp.concatenate([l[name] for l in lasts])
                       for name in lasts[0]}
            kb_loc = tuple(ks.shape[0] for ks in keys)
            k_loc = sum(kb_loc)

            grams_loc = self._grams_of(pooled_a)
            new_gbar = jax.lax.psum(grams_loc.sum(0), ax) / k
            if self.ecfg.aggregation == "precision":
                p_loc = jnp.maximum(
                    unc.batched_precisions(pooled, pooled_a), 0.0)
                w_loc = p_loc / jnp.maximum(
                    jax.lax.psum(p_loc.sum(), ax), 1e-12)
            else:
                w_loc = jnp.full((k_loc,), 1.0 / k, jnp.float32)

            # shipped average: per-bucket local partial sums -> one psum ->
            # broadcast (the unsharded server math with a psum in between)
            total = agg.bucketed_partial_sums(
                tuple(trains), w_loc, self.shipped_masks, kb_loc)
            total = jax.tree.map(
                lambda a: None if a is None else jax.lax.psum(a, ax),
                total, is_leaf=lambda x: x is None)
            if server_m is not None:
                # prev and total are replicated here, so the momentum
                # update needs no extra collective
                server_m, total = self._apply_server_momentum(
                    prev, total, server_m)
            trains = list(agg.broadcast_into_buckets(
                tuple(trains), self.shipped_masks, total))

            # gather per BUCKET (each reassembles that bucket's node order),
            # then concatenate — gathering the locally-concatenated array
            # would interleave shard-major instead of bucket-major
            gather = functools.partial(jax.lax.all_gather, axis_name=ax,
                                       axis=0, tiled=True)

            def gather_cat(v_loc):
                off, parts = 0, []
                for kb in kb_loc:
                    parts.append(gather(v_loc[off:off + kb]))
                    off += kb
                return jnp.concatenate(parts)

            grams_all = gather(grams_loc)   # order-invariant consumer
            metrics = {
                "scalars": {name: self._unpermute(gather_cat(v))
                            for name, v in scalars.items()},
                "weights": self._unpermute(gather_cat(w_loc)),
                "cross_node_cka": cka_mod.mean_offdiag_cka(
                    grams_all, center=self.ecfg.center_cka),
            }
            return (tuple(trains), tuple(opts), tuple(keys), new_gbar,
                    server_m, metrics)

        return _shard_map(
            inner, mesh=self.mesh,
            in_specs=(node_spec, node_spec, node_spec, P(), P(), node_spec,
                      batch_specs),
            out_specs=(node_spec, node_spec, node_spec, P(), P(), P()),
        )(trains, opts, keys, gbar, server_m, statics, batches)

    def _round_sharded_part(self, plan, trains, opts, keys, gbar, server_m,
                            part_state, statics, batches):
        """Participation on the shard_map path.  The sampler state is
        REPLICATED, so every shard draws the identical full-federation
        cohort and slices out its own rows (the shard's linearised index
        over the mesh batch axes); execution is always the masked path —
        cross-shard gather-compaction would need a resharding collective
        that costs more than the masked compute it saves.  The server
        collectives are the legacy psums with mask-aware normalisation."""
        ax = self._axes
        mesh_shape = dict(self.mesh.shape)
        node_spec = P(ax)
        batch_specs = tuple(P() if b is None else P(None, ax)
                            for b in batches)

        def inner(trains, opts, keys, gbar, server_m, part_state, statics,
                  batches):
            prev = None if server_m is None else self._server_prev(trains)
            row_masks, _, part_state = part_mod.sample_rows(
                plan, part_state, self._groups)
            mask_full = jnp.concatenate(row_masks)       # replicated (K,)
            shard = jnp.zeros((), jnp.int32)
            for a in ax:
                shard = shard * mesh_shape[a] + jax.lax.axis_index(a)
            trains, opts, keys = list(trains), list(opts), list(keys)
            lasts, masks_loc = [], []
            for b in range(self.n_buckets):
                kb_loc = keys[b].shape[0]
                mb = jax.lax.dynamic_slice(row_masks[b],
                                           (shard * kb_loc,), (kb_loc,))
                tr2, op2, ke2, last = self._local_epochs(
                    trains[b], opts[b], keys[b], gbar, statics[b],
                    batches[b])
                trains[b] = masked_select(mb, tr2, trains[b])
                opts[b] = masked_select(mb, op2, opts[b])
                keys[b] = masked_select(mb, ke2, keys[b])
                lasts.append(last)
                masks_loc.append(mb)
            pooled = jnp.concatenate([l.pop("pooled") for l in lasts])
            pooled_a = jnp.concatenate([l.pop("pooled_a") for l in lasts])
            scalars = {name: jnp.concatenate([l[name] for l in lasts])
                       for name in lasts[0]}
            m_loc = jnp.concatenate(masks_loc)
            kb_loc = tuple(ks.shape[0] for ks in keys)

            grams_loc = self._grams_of(pooled_a)
            g_num = jax.lax.psum(
                (m_loc[:, None, None] * grams_loc).sum(0), ax)
            new_gbar = g_num / jnp.maximum(jax.lax.psum(m_loc.sum(), ax),
                                           1.0)
            p_loc = None
            if (self.ecfg.aggregation == "precision"
                    or plan.strategy == "precision"):
                p_loc = jnp.maximum(
                    unc.batched_precisions(pooled, pooled_a), 0.0)
            if self.ecfg.aggregation == "precision":
                w_loc = m_loc * p_loc / jnp.maximum(
                    jax.lax.psum((m_loc * p_loc).sum(), ax), 1e-12)
            else:
                w_loc = m_loc / jnp.maximum(
                    jax.lax.psum(m_loc.sum(), ax), 1.0)

            total = agg.bucketed_partial_sums(
                tuple(trains), w_loc, self.shipped_masks, kb_loc)
            total = jax.tree.map(
                lambda a_: None if a_ is None else jax.lax.psum(a_, ax),
                total, is_leaf=lambda x: x is None)
            if server_m is not None:
                server_m, total = self._apply_server_momentum(
                    prev, total, server_m)
            trains = list(agg.broadcast_into_buckets(
                tuple(trains), self.shipped_masks, total))

            gather = functools.partial(jax.lax.all_gather, axis_name=ax,
                                       axis=0, tiled=True)

            def gather_cat(v_loc):
                off, parts = 0, []
                for kb in kb_loc:
                    parts.append(gather(v_loc[off:off + kb]))
                    off += kb
                return jnp.concatenate(parts)

            # per-bucket gather keeps grams aligned with the bucket-major
            # replicated mask (a plain shard-major gather would mispair)
            grams_all = gather_cat(grams_loc)
            if p_loc is not None:
                part_state = part_mod.update_state(
                    plan, part_state, mask_full, gather_cat(p_loc))
            metrics = {
                "scalars": {name: self._unpermute(gather_cat(v) * mask_full)
                            for name, v in scalars.items()},
                "weights": self._unpermute(gather_cat(w_loc)),
                "cross_node_cka": cka_mod.mean_offdiag_cka(
                    grams_all, center=self.ecfg.center_cka,
                    mask=mask_full),
                "participation": self._unpermute(mask_full),
                "cohort_size": mask_full.sum(),
            }
            return (tuple(trains), tuple(opts), tuple(keys), new_gbar,
                    server_m, part_state, metrics)

        return _shard_map(
            inner, mesh=self.mesh,
            in_specs=(node_spec, node_spec, node_spec, P(), P(), P(),
                      node_spec, batch_specs),
            out_specs=(node_spec, node_spec, node_spec, P(), P(), P(),
                       P()),
        )(trains, opts, keys, gbar, server_m, part_state, statics, batches)

    # ------------------------------------------------------------------
    def part_round_fn(self, plan):
        """Compiled participation-aware round for ``plan`` (cached per
        plan; plans are frozen/hashable).  Signature adds the sampler
        state: ``(trains, opts, keys, gbar, server_m, part_state, statics,
        batches) -> (..., part_state, metrics)``; the round-state buffers
        INCLUDING the sampler state are donated."""
        plan = part_mod.normalize(plan)
        if plan is None:
            raise ValueError("full participation is the legacy round_fn")
        fn = self._part_cache.get(plan)
        if fn is not None:
            return fn
        if plan.strategy == "async":
            body = (self._round_async if self.mesh is None
                    else self._round_sharded_async)
        else:
            body = (self._round_part if self.mesh is None
                    else self._round_sharded_part)
        donate = (0, 1, 2, 3, 4, 5) if self.ecfg.donate else ()
        fn = jax.jit(functools.partial(body, plan), donate_argnums=donate)
        self._part_cache[plan] = fn
        return fn

    # ------------------------------------------------------------------
    # fused multi-round blocks: lax.scan over M whole rounds, one dispatch
    def block_fn(self, m: int, *, tap=None, plan=None, state_tap=None,
                 state_tap_every: int = 0):
        """Compiled M-round block: ``jax.lax.scan`` over the round body with
        the (trains, opts, keys, gbar, server_m) carry DONATED, so M rounds
        cost one dispatch and zero intermediate host syncs.  ``tap`` is an
        optional host callback fired once per round (via ``io_callback``,
        ordered) with that round's metrics — an async log stream that never
        blocks the device.  Compiled functions are cached per
        (m, has-tap, plan, has-state-tap, every): the taps route through
        holders read at callback time, so passing a fresh closure per call
        swaps the target without re-tracing the M-round scan (the LATEST
        tap handles any still-in-flight blocks; ``jax.effects_barrier()``
        drains pending callbacks before swapping if that matters).  Scan
        traces the round body once, so compile time is ~independent of M.

        ``state_tap`` is the IN-BLOCK CHECKPOINT tap: a host callback
        ``state_tap(abs_round, carry)`` fired every ``state_tap_every``
        rounds FROM INSIDE the scan (unordered ``io_callback`` under a
        ``lax.cond``), so preemption during a long fused block loses
        < state_tap_every rounds instead of the whole block.  When armed,
        the compiled block takes one extra TRAILING scalar argument — the
        absolute round offset of the block — so in-flight blocks carry
        their own base round and the holder-swap pattern stays valid.
        Host-side exceptions in either tap are logged and dropped
        (``_safe_tap``) — a full disk never kills the in-flight block."""
        if m < 1:
            raise ValueError(f"block size must be >= 1, got {m}")
        if state_tap is not None and not 1 <= state_tap_every <= m:
            raise ValueError(f"state_tap_every {state_tap_every} outside "
                             f"[1, {m}]")
        plan = part_mod.normalize(plan)
        cache_key = (m, tap is not None, plan, state_tap is not None,
                     state_tap_every if state_tap is not None else 0)
        if tap is not None:
            self._tap_holders.setdefault(cache_key, [None])[0] = tap
        if state_tap is not None:
            self._tap_holders.setdefault(("state",) + cache_key,
                                         [None])[0] = state_tap
        fn = self._block_cache.get(cache_key)
        if fn is not None:
            return fn
        holder = self._tap_holders.get(cache_key)
        sholder = self._tap_holders.get(("state",) + cache_key)
        # the tap is ORDERED on a single host (log lines arrive in round
        # order) but UNORDERED on a mesh, so per-host callback delivery
        # never serialises the pods (ROADMAP item); each payload carries
        # its ``round_in_block`` index so consumers can reassemble order.
        ordered_tap = self.mesh is None

        def fire_tap(metrics, ridx):
            if holder is None:
                return
            from jax.experimental import io_callback
            io_callback(
                lambda i, metr: _safe_tap(
                    holder[0], dict(metr, round_in_block=int(i))),
                None, ridx, metrics, ordered=ordered_tap)

        def fire_state_tap(carry, ridx, r0):
            # unordered io_callback is legal under lax.cond (ordered is
            # not), and checkpoint writes are self-describing (each
            # payload carries its absolute round), so ordering is free
            if sholder is None:
                return
            from jax.experimental import io_callback
            every = state_tap_every

            def fire(c):
                io_callback(
                    lambda r_, c_: _safe_tap(sholder[0], int(r_), c_),
                    None, r0 + ridx + 1, c, ordered=False)
                return jnp.int32(0)

            jax.lax.cond((ridx + 1) % every == 0,
                         fire, lambda c: jnp.int32(0), carry)

        if plan is None:
            body_fn = (self._round if self.mesh is None
                       else self._round_sharded)

            def block(trains, opts, keys, gbar, server_m, statics,
                      batches, *r0):
                def body(carry, xs):
                    ridx, bt = xs
                    tr, op, ks, gb, sm = carry
                    tr, op, ks, gb, sm, metrics = body_fn(
                        tr, op, ks, gb, sm, statics, bt)
                    fire_tap(metrics, ridx)
                    fire_state_tap((tr, op, ks, gb, sm), ridx,
                                   r0[0] if r0 else 0)
                    return (tr, op, ks, gb, sm), metrics

                # per-bucket batches carry leading (M, E, k_b, ...) axes
                # and are scanned over; None buckets sample on-device from
                # the carried RNG keys.  The stacked ys ARE the (M, ...)
                # metric buffers.
                (trains, opts, keys, gbar, server_m), metrics = \
                    jax.lax.scan(body, (trains, opts, keys, gbar,
                                        server_m),
                                 (jnp.arange(m), batches), length=m)
                return trains, opts, keys, gbar, server_m, metrics

            donate = (0, 1, 2, 3, 4) if self.ecfg.donate else ()
        else:
            if plan.strategy == "async":
                part_body = (self._round_async if self.mesh is None
                             else self._round_sharded_async)
            else:
                part_body = (self._round_part if self.mesh is None
                             else self._round_sharded_part)

            def block(trains, opts, keys, gbar, server_m, part_state,
                      statics, batches, *r0):
                def body(carry, xs):
                    ridx, bt = xs
                    tr, op, ks, gb, sm, ps = carry
                    tr, op, ks, gb, sm, ps, metrics = part_body(
                        plan, tr, op, ks, gb, sm, ps, statics, bt)
                    fire_tap(metrics, ridx)
                    fire_state_tap((tr, op, ks, gb, sm, ps), ridx,
                                   r0[0] if r0 else 0)
                    return (tr, op, ks, gb, sm, ps), metrics

                (trains, opts, keys, gbar, server_m, part_state), \
                    metrics = jax.lax.scan(
                        body, (trains, opts, keys, gbar, server_m,
                               part_state),
                        (jnp.arange(m), batches), length=m)
                return (trains, opts, keys, gbar, server_m, part_state,
                        metrics)

            donate = (0, 1, 2, 3, 4, 5) if self.ecfg.donate else ()
        fn = jax.jit(block, donate_argnums=donate)
        self._block_cache[cache_key] = fn
        return fn

    def run_block(self, state, m: int, *, statics, batches=None, tap=None,
                  plan=None, state_tap=None, state_tap_every: int = 0,
                  round_offset: int = 0):
        """Run M fused rounds in ONE donated dispatch.

        ``state`` is the round carry ``(trains, opts, keys, gbar,
        server_m)`` — plus the participation sampler state as a sixth
        element when ``plan`` is given; ``batches`` is a per-bucket tuple
        of either ``None`` (draw on-device from the carried RNG stream) or
        a pytree with leading ``(M, E, k_b, ...)`` axes pre-staged on
        device.  Returns ``(state, metrics)`` where every metrics leaf
        gained a leading M axis (round-major).  The call is ASYNC: nothing
        blocks until the caller materialises an output, so drivers can
        stage block N+1's batches while block N is in flight.

        ``state_tap``/``state_tap_every``/``round_offset`` arm the
        in-block checkpoint tap (see ``block_fn``): ``state_tap(abs_round,
        carry)`` fires from inside the scan every ``state_tap_every``
        rounds, with ``abs_round = round_offset + rounds completed``."""
        if batches is None:
            batches = (None,) * self.n_buckets
        plan = part_mod.normalize(plan)
        n_state = 5 if plan is None else 6
        fn = self.block_fn(m, tap=tap, plan=plan, state_tap=state_tap,
                           state_tap_every=state_tap_every)
        args = (*state, statics, batches)
        if state_tap is not None:
            args = args + (jnp.int32(round_offset),)
        out = fn(*args)
        return out[:n_state], out[n_state]


def _shard_map(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off: the round
    bodies mix per-shard and replicated values that the check cannot
    type."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
