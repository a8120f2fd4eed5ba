"""Federated rounds for unpaired multimodal data — the paper's protocol.

Per round, each node k (one modality each, strictly private data):
  1. runs local AdamW steps on  L_task + lambda * (1 - CKA(G_k, G_bar))
     (Eq. 3), where only the GeoLoRA ``lora_B`` / GeoDoRA ``dora_m`` /
     shared-head params and the LOCAL adapter W_mk are trainable;
     under GeoDoRA the geometric loss sees ``stop_gradient(dora_m)`` so it
     constrains *direction only* (paper: "R_geo applied exclusively to D");
  2. computes its public-anchor Gram matrix G_k (Eq. 1) and its LAP
     precision p_k (Eq. 6) — the ONLY things uploaded besides the side-cars;
  3. the server averages Grams into G_bar, computes precision weights, and
     precision-weight-averages the shipped side-cars (Eqs. 4-5), then
     broadcasts.

Adapters W_mk never leave the node; the frozen base theta is never
communicated after initialisation.  Communication per round is measured and
compared against full-model FedAvg in the benchmarks (paper claim: >99.9%
reduction).

Execution engine
----------------
Two implementations share one substrate:

``SequentialFederation`` — the readable reference: a Python loop over nodes
and local steps, one jit dispatch per node per step (K x E per round).
Kept as the oracle for the engine-equivalence tests and benchmarks.

``Federation`` — the node-stacked engine (``repro.core.engine``), the
default.  Architecture:

  * **node axis**: per-node trainables, optimizer states and RNG keys are
    stacked along a leading axis; ``jax.vmap`` maps the local step across
    it and ``jax.lax.scan`` runs the E local steps.
  * **width bucketing** (the heterogeneous-width strategy): per-modality
    tokenizer widths differ per node (text 2048 .. tabular 192), and the
    paper's regime makes that the COMMON case.  Nodes are grouped by
    adapter width into W buckets; each bucket stacks only the nodes whose
    widths match (zero-padded to the bucket width — for a bridge node,
    the max of its two adapters' widths), so a narrow tabular node never
    pays the quadratic w^2 tokenizer/adapter compute of the text bucket.
    Zero padding WITHIN a bucket stays exact: padded token channels are
    zero, so padded adapter rows receive zero gradients and stay zero
    under AdamW (no weight decay) — each bucket's program is numerically
    equivalent to the ragged one.  Bucket membership is static, so the W
    per-bucket sub-programs are stitched at trace time and the round
    stays ONE jit dispatch; the server step runs once on the
    bucket-concatenated pooled activations and the engine returns metrics
    in canonical node order (the stable node->bucket permutation is
    engine state, invisible to callers).  ``width_bucketing=False``
    restores the legacy single-bucket pad-to-max-width layout (the
    benchmark baseline).
  * **heterogeneous node types** (corrupt / bridge / synthetic-anchor)
    are static branch masks: both data branches are computed from the
    same RNG keys and selected per node, and the bridge contrastive term
    is weighted by a 0/1 mask, so ONE compiled program serves every node
    type.
  * **round compilation boundary**: local epochs + Gram upload + LAP
    precision + consensus + precision-weighted side-car averaging +
    broadcast are one jitted call — K x E dispatches per round become 1.
    Round-state buffers (stacked trainables / opt states / keys / G_bar)
    are DONATED to the compiled round, so round N's outputs alias round
    N+1's inputs and peak round-state memory stays ~1x instead of 2x.
  * **mesh path**: with ``mesh=...`` each bucket's node axis is
    ``shard_map``-ped onto the mesh batch axes (``launch.mesh.batch_axes``;
    every bucket size must divide the shard count); the server step
    becomes psum/all_gather collectives whose payload is the protocol's
    actual uplink (Grams, precisions, shipped side-cars).
"""
from __future__ import annotations

import functools
import zlib
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig, get_config
from repro.core import aggregation as agg
from repro.core import cka as cka_mod
from repro.core import engine as engine_mod
from repro.core import lora as lora_mod
from repro.core import participation as part_mod
from repro.core import uncertainty as unc
from repro.core.participation import ParticipationPlan  # re-export
from repro.data.synthetic import SyntheticMultimodal
from repro.data.tokenizers import FrozenTokenizer, default_tokenizers
from repro.models import transformer as T
from repro.models.common import cross_entropy_loss, linear, make_linear
from repro.optim.adamw import AdamW

Array = jax.Array


@dataclass(frozen=True)
class FederationConfig:
    n_nodes: int = 4
    modalities: Tuple[str, ...] = ("image", "text", "genetics", "tabular")
    method: str = "geolora"            # geolora | geodora | fedavg_full
    aggregation: str = "precision"     # precision | uniform
    lora_rank: int = 8
    lambda_geo: float = 1.0
    rounds: int = 5
    local_steps: int = 10
    local_batch: int = 32
    lr: float = 3e-3
    n_classes: int = 8
    anchors_per_class: int = 4
    n_tokens: int = 16
    corrupt_nodes: Tuple[int, ...] = ()
    # bridge clients (paper's hybrid federation): nodes holding locally
    # PAIRED data across two modalities add an intra-node contrastive loss,
    # rigidifying the global manifold alignment.
    bridge_nodes: Tuple[int, ...] = ()
    bridge_modality: str = "text"            # second modality on bridges
    lambda_bridge: float = 0.5
    # nodes whose anchor modality is MISSING from the public set and is
    # replaced by noisy synthetic anchors (digital twins); the paper claims
    # LAP naturally downweights them via the distributional shift.
    synthetic_anchor_nodes: Tuple[int, ...] = ()
    synthetic_anchor_noise: float = 2.0
    seed: int = 0
    center_cka: bool = False
    # server-side FedOpt: momentum on the precision-weighted side-car
    # average (engine-backed ``Federation`` only).  ``None`` = off (exact
    # legacy server step); 0.0 carries the state but reduces to the plain
    # average; > 0 accumulates the round pseudo-gradient.
    server_momentum: Optional[float] = None
    # global-round LR schedule (round index -> multiplier), threaded
    # through the engine's scan carry via the optimizer's "round" counter:
    # warmup/cosine ACROSS fused round blocks without re-jitting.  ``None``
    # keeps the exact legacy optimizer state structure.
    round_lr_schedule: Optional[Callable] = None


def _stopgrad_named(tree, names=("dora_m",)):
    def walk(node, name):
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v, name) for v in node)
        if node is None:
            return None
        return jax.lax.stop_gradient(node) if name in names else node
    return walk(tree, "")


# shipped/local split lives in repro.core.lora (shared with the engine)
_shipped_mask = lora_mod.shipped_mask


def _split_by_mask(tree, mask):
    a = jax.tree.map(lambda p, m: p if (p is not None and m) else None,
                     tree, mask, is_leaf=lambda x: x is None)
    b = jax.tree.map(lambda p, m: p if (p is not None and not m) else None,
                     tree, mask, is_leaf=lambda x: x is None)
    return a, b


def _merge_by_mask(shipped, local, mask):
    return jax.tree.map(
        lambda m, s, l: s if m else l, mask, shipped, local,
        is_leaf=lambda x: x is None)


class SequentialFederation:
    """Simulated federation (K nodes on one host), sequential reference:
    Python loop over nodes, one jit dispatch per node per local step.  The
    node-stacked single-dispatch engine is ``Federation``; this class is
    the oracle it is equivalence-tested against."""

    def __init__(self, fed: FederationConfig, model: ModelConfig = None):
        self.fed = fed
        self.cfg = model or get_config("fedmm-small")
        key = jax.random.PRNGKey(fed.seed)
        k_model, k_data, k_anchor, k_lora, k_nodes = jax.random.split(key, 5)

        # ---- substrate: task, tokenizers, anchors ----
        from repro.configs.fedmm_base import MODALITY_TOKENIZER_DIMS
        self.task = SyntheticMultimodal(n_classes=fed.n_classes,
                                        modalities=fed.modalities,
                                        seed=fed.seed)
        self.tokenizers = default_tokenizers(
            {m: MODALITY_TOKENIZER_DIMS[m] for m in fed.modalities},
            self.task.d_raw, fed.n_tokens, seed=fed.seed)
        anchors_raw = self.task.anchor_set(k_anchor, fed.anchors_per_class)
        # pre-tokenize public anchors once per modality (tokenizers frozen)
        self.anchor_tokens = {m: self.tokenizers[m](anchors_raw[m][0])
                              for m in fed.modalities}
        # synthetic (generated) anchors: same class structure, heavy noise
        self.synthetic_anchor_tokens = {}
        if fed.synthetic_anchor_nodes:
            kn = jax.random.fold_in(k_anchor, 777)
            for m, (raw, _) in anchors_raw.items():
                noisy = raw + fed.synthetic_anchor_noise * \
                    jax.random.normal(jax.random.fold_in(
                        kn, zlib.crc32(m.encode()) % (2 ** 31)), raw.shape)
                self.synthetic_anchor_tokens[m] = self.tokenizers[m](noisy)

        # ---- global model (the paper's VLM-initialised homogeneous
        # transformer; random init here — protocol math is init-agnostic) ----
        params = T.init_params(k_model, self.cfg)
        if fed.method in ("geolora", "geodora"):
            spec = lora_mod.LoRASpec(rank=fed.lora_rank,
                                     dora=(fed.method == "geodora"))
            params = lora_mod.attach_lora(k_lora, params, spec)
        kh = jax.random.fold_in(k_model, 99)
        params["cls_head"] = make_linear(kh, self.cfg.d_model, fed.n_classes,
                                         jnp.float32)

        if fed.method == "fedavg_full":
            mask = jax.tree.map(lambda _: True, params)
        else:
            mask = lora_mod.trainable_mask(params)
        self.mask = mask
        trainable, self.frozen = lora_mod.partition(params, mask)

        # ---- per-node state: shared trainables + local adapter ----
        self.node_modality = [fed.modalities[i % len(fed.modalities)]
                              for i in range(fed.n_nodes)]
        self.opt = AdamW(lr=fed.lr, weight_decay=0.0, grad_clip=1.0,
                         round_schedule=fed.round_lr_schedule)
        self.nodes = []
        for i in range(fed.n_nodes):
            m = self.node_modality[i]
            ka = jax.random.fold_in(k_nodes, i)
            node_train = dict(trainable)
            node_train["adapter"] = make_linear(
                ka, self.tokenizers[m].d_out, self.cfg.d_model, jnp.float32)
            self.nodes.append({
                "trainable": node_train,
                "opt_state": self.opt.init(node_train),
                "modality": m,
                "corrupt": i in fed.corrupt_nodes,
                "bridge": i in fed.bridge_nodes,
                "key": jax.random.fold_in(k_data, i),
            })
        # bridge clients get a second local adapter for the paired modality
        for node in self.nodes:
            if node["bridge"]:
                m2 = fed.bridge_modality
                if m2 == node["modality"]:
                    m2 = next(m for m in fed.modalities
                              if m != node["modality"])
                node["modality2"] = m2
                ka2 = jax.random.fold_in(k_nodes, 1000 + self.nodes.index(node))
                node["trainable"]["adapter2"] = make_linear(
                    ka2, self.tokenizers[m2].d_out, self.cfg.d_model,
                    jnp.float32)
                node["opt_state"] = self.opt.init(node["trainable"])
        # frozen tree needs structure-matching adapter placeholders
        self.frozen = dict(self.frozen)
        self.frozen["adapter"] = {"w": None}
        self.mask = dict(self.mask)
        self.mask["adapter"] = {"w": True}
        if any(n.get("bridge") for n in self.nodes):
            self.frozen_bridge = dict(self.frozen, adapter2={"w": None})
        else:
            self.frozen_bridge = None

        self.gbar = self._initial_consensus()
        self.history: List[dict] = []

    # ------------------------------------------------------------------
    def _pooled(self, params, tokens) -> Array:
        embeds = linear(tokens.astype(jnp.float32), params["adapter"])
        _, aux = T.forward(params, {"inputs_embeds": embeds}, self.cfg)
        return aux["pooled"]

    def _frozen_for(self, node) -> dict:
        return self.frozen_bridge if node.get("bridge") else self.frozen

    def _initial_consensus(self) -> Array:
        grams = []
        for node in self.nodes:
            params = lora_mod.combine(node["trainable"],
                                      self._frozen_for(node))
            pooled = self._pooled(params, self.anchor_tokens[node["modality"]])
            grams.append(cka_mod.cosine_gram(pooled))
        return cka_mod.consensus_gram(jnp.stack(grams))

    # ------------------------------------------------------------------
    @staticmethod
    def _contrastive(z1: Array, z2: Array, tau: float = 0.2) -> Array:
        """Intra-node InfoNCE on locally PAIRED samples (bridge clients)."""
        z1 = z1 / jnp.maximum(jnp.linalg.norm(z1, axis=-1, keepdims=True),
                              1e-8)
        z2 = z2 / jnp.maximum(jnp.linalg.norm(z2, axis=-1, keepdims=True),
                              1e-8)
        sim = (z1 @ z2.T) / tau
        labels = jnp.arange(z1.shape[0])
        return 0.5 * (cross_entropy_loss(sim, labels)
                      + cross_entropy_loss(sim.T, labels))

    @functools.partial(jax.jit, static_argnums=(0,))
    def _local_step(self, trainable, opt_state, frozen, batch_tokens, labels,
                    anchor_tokens, gbar):
        lam = self.fed.lambda_geo

        def loss_fn(train):
            params = lora_mod.combine(train, frozen)
            pooled = self._pooled(params, batch_tokens)
            logits = linear(pooled, params["cls_head"])
            task = cross_entropy_loss(logits, labels)
            # GeoDoRA: geometric loss constrains direction only
            params_geo = lora_mod.combine(_stopgrad_named(train), frozen)
            pooled_a = self._pooled(params_geo, anchor_tokens)
            geo = cka_mod.geo_alignment_loss(pooled_a, gbar,
                                             center=self.fed.center_cka)
            acc = (logits.argmax(-1) == labels).mean()
            return task + lam * geo, (task, geo, acc, pooled, pooled_a)

        grads, (task, geo, acc, pooled, pooled_a) = \
            jax.grad(loss_fn, has_aux=True)(trainable)
        new_train, new_opt = self.opt.update(grads, opt_state, trainable)
        return new_train, new_opt, {"task": task, "geo": geo, "acc": acc,
                                    "pooled": pooled, "pooled_a": pooled_a}

    @functools.partial(jax.jit, static_argnums=(0,))
    def _bridge_step(self, trainable, opt_state, frozen, batch_tokens,
                     batch_tokens2, labels, anchor_tokens, gbar):
        """Local step on a bridge client: task + geo + paired contrastive
        between the two local modalities (paper: 'bridge clients ...
        rigidify the global manifold alignment')."""
        lam, lam_b = self.fed.lambda_geo, self.fed.lambda_bridge

        def loss_fn(train):
            params = lora_mod.combine(train, frozen)
            pooled = self._pooled(params, batch_tokens)
            params2 = dict(params, adapter=params["adapter2"])
            pooled2 = self._pooled(params2, batch_tokens2)
            logits = linear(pooled, params["cls_head"])
            task = cross_entropy_loss(logits, labels)
            contrast = self._contrastive(pooled, pooled2)
            params_geo = lora_mod.combine(_stopgrad_named(train), frozen)
            pooled_a = self._pooled(params_geo, anchor_tokens)
            geo = cka_mod.geo_alignment_loss(pooled_a, gbar,
                                             center=self.fed.center_cka)
            acc = (logits.argmax(-1) == labels).mean()
            return task + lam * geo + lam_b * contrast, \
                (task, geo, acc, pooled, pooled_a)

        grads, (task, geo, acc, pooled, pooled_a) = \
            jax.grad(loss_fn, has_aux=True)(trainable)
        new_train, new_opt = self.opt.update(grads, opt_state, trainable)
        return new_train, new_opt, {"task": task, "geo": geo, "acc": acc,
                                    "pooled": pooled, "pooled_a": pooled_a}

    # ------------------------------------------------------------------
    def run_round(self, participants=None) -> dict:
        """One protocol round.  ``participants`` (an iterable of node ids)
        restricts the round to a reporting cohort: non-participants do
        NOTHING — their trainables, optimizer moments and RNG keys carry
        through untouched, they contribute nothing to the consensus Gram /
        LAP precision pool / side-car average, and they still receive the
        server broadcast at round end (next-round downlink).  ``None`` is
        the exact legacy full-participation round."""
        fed = self.fed
        active = (None if participants is None else set(participants))
        k_active = fed.n_nodes if active is None else len(active)
        if active is not None and k_active == 0:
            raise ValueError("empty participant set")
        grams, precisions, shipped_list = [], [], []
        metrics = {"task": [], "geo": [], "acc": []}
        self._last_raw_precisions = {}
        for i, node in enumerate(self.nodes):
            if active is not None and i not in active:
                continue
            if "round" in node["opt_state"]:
                node["opt_state"] = dict(
                    node["opt_state"],
                    round=node["opt_state"]["round"] + 1)
            m = node["modality"]
            anchors = (self.synthetic_anchor_tokens[m]
                       if i in fed.synthetic_anchor_nodes
                       else self.anchor_tokens[m])
            last = None
            for s in range(fed.local_steps):
                node["key"], kb = jax.random.split(node["key"])
                raw, labels = self.task.sample(kb, m, fed.local_batch,
                                               corrupt=node["corrupt"])
                tokens = self.tokenizers[m](raw)
                if node.get("bridge"):
                    # locally paired: same latent draws through modality 2
                    m2 = node["modality2"]
                    raw2, _ = self.task.sample(kb, m2, fed.local_batch)
                    tokens2 = self.tokenizers[m2](raw2)
                    node["trainable"], node["opt_state"], last = \
                        self._bridge_step(
                            node["trainable"], node["opt_state"],
                            self.frozen_bridge, tokens, tokens2, labels,
                            anchors, self.gbar)
                else:
                    node["trainable"], node["opt_state"], last = \
                        self._local_step(
                            node["trainable"], node["opt_state"],
                            self.frozen, tokens, labels, anchors, self.gbar)
            metrics["task"].append(float(last["task"]))
            metrics["geo"].append(float(last["geo"]))
            metrics["acc"].append(float(last["acc"]))
            # upload: Gram + precision + shipped side-cars
            grams.append(cka_mod.cosine_gram(last["pooled_a"]))
            u = unc.lap_uncertainty(last["pooled"], last["pooled_a"])
            precisions.append(unc.node_precision(u))
            # device array, NOT float(): materialising here would force a
            # host sync per node per round even in full-participation runs
            # (only the precision-strategy sampler ever reads these)
            self._last_raw_precisions[i] = precisions[-1]
            smask = _shipped_mask(node["trainable"])
            shipped, _ = _split_by_mask(node["trainable"], smask)
            # bridge nodes carry extra local-only keys (adapter2) that are
            # all-None in the shipped view — drop for structural uniformity
            shipped = {k: v for k, v in shipped.items()
                       if any(l is not None for l in jax.tree.leaves(
                           v, is_leaf=lambda x: x is None))}
            shipped_list.append(shipped)

        # ---- server (averages over whichever nodes reported) ----
        grams = jnp.stack(grams)
        self.gbar = cka_mod.consensus_gram(grams)
        if fed.aggregation == "precision":
            weights = unc.precision_weights(jnp.stack(precisions))
        else:
            weights = jnp.full((k_active,), 1.0 / k_active)
        avg_shipped = agg.aggregate_geolora(shipped_list, weights)
        # broadcast to EVERY node, participants or not (next-round downlink)
        for node in self.nodes:
            merged = dict(avg_shipped)
            for k in node["trainable"]:
                if k not in merged:
                    merged[k] = jax.tree.map(lambda _: None,
                                             node["trainable"][k])
            node["trainable"] = _merge_by_mask(
                merged, node["trainable"], _shipped_mask(node["trainable"]))

        off_diag = cka_mod.mean_offdiag_cka(grams, center=fed.center_cka)
        shipped_bytes = agg.comm_bytes_per_round(
            shipped_list[0], gram_side=self.gbar.shape[0])
        full_bytes = lora_mod.param_bytes(
            lora_mod.combine(self.nodes[0]["trainable"],
                             self._frozen_for(self.nodes[0])))
        rec = {
            "task_loss": sum(metrics["task"]) / k_active,
            "geo_loss": sum(metrics["geo"]) / k_active,
            "acc": sum(metrics["acc"]) / k_active,
            "cross_node_cka": float(off_diag),
            "uplink_bytes": int(shipped_bytes),
            "full_model_bytes": int(full_bytes),
        }
        if active is None:
            rec["weights"] = [float(w) for w in weights]
        else:
            # full-length weight vector, zero at non-reporting nodes, plus
            # the per-round participation log the engine also emits
            ordered = sorted(active)
            wfull = [0.0] * fed.n_nodes
            for wi, i in zip(weights, ordered):
                wfull[i] = float(wi)
            rec["weights"] = wfull
            rec["participation"] = [1.0 if i in active else 0.0
                                    for i in range(fed.n_nodes)]
            rec["cohort_size"] = k_active
        self.history.append(rec)
        return rec

    # ------------------------------------------------------------------
    # participation (sequential reference): the SAME sampler functions the
    # engine traces into its compiled round run here eagerly, over the
    # same width-bucket group layout, so the cohort sequence is identical
    # — this class is the oracle the masked/compacted engine paths are
    # equivalence-tested against.
    def _node_width(self, node) -> int:
        """Adapter width the node needs inside its bucket: its tokenizer's
        d_out, or for a bridge node the max of its two adapters' widths."""
        d = self.tokenizers[node["modality"]].d_out
        if node.get("bridge"):
            d = max(d, self.tokenizers[node["modality2"]].d_out)
        return d

    def _participation_groups(self) -> tuple:
        """Canonical node ids per width bucket — the sampler's group
        layout, mirroring the engine's default bucketed layout."""
        nodes = self.nodes
        widths = [self._node_width(n) for n in nodes]
        bucket_widths = tuple(sorted(set(widths)))
        return tuple(tuple(i for i, w in enumerate(widths) if w == wb)
                     for wb in bucket_widths)

    def _sample_participants(self, plan):
        """Advance the carried sampler state one round and return the
        participating canonical node ids."""
        groups = self._participation_groups()
        prev = getattr(self, "_seq_part", None)
        if prev is None or prev[0] != plan:
            state = part_mod.init_state(plan, self.fed.n_nodes)
        else:
            state = prev[1]
        row_masks, _, state = part_mod.sample_rows(plan, state, groups)
        self._seq_part = (plan, state)
        parts = [g[r] for g, mask in zip(groups, row_masks)
                 for r in range(len(g)) if float(mask[r]) > 0]
        return sorted(parts), groups

    def _update_seq_sampler(self, plan, groups, participants):
        """Fold this round's reported precisions into the sampler state
        (precision-proportional strategy), mirroring the engine's
        on-device ``update_state``."""
        if plan.strategy != "precision":
            return
        plan_, state = self._seq_part
        rows = [i for g in groups for i in g]         # row order
        mask = jnp.asarray([1.0 if i in participants else 0.0
                            for i in rows], jnp.float32)
        p = jnp.asarray([float(self._last_raw_precisions.get(i, 0.0))
                         for i in rows], jnp.float32)
        self._seq_part = (plan_, part_mod.update_state(plan, state, mask,
                                                       p))

    def run_rounds(self, n: int, block_size: int = 1,
                   participation=None) -> List[dict]:
        """Run ``n`` rounds.  ``block_size`` is accepted for API parity with
        the engine-backed ``Federation`` (whose blocks fuse M rounds into
        one dispatch); the sequential reference always steps per round.
        ``participation`` accepts a ``ParticipationPlan`` (or strategy
        string): cohorts are sampled eagerly with the engine's sampler."""
        plan = part_mod.normalize(participation)
        if plan is None:
            return [self.run_round() for _ in range(n)]
        if plan.strategy == "async":
            return [self._run_async_round(plan) for _ in range(n)]
        recs = []
        for _ in range(n):
            parts, groups = self._sample_participants(plan)
            recs.append(self.run_round(participants=parts))
            self._update_seq_sampler(plan, groups, set(parts))
        return recs

    # ------------------------------------------------------------------
    # async (FedBuff) reference: the SAME ``async_events`` draws from the
    # same carried key produce the identical lag/failure stream the
    # engine's compiled round consumes, and the server math calls the
    # same staleness/consensus functions — this eager loop is the oracle
    # the fused async engine path is equivalence-tested against.
    def _run_async_round(self, plan) -> dict:
        fed = self.fed
        k = fed.n_nodes
        groups = self._participation_groups()
        rows = [i for g in groups for i in g]      # canonical id per row
        prev = getattr(self, "_seq_async", None)
        if prev is None or prev[0] != plan:
            self._seq_async = (plan, part_mod.init_state(plan, k),
                               [None] * k)
        _, ctl, buf = self._seq_async
        # the server's previous broadcast value: shipped leaves are
        # identical on every node at round start (node 0 is as good as
        # any) — re-broadcast on a no-delivery round, like the engine
        smask0 = _shipped_mask(self.nodes[0]["trainable"])
        prev_shipped, _ = _split_by_mask(self.nodes[0]["trainable"],
                                         smask0)
        prev_shipped = {kk: v for kk, v in prev_shipped.items()
                        if any(l is not None for l in jax.tree.leaves(
                            v, is_leaf=lambda x: x is None))}
        prev_shipped = jax.tree.map(lambda l: l.astype(jnp.float32),
                                    prev_shipped)
        start, lag_draw, ctl = part_mod.async_events(plan, ctl)
        start_np = [float(v) for v in start]
        countdown = [int(v) for v in ctl["countdown"]]
        lag = [int(v) for v in ctl["lag"]]
        quarantined = [int(v) for v in ctl["quarantined"]]

        # starters run their local epochs; everyone else does NOTHING
        metrics = {"task": [], "geo": [], "acc": []}
        for r, i in enumerate(rows):
            if start_np[r] <= 0:
                continue
            node = self.nodes[i]
            if "round" in node["opt_state"]:
                node["opt_state"] = dict(
                    node["opt_state"],
                    round=node["opt_state"]["round"] + 1)
            m = node["modality"]
            anchors = (self.synthetic_anchor_tokens[m]
                       if i in fed.synthetic_anchor_nodes
                       else self.anchor_tokens[m])
            last = None
            for _ in range(fed.local_steps):
                node["key"], kb = jax.random.split(node["key"])
                raw, labels = self.task.sample(kb, m, fed.local_batch,
                                               corrupt=node["corrupt"])
                tokens = self.tokenizers[m](raw)
                if node.get("bridge"):
                    m2 = node["modality2"]
                    raw2, _ = self.task.sample(kb, m2, fed.local_batch)
                    tokens2 = self.tokenizers[m2](raw2)
                    node["trainable"], node["opt_state"], last = \
                        self._bridge_step(
                            node["trainable"], node["opt_state"],
                            self.frozen_bridge, tokens, tokens2, labels,
                            anchors, self.gbar)
                else:
                    node["trainable"], node["opt_state"], last = \
                        self._local_step(
                            node["trainable"], node["opt_state"],
                            self.frozen, tokens, labels, anchors,
                            self.gbar)
            metrics["task"].append(float(last["task"]))
            metrics["geo"].append(float(last["geo"]))
            metrics["acc"].append(float(last["acc"]))

            # the uplink report: shipped side-cars + Gram + precision
            gram = cka_mod.cosine_gram(last["pooled_a"])
            if fed.aggregation == "precision":
                prec = unc.node_precision(unc.lap_uncertainty(
                    last["pooled"], last["pooled_a"]))
            else:
                prec = jnp.float32(1.0)
            smask = _shipped_mask(node["trainable"])
            shipped, _ = _split_by_mask(node["trainable"], smask)
            shipped = {kk: v for kk, v in shipped.items()
                       if any(l is not None for l in jax.tree.leaves(
                           v, is_leaf=lambda x: x is None))}
            shipped = jax.tree.map(lambda l: l.astype(jnp.float32),
                                   shipped)
            if i in plan.poison_nodes:        # fault injection: uplink only
                nan = jnp.float32(jnp.nan)
                shipped = jax.tree.map(lambda l: l + nan, shipped)
                gram, prec = gram + nan, prec + nan

            # quarantine guard (same formula as the engine, eagerly)
            finite = all(bool(jnp.isfinite(l).all())
                         for l in jax.tree.leaves(shipped))
            finite = finite and bool(jnp.isfinite(gram).all()) \
                and bool(jnp.isfinite(prec).all())
            norm_sq = sum(float((l.astype(jnp.float32) ** 2).sum())
                          for l in jax.tree.leaves(shipped))
            if (not finite) or norm_sq > plan.quarantine_norm ** 2:
                quarantined[r] += 1
                continue                        # idle again; retries next
            buf[r] = {"shipped": shipped, "gram": gram,
                      "prec": jnp.float32(prec)}
            countdown[r] = int(lag_draw[r])
            lag[r] = int(lag_draw[r])

        # staleness-weighted delivery over expiring reports
        delivered = [1.0 if (c == 0 and buf[r] is not None) else 0.0
                     for r, c in enumerate(countdown)]
        base = jnp.asarray(
            [(float(buf[r]["prec"]) if buf[r] is not None else 0.0)
             if fed.aggregation == "precision" else 1.0
             for r in range(k)], jnp.float32)
        wn = unc.stale_precision_weights(
            base, jnp.asarray(lag, jnp.int32),
            jnp.asarray(delivered, jnp.float32), plan.staleness,
            plan.staleness_alpha, plan.max_staleness)
        f = unc.staleness_factor(jnp.asarray(lag, jnp.int32),
                                 plan.staleness, plan.staleness_alpha,
                                 plan.max_staleness)
        fresh = [d * (1.0 if float(f[r]) > 0 else 0.0)
                 for r, d in enumerate(delivered)]
        if float(wn.sum()) > 0:
            total = None
            for r in range(k):
                w = wn[r]
                if float(w) <= 0:
                    continue
                term = jax.tree.map(lambda l: w * l, buf[r]["shipped"])
                total = term if total is None else jax.tree.map(
                    lambda a, b_: a + b_, total, term)
        else:
            total = prev_shipped       # no deliveries: protocol idles
        for node in self.nodes:
            merged = dict(total)
            for kk in node["trainable"]:
                if kk not in merged:
                    merged[kk] = jax.tree.map(
                        lambda _: None, node["trainable"][kk])
            node["trainable"] = _merge_by_mask(
                merged, node["trainable"],
                _shipped_mask(node["trainable"]))
        if sum(fresh) > 0:
            zeros = jnp.zeros_like(self.gbar)
            grams = jnp.stack([buf[r]["gram"] if buf[r] is not None
                               else zeros for r in range(k)])
            self.gbar = cka_mod.consensus_gram(
                grams, mask=jnp.asarray(fresh, jnp.float32),
                fallback=self.gbar)
            xcka = float(cka_mod.mean_offdiag_cka(
                grams, center=fed.center_cka,
                mask=jnp.asarray(fresh, jnp.float32)))
        else:
            xcka = 0.0
        for r in range(k):
            if delivered[r] > 0:
                countdown[r] = -1
            elif countdown[r] > 0:
                countdown[r] -= 1

        self._seq_async = (plan, dict(
            ctl, countdown=jnp.asarray(countdown, jnp.int32),
            lag=jnp.asarray(lag, jnp.int32),
            quarantined=jnp.asarray(quarantined, jnp.int32)), buf)
        n_started = max(sum(1 for s in start_np if s > 0), 1)
        perm = rows
        by_node = lambda vals: [vals[perm.index(i)]
                                for i in range(k)]  # row -> canonical
        rec = {
            "task_loss": sum(metrics["task"]) / n_started,
            "geo_loss": sum(metrics["geo"]) / n_started,
            "acc": sum(metrics["acc"]) / n_started,
            "cross_node_cka": xcka,
            "weights": by_node([float(w) for w in wn]),
            "participation": by_node(start_np),
            "cohort_size": int(sum(start_np)),
            "delivered": by_node(delivered),
            "staleness": by_node([float(lag[r]) if delivered[r] > 0
                                  else -1.0 for r in range(k)]),
            "quarantined": by_node([float(q) for q in quarantined]),
            "n_delivered": float(sum(delivered)),
            "uplink_bytes": 0, "full_model_bytes": 0,
        }
        smask0 = _shipped_mask(self.nodes[0]["trainable"])
        shipped0, _ = _split_by_mask(self.nodes[0]["trainable"], smask0)
        rec["uplink_bytes"] = int(agg.comm_bytes_per_round(
            shipped0, gram_side=self.gbar.shape[0]))
        rec["full_model_bytes"] = int(lora_mod.param_bytes(
            lora_mod.combine(self.nodes[0]["trainable"],
                             self._frozen_for(self.nodes[0]))))
        self.history.append(rec)
        return rec

    def run(self, block_size: int = 1, participation=None) -> List[dict]:
        self.run_rounds(self.fed.rounds, block_size,
                        participation=participation)
        return self.history

    # ------------------------------------------------------------------
    # checkpointing: the server checkpoint is (consensus Gram + per-node
    # trainables + opt states) — the frozen base/tokenizers are rebuilt
    # deterministically from the config seed.
    def save(self, path: str) -> None:
        from repro.checkpoint import save_checkpoint
        state = {
            "gbar": self.gbar,
            "nodes": [{"trainable": n["trainable"],
                       "opt_state": n["opt_state"],
                       "key": n["key"]} for n in self.nodes],
        }
        save_checkpoint(path, state, step=len(self.history))

    def restore(self, path: str) -> int:
        from repro.checkpoint import load_checkpoint
        like = {
            "gbar": self.gbar,
            "nodes": [{"trainable": n["trainable"],
                       "opt_state": n["opt_state"],
                       "key": n["key"]} for n in self.nodes],
        }
        state, step = load_checkpoint(path, like)
        self.gbar = state["gbar"]
        for node, saved in zip(self.nodes, state["nodes"]):
            node["trainable"] = saved["trainable"]
            node["opt_state"] = saved["opt_state"]
            node["key"] = saved["key"]
        return step

    def node_params(self, i: int) -> dict:
        return lora_mod.combine(self.nodes[i]["trainable"],
                                self._frozen_for(self.nodes[i]))


class Federation(SequentialFederation):
    """Width-bucketed node-stacked federation: a thin wrapper over
    ``repro.core.engine.RoundEngine``.  One round — E vmapped local epochs
    per width bucket plus the whole server step — is a single jitted call
    with donated round-state buffers; ``run_rounds(n, block_size=M)`` fuses
    M whole rounds into one donated dispatch (lax.scan over the round body,
    on-device batch sampling from the carried RNG streams, one host sync
    per block); pass ``mesh=`` to shard each bucket's node axis over the
    mesh batch axes (see the module docstring for the architecture).
    Public API and history records match the sequential
    reference; per-node views in ``self.nodes`` are materialised lazily
    (unpadded, through the bucket permutation) from the stacked state on
    access.  Checkpoints store the BUCKETED server state and are
    engine-to-engine only — not loadable into a ``SequentialFederation``
    (whose checkpoints are per-node) nor across a different bucket layout:
    ``width_bucketing`` AND the mesh batch-slice count must match at save
    and restore (an unshardable bucketed layout falls back to the single
    padded bucket, with a warning, which changes the state structure)."""

    def __init__(self, fed: FederationConfig, model: ModelConfig = None, *,
                 mesh=None, width_bucketing: bool = True, donate: bool = True,
                 gram_backend: str = "auto"):
        super().__init__(fed, model)
        self._width_bucketing = width_bucketing
        self._donate = donate
        self._gram_backend = gram_backend
        self._build_engine(mesh)

    # self.nodes is a lazily refreshed VIEW of the stacked state: rounds
    # only mark it stale, so the hot loop never pays K x n_leaves of
    # per-node slicing unless someone actually reads the views.
    @property
    def nodes(self):
        if getattr(self, "_views_stale", False):
            self._views_stale = False
            self._refresh_node_views()
        return self._nodes

    @nodes.setter
    def nodes(self, value):
        self._nodes = value

    # ------------------------------------------------------------------
    def _bucket_layout(self, widths, mesh):
        """Per-node widths -> (bucket_widths, buckets).  With a mesh, every
        bucket's node count must divide the shard count; when the bucketed
        layout can't shard (e.g. one node per width on a multi-device
        mesh), fall back to the single pad-to-max-width bucket rather than
        reject a config the pre-bucketing engine accepted."""
        if self._width_bucketing:
            bucket_widths = tuple(sorted(set(widths)))
            buckets = [tuple(i for i, w in enumerate(widths) if w == wb)
                       for wb in bucket_widths]
        else:           # legacy layout: one bucket padded to the max width
            bucket_widths = (self._d_max,)
            buckets = [tuple(range(len(widths)))]
        if mesh is not None and len(buckets) > 1:
            from repro.launch.mesh import n_nodes as mesh_shards
            n_shards = mesh_shards(mesh)
            if any(len(m) % n_shards for m in buckets):
                import warnings
                warnings.warn(
                    f"width buckets {[len(m) for m in buckets]} do not "
                    f"divide the {n_shards} mesh batch slices; falling "
                    f"back to the single pad-to-max-width bucket "
                    f"(checkpoints from this layout require the same "
                    f"mesh shard count to restore)", stacklevel=3)
                bucket_widths = (self._d_max,)
                buckets = [tuple(range(len(widths)))]
        return bucket_widths, buckets

    def _build_engine(self, mesh) -> None:
        fed = self.fed
        nodes = self._nodes
        self._has_bridges = any(n.get("bridge") for n in nodes)
        self._d_max = max(t.d_out for t in self.tokenizers.values())
        d_model = self.cfg.d_model

        # ---- width-bucket layout (see module doc) ----
        widths = [self._node_width(n) for n in nodes]
        self._bucket_widths, buckets = self._bucket_layout(widths, mesh)
        self._buckets = tuple(buckets)
        self._node_bucket = {i: (b, r) for b, members in enumerate(buckets)
                             for r, i in enumerate(members)}

        # ---- per-bucket node-stacked state ----
        trains, opts, keyss, staticss, masks = [], [], [], [], []
        for members, wb in zip(buckets, self._bucket_widths):
            trees = []
            for i in members:
                node = nodes[i]
                t = dict(node["trainable"])
                t["adapter"] = {"w": engine_mod.pad_axis(
                    t["adapter"]["w"], wb, 0)}
                if self._has_bridges:
                    if node.get("bridge"):
                        t["adapter2"] = {"w": engine_mod.pad_axis(
                            t["adapter2"]["w"], wb, 0)}
                    else:
                        # inert slot: the masked contrastive term gives it
                        # exactly-zero grads and it is never shipped, but it
                        # must be NONZERO — a zero adapter makes pooled2 the
                        # zero vector, whose norm has a NaN gradient that
                        # poisons the whole node even under a 0.0 mask
                        t["adapter2"] = {"w": engine_mod.pad_axis(
                            make_linear(
                                jax.random.fold_in(node["key"], 4242),
                                self.tokenizers[node["modality"]].d_out,
                                d_model, jnp.float32)["w"], wb, 0)}
                trees.append(t)
            train_b = engine_mod.stack_nodes(trees)
            trains.append(train_b)
            opts.append(jax.vmap(self.opt.init)(train_b))
            keyss.append(jnp.stack([nodes[i]["key"] for i in members]))
            staticss.append(self._bucket_statics(members, wb))
            masks.append(_shipped_mask(train_b))
        self._trains = tuple(trains)
        self._opts = tuple(opts)
        self._keys = tuple(keyss)
        self._staticss = tuple(staticss)

        # comm accounting (constant across rounds; matches the reference,
        # computed from node 0's UNpadded view)
        smask0 = _shipped_mask(nodes[0]["trainable"])
        shipped0, _ = _split_by_mask(nodes[0]["trainable"], smask0)
        self._uplink_bytes = int(agg.comm_bytes_per_round(
            shipped0, gram_side=self.gbar.shape[0]))
        self._full_bytes = int(lora_mod.param_bytes(lora_mod.combine(
            nodes[0]["trainable"], self._frozen_for(nodes[0]))))

        ecfg = engine_mod.EngineConfig(
            n_nodes=fed.n_nodes, local_steps=fed.local_steps,
            aggregation=fed.aggregation, center_cka=fed.center_cka,
            bucket_sizes=tuple(len(m) for m in buckets),
            node_perm=tuple(i for members in buckets for i in members),
            donate=self._donate, gram_backend=self._gram_backend,
            server_momentum=fed.server_momentum)
        self.engine = engine_mod.RoundEngine(
            ecfg, self.opt, self._make_local_step(), tuple(masks),
            mesh=mesh)
        self._server_m = self.engine.init_server_state(self._trains)

    def _bucket_statics(self, members, wb: int) -> dict:
        """Compile-time constants for one bucket's nodes, padded to the
        bucket width ``wb``: anchor tokens, frozen tokenizer weights,
        modality maps, corrupt/bridge masks."""
        fed = self.fed
        nodes = self._nodes
        anchors, tw1, tw2, tb1, mw, mb = [], [], [], [], [], []
        for i in members:
            m = nodes[i]["modality"]
            a = (self.synthetic_anchor_tokens[m]
                 if i in fed.synthetic_anchor_nodes
                 else self.anchor_tokens[m])
            anchors.append(engine_mod.pad_axis(a, wb, -1))
            w1, b1, w2 = self.tokenizers[m].padded_weights(wb)
            tw1.append(w1), tb1.append(b1), tw2.append(w2)
            w, b = self.task.modality_map(m)
            mw.append(w), mb.append(b)
        statics = {
            "anchors": jnp.stack(anchors),
            "tok_w1": jnp.stack(tw1), "tok_b1": jnp.stack(tb1),
            "tok_w2": jnp.stack(tw2),
            "mod_w": jnp.stack(mw), "mod_b": jnp.stack(mb),
            "corrupt": jnp.array([bool(nodes[i]["corrupt"])
                                  for i in members]),
        }
        if self._has_bridges:
            b2w1, b2b1, b2w2, m2w, m2b = [], [], [], [], []
            for i in members:
                node = nodes[i]
                m2 = node.get("modality2", node["modality"])
                w1, b1, w2 = self.tokenizers[m2].padded_weights(wb)
                b2w1.append(w1), b2b1.append(b1), b2w2.append(w2)
                w, b = self.task.modality_map(m2)
                m2w.append(w), m2b.append(b)
            statics.update({
                "bridge": jnp.array([1.0 if nodes[i].get("bridge") else 0.0
                                     for i in members], jnp.float32),
                "tok2_w1": jnp.stack(b2w1), "tok2_b1": jnp.stack(b2b1),
                "tok2_w2": jnp.stack(b2w2),
                "mod2_w": jnp.stack(m2w), "mod2_b": jnp.stack(m2b),
            })
        return statics

    # ------------------------------------------------------------------
    def _make_local_step(self):
        """Per-node local step (runs under vmap over the node axis inside
        the engine's scan).  Reproduces the sequential reference exactly:
        same RNG splits, same corrupt/bridge draws, same loss terms."""
        fed, cfg, opt, dataset = self.fed, self.cfg, self.opt, self.task
        n = fed.local_batch
        has_bridges = self._has_bridges
        frozen = self.frozen_bridge if has_bridges else self.frozen
        lam, lam_b, center = fed.lambda_geo, fed.lambda_bridge, fed.center_cka

        def tokenize(raw, w1, b1, w2):
            h = jnp.einsum("nd,dlo->nlo", raw.astype(jnp.float32), w1) + b1
            return jnp.tanh(h) @ w2

        def pooled_of(params, tokens):
            embeds = linear(tokens.astype(jnp.float32), params["adapter"])
            _, aux = T.forward(params, {"inputs_embeds": embeds}, cfg)
            return aux["pooled"]

        def local_step(train, opt_state, key, gbar, st, _batch):
            key, kb = jax.random.split(key)
            # in-scan sampling: both node-type branches from the SAME keys
            # as the reference's task.sample(...), selected per node
            raw, labels, raw2 = dataset.sample_in_scan(
                kb, st["mod_w"], st["mod_b"], n, st["corrupt"],
                mod2_w=st.get("mod2_w"), mod2_b=st.get("mod2_b"))
            tokens = tokenize(raw, st["tok_w1"], st["tok_b1"], st["tok_w2"])

            def loss_fn(tr):
                params = lora_mod.combine(tr, frozen)
                pooled = pooled_of(params, tokens)
                logits = linear(pooled, params["cls_head"])
                task = cross_entropy_loss(logits, labels)
                loss = task
                if has_bridges:
                    tokens2 = tokenize(raw2, st["tok2_w1"], st["tok2_b1"],
                                       st["tok2_w2"])
                    params2 = dict(params, adapter=params["adapter2"])
                    pooled2 = pooled_of(params2, tokens2)
                    loss = loss + lam_b * st["bridge"] * \
                        SequentialFederation._contrastive(pooled, pooled2)
                params_geo = lora_mod.combine(_stopgrad_named(tr), frozen)
                pooled_a = pooled_of(params_geo, st["anchors"])
                geo = cka_mod.geo_alignment_loss(pooled_a, gbar,
                                                 center=center)
                acc = (logits.argmax(-1) == labels).mean()
                return loss + lam * geo, (task, geo, acc, pooled, pooled_a)

            grads, (task, geo, acc, pooled, pooled_a) = \
                jax.grad(loss_fn, has_aux=True)(train)
            new_train, new_opt = opt.update(grads, opt_state, train)
            return new_train, new_opt, key, {
                "task": task, "geo": geo, "acc": acc,
                "pooled": pooled, "pooled_a": pooled_a}

        return local_step

    # ------------------------------------------------------------------
    def run_round(self, participants=None) -> dict:
        """One engine round.  ``participants`` mirrors the sequential
        reference's explicit-cohort hook by running a one-shot fixed
        ``nodes`` participation plan (each DISTINCT cohort compiles its
        own round program — for per-round sampled cohorts use
        ``run_rounds(participation=...)``, which samples inside one
        compiled program)."""
        if participants is not None:
            plan = part_mod.ParticipationPlan(
                strategy="nodes", nodes=tuple(sorted(participants)))
            self._ensure_participation(plan)
            return self._run_round_part(plan)
        # round-state buffers are donated: the previous round's arrays are
        # invalidated by this call and replaced by the outputs
        (self._trains, self._opts, self._keys, self.gbar, self._server_m,
         metrics) = self.engine.round_fn(
            self._trains, self._opts, self._keys, self.gbar, self._server_m,
            self._staticss, (None,) * len(self._trains))
        rec = self._metrics_record(metrics)
        self._views_stale = True
        self.history.append(rec)
        return rec

    def _metrics_record(self, metrics, r: Optional[int] = None) -> dict:
        """One history record from engine metrics — per-round metrics when
        ``r`` is None, else round ``r`` of a block's stacked (M, ...)
        metric buffers.  Participation-aware metrics (per-node scalars are
        zero at non-reporting nodes) average over the cohort."""
        sl = (lambda x: x) if r is None else (lambda x: x[r])
        s = metrics["scalars"]
        if "participation" in metrics:
            c = max(float(sl(metrics["cohort_size"])), 1.0)
            mean = lambda x: float(jnp.sum(sl(x))) / c
        else:
            mean = lambda x: float(jnp.mean(sl(x)))
        rec = {
            "task_loss": mean(s["task"]),
            "geo_loss": mean(s["geo"]),
            "acc": mean(s["acc"]),
            "cross_node_cka": float(sl(metrics["cross_node_cka"])),
            "weights": [float(w) for w in sl(metrics["weights"])],
            "uplink_bytes": self._uplink_bytes,
            "full_model_bytes": self._full_bytes,
        }
        if "participation" in metrics:
            rec["participation"] = [float(p)
                                    for p in sl(metrics["participation"])]
            rec["cohort_size"] = int(round(float(sl(
                metrics["cohort_size"]))))
        if "delivered" in metrics:
            rec["delivered"] = [float(d) for d in sl(metrics["delivered"])]
            rec["staleness"] = [float(s) for s in sl(metrics["staleness"])]
            rec["quarantined"] = [float(q)
                                  for q in sl(metrics["quarantined"])]
            rec["n_delivered"] = float(sl(metrics["n_delivered"]))
        return rec

    def _init_part_state(self, plan):
        if plan is None:
            return None
        if plan.strategy == "async":
            return self.engine.init_async_state(
                self._trains, plan, gram_side=int(self.gbar.shape[0]))
        return part_mod.init_state(plan, self.fed.n_nodes)

    def _ensure_participation(self, plan) -> None:
        """Install ``plan`` as the active participation plan, carrying the
        sampler state across calls (and through checkpoints) when the plan
        is unchanged, re-seeding it when the plan switches.  Async plans
        additionally carry the zeroed report buffer (shaped from the
        current stacked trainables) in the state."""
        if getattr(self, "_part_plan", None) != plan \
                or not hasattr(self, "_part_state"):
            self._part_plan = plan
            self._part_state = self._init_part_state(plan)

    def _run_round_part(self, plan) -> dict:
        (self._trains, self._opts, self._keys, self.gbar, self._server_m,
         self._part_state, metrics) = self.engine.part_round_fn(plan)(
            self._trains, self._opts, self._keys, self.gbar,
            self._server_m, self._part_state, self._staticss,
            (None,) * len(self._trains))
        rec = self._metrics_record(metrics)
        self._views_stale = True
        self.history.append(rec)
        return rec

    def _make_state_tap(self, path: str):
        """Host side of the in-block checkpoint tap: receives the block
        carry at round granularity from inside the fused scan and writes
        a checkpoint structurally identical to ``save()`` (restorable by
        ``restore()``).  ``path`` may contain ``{step}``; otherwise the
        file is overwritten in place (atomic rename in save_checkpoint,
        so a crash mid-write never corrupts the previous one).  Raising
        here (disk full) is logged and dropped by the engine's tap guard
        — a failing checkpoint never kills the in-flight block."""
        from repro.checkpoint import save_checkpoint
        meta = {"server_momentum": self.fed.server_momentum,
                "n_buckets": len(self._trains),
                "round_schedule": self.fed.round_lr_schedule is not None,
                "participation": part_mod.plan_meta(
                    getattr(self, "_part_plan", None))}

        def state_tap(step: int, carry):
            if len(carry) == 6:
                tr, op, ks, gb, sm, ps = carry
            else:
                (tr, op, ks, gb, sm), ps = carry, None
            state = {"gbar": gb, "train": tr, "opt": op, "keys": ks}
            if sm is not None:
                state["server_m"] = sm
            if ps is not None:
                state["part"] = ps
            p = path.format(step=step) if "{step}" in path else path
            save_checkpoint(p, state, step=step, meta=meta)

        return state_tap

    def run_rounds(self, n: int, block_size: int = 1, tap=None,
                   participation=None, checkpoint_path: str = None,
                   checkpoint_every: int = 0) -> List[dict]:
        """Run ``n`` rounds; with ``block_size`` M > 1, rounds execute as
        fused M-round blocks (``engine.run_block``): ONE donated dispatch
        and one host sync per block instead of per round.  Dispatch is
        async — every block is enqueued before any metric is read back, so
        the device never waits on the host between blocks; history records
        materialise after the last block is in flight.  ``block_size=1`` is
        the exact legacy per-round path.  ``tap`` (block mode) streams each
        round's metrics to the host via ``io_callback`` without forcing a
        sync.

        ``participation`` (a ``ParticipationPlan`` or strategy string)
        samples a reporting cohort per round on device; the sampler state
        rides the block carry and the checkpoint.  ``None`` / ``"full"``
        is routed onto the unchanged legacy path (bit-identical).

        ``checkpoint_path`` + ``checkpoint_every`` arm the IN-BLOCK
        checkpoint tap (block mode): the full block carry streams to a
        ``restore()``-compatible checkpoint every ``checkpoint_every``
        rounds FROM INSIDE the fused scan, so killing the process
        mid-block loses < checkpoint_every rounds (< M without it losing
        the whole block).  The step recorded is the absolute round count,
        so a resumed driver knows how many rounds remain."""
        plan = part_mod.normalize(participation)
        state_tap, every = None, 0
        if checkpoint_path is not None and block_size > 1:
            if plan is not None:
                self._ensure_participation(plan)
            state_tap = self._make_state_tap(checkpoint_path)
            every = max(1, checkpoint_every)
        if plan is None:
            if block_size <= 1:
                return [self.run_round() for _ in range(n)]
            pending, done = [], 0
            while done < n:
                m = min(block_size, n - done)
                state = (self._trains, self._opts, self._keys, self.gbar,
                         self._server_m)
                (self._trains, self._opts, self._keys, self.gbar,
                 self._server_m), metrics = self.engine.run_block(
                    state, m, statics=self._staticss, tap=tap,
                    state_tap=state_tap,
                    state_tap_every=min(every, m) if state_tap else 0,
                    round_offset=len(self.history) + done)
                pending.append((m, metrics))
                done += m
        else:
            self._ensure_participation(plan)
            if block_size <= 1:
                return [self._run_round_part(plan) for _ in range(n)]
            pending, done = [], 0
            while done < n:
                m = min(block_size, n - done)
                state = (self._trains, self._opts, self._keys, self.gbar,
                         self._server_m, self._part_state)
                (self._trains, self._opts, self._keys, self.gbar,
                 self._server_m, self._part_state), metrics = \
                    self.engine.run_block(
                        state, m, statics=self._staticss, tap=tap,
                        plan=plan, state_tap=state_tap,
                        state_tap_every=min(every, m) if state_tap else 0,
                        round_offset=len(self.history) + done)
                pending.append((m, metrics))
                done += m
        self._views_stale = True
        recs = [self._metrics_record(metrics, r)
                for m, metrics in pending for r in range(m)]
        self.history.extend(recs)
        if tap is not None or state_tap is not None:
            # metric readback does not wait for the io_callback thread;
            # drain it so every round's tap has fired before returning
            jax.effects_barrier()
        return recs

    def _unpad_node_tree(self, tree: dict, node: dict) -> dict:
        """Strip the padded widths from one node's slice of a stacked tree
        (trainables or AdamW moments), restoring the reference's ragged
        per-node structure."""
        tree = dict(tree)
        d = self.tokenizers[node["modality"]].d_out
        tree["adapter"] = {"w": tree["adapter"]["w"][:d]}
        if "adapter2" in tree:
            if node.get("bridge"):
                d2 = self.tokenizers[node["modality2"]].d_out
                tree["adapter2"] = {"w": tree["adapter2"]["w"][:d2]}
            else:
                del tree["adapter2"]
        return tree

    def _refresh_node_views(self) -> None:
        """Materialise per-node (unpadded) views of the bucketed state so
        ``self.nodes`` / ``node_params`` keep the reference's shapes: node
        i lives at row r of bucket b under the stable permutation."""
        for i, node in enumerate(self._nodes):
            b, r = self._node_bucket[i]
            node["trainable"] = self._unpad_node_tree(
                jax.tree.map(lambda x: x[r], self._trains[b]), node)
            opt_i = jax.tree.map(lambda x: x[r], self._opts[b])
            node["opt_state"] = {
                "m": self._unpad_node_tree(opt_i["m"], node),
                "v": self._unpad_node_tree(opt_i["v"], node),
                "step": opt_i["step"],
            }
            if "round" in opt_i:
                node["opt_state"]["round"] = opt_i["round"]
            node["key"] = self._keys[b][r]

    # ------------------------------------------------------------------
    # checkpointing: engine checkpoints store the BUCKETED server state
    # (tuples of per-bucket stacked trees); the bucket layout is rebuilt
    # deterministically from the config, so a restore into a federation
    # with the same config and ``width_bucketing`` lands every node back
    # at its row through the same permutation
    def _ckpt_state(self) -> dict:
        state = {"gbar": self.gbar, "train": self._trains,
                 "opt": self._opts, "keys": self._keys}
        if self._server_m is not None:
            state["server_m"] = self._server_m
        if getattr(self, "_part_state", None) is not None:
            state["part"] = self._part_state
        return state

    def save(self, path: str) -> None:
        from repro.checkpoint import save_checkpoint
        # the saved state IS the engine's block carry (trains / opts / keys
        # / gbar / server-opt / participation sampler), so a save at a
        # block boundary captures everything a resumed run_block needs to
        # continue bit-identically — including the cohort sampling stream
        save_checkpoint(path, self._ckpt_state(), step=len(self.history),
                        meta={"server_momentum": self.fed.server_momentum,
                              "n_buckets": len(self._trains),
                              "round_schedule":
                                  self.fed.round_lr_schedule is not None,
                              "participation": part_mod.plan_meta(
                                  getattr(self, "_part_plan", None))})

    def restore(self, path: str) -> int:
        from repro.checkpoint import load_checkpoint, read_meta
        meta = read_meta(path)
        if meta.get("server_momentum") != self.fed.server_momentum:
            raise ValueError(
                f"checkpoint server_momentum={meta.get('server_momentum')} "
                f"does not match config {self.fed.server_momentum}; the "
                f"block carry structure differs")
        if bool(meta.get("round_schedule", False)) != \
                (self.fed.round_lr_schedule is not None):
            raise ValueError(
                f"checkpoint round_schedule="
                f"{bool(meta.get('round_schedule', False))} does not match "
                f"config round_lr_schedule="
                f"{self.fed.round_lr_schedule is not None}; the optimizer "
                f"carry structure (round counter) differs")
        plan = part_mod.plan_from_meta(meta.get("participation"))
        if plan is not None:
            # the sampler state is part of the checkpointed carry; restore
            # resumes the cohort stream without the caller re-passing the
            # plan (run_rounds with the same plan keeps the state)
            self._part_plan = plan
            self._part_state = self._init_part_state(plan)
        else:
            # a full-participation checkpoint must also restore INTO a
            # federation that previously ran with a plan: drop the stale
            # sampler state so the carry template matches the file
            self._part_plan = None
            self._part_state = None
        state, step = load_checkpoint(path, self._ckpt_state())
        self.gbar = state["gbar"]
        self._trains = state["train"]
        self._opts = state["opt"]
        self._keys = state["keys"]
        if "server_m" in state:
            self._server_m = state["server_m"]
        if "part" in state:
            self._part_state = state["part"]
        self._views_stale = True
        return step
