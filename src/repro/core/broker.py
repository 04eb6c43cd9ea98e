"""Multi-subscriber interest broker: cohort-cached fused evaluation passes.

The paper's headline deployment (§1, §3) is many long-lived remote
applications each holding an interest expression ``i_g = <τ, b, op>``
(Definition 7) against one continuously-evolving source. PR 1 amortized the
per-changeset scan across subscribers with a single fused jitted step; this
module additionally amortizes the *lifecycle*: subscribers come and go, and
none of that churn may recompile work that belongs to other subscribers.

The broker is four layers, plus a distribution layer over them:

1. **Cohort executable cache.** Subscribers whose interests share the same
   static plan shape (pattern kinds/slots/const-masks, Definition 7
   structure) and capacities form a cohort evaluated by one ``jax.vmap``
   over the pattern *values* (:func:`make_cohort_step`). Each cohort's step
   is compiled separately and cached under ``(plan-shape key, caps,
   id-capacity, padded cohort size, padded target count, padded bank
   words)``. Cohort membership is padded to power-of-two sizes with masked
   dummy lanes (``kernels.ops.lane_bits_batched(active=...)`` zeroes their
   bits, so they contribute nothing and cost no extra recompiles), and every
   dynamic quantity — pattern values, lane maps, the bank array, the member
   mask — is a *traced input*, so subscribing, unsubscribing, or growing one
   subscriber (re)compiles at most its own cohort; every other cohort
   reuses its cached executable.

2. **Incremental pattern bank.** All registered interests dedup into one
   :class:`~repro.core.interest.IncrementalPatternBank`: subscribing extends
   lanes without renumbering existing ones, unsubscribing tombstones lanes
   (reused by later subscriptions) until compaction, and the device bank
   array is padded to power-of-two lane counts — so bank churn neither
   invalidates unrelated cohorts' lane maps nor changes executable input
   shapes. Per changeset there is one chunked bank bitmask pass over the
   deleted side D shared by every cohort, and one per cohort over the
   stacked ``I_k = A ∪ ρ_k`` sets (Definition 14); bitset-lane routing hands
   each subscriber its local pattern bits.

3. **Interest-subsumption lattice + subscriber fanout** (default,
   ``Broker(subsume_interests=False)`` preserves the per-subscriber PR 5
   path as the baseline). The paper's deployment is many consumers holding
   *overlapping* interests over one stream, so distinct interests — not
   subscribers — are the unit of evaluation cost (cf. Fedra's
   containment-driven source selection and Knuth & Hartig's
   distinct-queries scheduling):

   * **canonical lane groups.** Every ``subscribe()`` canonicalizes its
     expression (:func:`repro.core.interest.canonicalize_expr`: skeleton
     pattern sort + bijective variable renaming), so expressions that
     differ only in pattern order / variable names land on identical
     compiled plans and bank lanes. A new subscription whose canonical
     key, capacities, policy, frontier, and τ/ρ state provably match an
     existing lineage auto-joins it (the previously opt-in
     ``share_target`` detection, now automatic for the exact-duplicate
     case); members of one lineage occupy ONE cohort slot per fire — the
     lane result is computed once and **fanned out host-side** to every
     member's output, with per-subscriber τ/ρ applied only at commit, so
     delivery is O(1) executable work per distinct interest
     (``BrokerStats.distinct_interests`` vs ``fanout_copies``).
   * **containment DAG.** Bank rows are deduplicated pattern-wise and a
     row whose pattern is *strictly contained* by an existing row's (a
     constant where the parent has a variable) becomes a **virtual lane**
     (:class:`~repro.core.interest.SubsumptionBank`): it occupies no bank
     width in the deleted-side words pass — its words are the parent
     lane's already-emitted words ANDed with the cheap residual-constant
     compare (:func:`repro.kernels.ops.lane_refine`), concatenated after
     the real planes so lane routing is oblivious to the distinction.
     The added-side fused pass matches virtual rows as materialized
     patterns in the extended bank (refining the fused kernel is a
     ROADMAP follow-on).

4. **Push scheduler — device-resident, delta-chained frontiers.** Each
   subscription carries a :class:`PushPolicy` (every-k-changesets, priority
   lane, or max-staleness, cf. the SPARQL refresh-scheduling literature).
   The host orchestrator accumulates pending changesets as composed batches
   (:func:`repro.core.propagation.compose_changesets` — Definition 6
   algebra over the device triple-set ops — one batch per consumption
   frontier), and a subscriber's cohort is routed through the fused pass
   only when its policy fires; :meth:`Broker.flush` drains the rest (a
   flush with nothing pending, and a fired frontier whose composed batch
   is empty, return without touching statics or executables at all). The
   deferred path stays on device end-to-end: a fire consumes the batch's
   already-lex-sorted device stores (:meth:`~repro.core.propagation
   .ChangesetBatch.device_stores`), re-homing via
   :func:`repro.core.triples.rehome` (pad/slice, never re-sort or
   transfer) when padding shapes change, and when several frontiers fire in
   one call their same-shape cohort invocations stack into ONE batched
   executable call (the frontier is one more padded, masked axis folded
   into the cohort's member dimension — see :func:`make_cohort_step`).

   Fired frontiers *overlap* — every batch composes a suffix of the same
   stream — so the multi-frontier deleted-side pass is **delta-encoded**
   rather than stacked: the flush builds a
   :class:`~repro.core.propagation.FrontierChain` (the lex-sorted
   distinct-row union of every fired D side plus per-frontier int32
   membership bitmaps, probed — not assumed — with an exact containment
   check) and ONE segmented bank pass
   (:func:`repro.kernels.ops.pattern_bitmask_words_segmented`) matches
   each distinct changeset row once, composing each frontier's words by
   membership masking. Cohort members then share the single union store —
   their ``f_map`` slot selects masked words instead of gathering
   duplicated per-frontier stores — and rows outside a member's frontier
   carry zero bits, which the evaluator's zero-bits discipline turns into
   "no candidates, no signatures, no outputs", keeping every output
   bit-identical to the stacked evaluation while the matched-row volume
   drops from ~F× the union to ~1× (observable as
   ``BrokerStats.rows_matched`` vs ``rows_distinct``).
   ``Broker(delta_frontiers=False)`` preserves the stacked per-frontier
   pass as the escape hatch / benchmark baseline. Subscribers attached to
   one target dataset replica (``subscribe(..., share_target=True)``)
   share a single ``build_index(τ)`` inside the cohort step.

5. **Device-sharded cohort routing.** Cohorts are independently compiled,
   independently schedulable units, which makes them the natural unit of
   *distribution*: with ``Broker(mesh=...)`` a
   :class:`~repro.core.distributed.CohortPlacement` policy places each
   cohort on a mesh device (round-robin, load-balanced by padded member
   count, or pinned) and the frontier pass dispatches its cohort calls
   grouped by device — executables, statics, the padded bank copy, and
   every member's τ/ρ state stay resident per device, so steady-state
   fires move only the frontier's changeset slices and the asynchronously
   dispatched cohorts run concurrently across the mesh. With
   ``shard_cohorts=True`` each cohort pass instead runs *inside* shard_map
   over the whole mesh (:func:`make_sharded_cohort_step`): τ replicas
   hash-partition across the shards (cached per (subscription, τ-version,
   capacity), so churn never re-partitions untouched replicas), the bank
   match passes block-split and block-gather-stitched, and candidate probes
   route to their owner shard via the batched all_to_all probe. Both modes
   are bit-identical to the single-device broker by construction; the
   per-frontier composed batches remain the delivery windows — the natural
   cross-host boundary.

6. **Durability + delivery robustness** (both opt-in; a broker without a
   journal or channel behaves exactly as before, on the same unified
   sequence clock). Attaching a :class:`~repro.core.journal.ChangesetJournal`
   (``Broker(journal=...)`` or ``broker.journal = ...``) write-ahead-logs
   every state-changing event on one monotonic sequence: ``subscribe`` /
   ``unsubscribe`` records carry the call's arguments, ``ingest`` records
   carry the raw changeset arrays (appended *before* the batches extend),
   and a ``fire`` record carries the acked ``{subscriber: new frontier}``
   advances — appended after delivery but *before* the in-memory commit,
   so the journal's durable prefix is always a consistent boundary.
   :meth:`Broker.snapshot` checkpoints full subscriber state (τ/ρ valid
   rows, caps, policy, frontier) through the
   :class:`~repro.checkpoint.store.CheckpointStore` atomic tmp-dir+rename
   discipline keyed by journal seq, and :meth:`Broker.recover` rebuilds a
   bit-identical broker by snapshot-plus-tail-replay (replayed ingests
   rebuild composed batches; replayed fires re-evaluate exactly the
   recorded subscribers with delivery suppressed).

   **The durability/exactly-once contract.** Recovery gives at-least-once
   fire semantics: a crash between delivery and the ``fire`` record means
   the frontier never durably advanced, so the next fire re-delivers —
   but always as the *composed* window ``C[f..j]`` re-extended to
   ``C[f..j']``. Definition 6 composition makes that idempotent for the
   receiver: for set-semantic changesets, ``apply(apply(τ, X), X∘Y) ==
   apply(apply(τ, X), Y)`` — the composed delta's D side re-deletes rows
   already gone and its A side re-adds rows already present — so a replica
   that applies every delivered composed window converges to exactly-once
   *state* regardless of redelivery. This is why the journal only needs
   ingest WAL + acked-frontier records, never delivered payloads.

   A :class:`~repro.core.delivery.DeliveryChannel` (``Broker(channel=...)``)
   adds the failure-handling tier at the same commit point: per-subscriber
   retry with exponential backoff + jitter + timeout, a bounded in-flight
   retry queue that backpressures :meth:`process_changeset`, and poison
   quarantine — a subscriber failing N consecutive deliveries stops firing
   (its frontier pins, its batch keeps composing) instead of stalling the
   broker. Delivery happens before commit, so a failed delivery needs no
   rollback: the subscriber is simply not committed. Channel state (retry
   counts, quarantine) is deliberately *not* durable — after recovery every
   subscriber starts unpinned and re-earns its quarantine. Finally, the
   capacity-overflow retry loop gains a bounded ceiling
   (``max_fire_retries``): past it, the affected subscribers are evaluated
   through the per-interest seed path (bit-identical by the oracle
   discipline, just slower) and ``BrokerStats.degraded_fires`` records the
   degradation instead of the fire doubling capacities without limit.

Downstream of the bitmask every subscriber runs the *same* traced
computation as the single-interest path — the side evaluators of
:mod:`repro.core.evaluation` (π / π', Definitions 11-12) with precomputed
bits and traced pattern values (``probe_dyn``), and
:func:`repro.core.propagation.combine_side_results` for Δ(τ), Δ(ρ), Υ
(Definitions 16-18) — so per-subscriber outputs stay bit-identical to N
independent :func:`~repro.core.propagation.make_interest_step` runs.

Paper-name ↔ code-name map (Definitions 13-18):

========================  ====================================================
paper                     code
========================  ====================================================
``d(i, D) = <r, r_i, r'>``  ``EvalOutputs.r / .r_i / .r_prime`` (Def 13)
``α(i, A ∪ ρ) = <a, a_i>``  ``EvalOutputs.a / .a_i``            (Def 14)
``Δ(τ)``                    applied to ``BrokerSubscription.tau`` (Def 16)
``Δ(ρ)``                    applied to ``BrokerSubscription.rho`` (Def 17)
``Υ``                       ``combine_side_results``              (Def 18)
========================  ====================================================

The host-side :class:`Broker` mirrors the iRap architecture's Interest
Manager / Changeset Manager / Evaluator split, with compile/rebuild time
accounted separately from evaluation time (``BrokerStats.rejit_s``).
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import OrderedDict
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..kernels import ops as kops
from .dictionary import Dictionary
from .distributed import (
    CohortPlacement,
    make_or_reduce,
    make_routed_probe_batched,
    prepare_target_shards,
)
from .evaluation import (
    SideResult,
    TripleIndex,
    build_index,
    make_side_evaluator,
    tree_gather,
    tree_index,
    tree_stack,
)
from .interest import (
    CompiledInterest,
    IncrementalPatternBank,
    InterestExpr,
    PatternBank,
    SubsumptionBank,
    canonicalize_expr,
    compile_interest,
    next_pow2,
)
from . import tracing
from .journal import ChangesetJournal
from .propagation import (
    ChangesetBatch,
    EvalOutputs,
    StepCapacities,
    build_frontier_chain,
    combine_side_results,
    make_interest_step,
)
from .triples import (
    PAD,
    TripleStore,
    empty,
    from_array,
    rehome,
    store_from_np,
    to_numpy,
    union,
)


def _plan_shape_key(plan: CompiledInterest):
    """Static evaluation structure of a plan — everything the traced
    evaluator specializes on except the pattern *values* (which slots are
    constant matters; what constant they hold does not)."""
    const_mask = tuple(
        tuple(int(x) >= 0 for x in row) for row in plan.patterns
    )
    return (
        plan.n_bgp,
        plan.n_ogp,
        plan.kinds,
        plan.anchor_slot,
        plan.child_slot,
        plan.child_var,
        plan.eq_pairs,
        plan.n_children,
        const_mask,
    )


# ---------------------------------------------------------------------------
# durability: journal/snapshot (de)serialization of subscription arguments
# ---------------------------------------------------------------------------

def _expr_to_json(expr: InterestExpr) -> dict:
    return {
        "source": expr.source,
        "target": expr.target,
        "bgp": [list(p.slots()) for p in expr.bgp],
        "ogp": [list(p.slots()) for p in expr.ogp],
    }


def _expr_from_json(d: dict) -> InterestExpr:
    return InterestExpr.parse(
        d["source"], d["target"],
        bgp=[tuple(p) for p in d["bgp"]],
        ogp=[tuple(p) for p in d.get("ogp", [])],
    )


def _caps_to_json(caps: StepCapacities) -> dict:
    return dataclasses.asdict(caps)


def _caps_from_json(d: dict) -> StepCapacities:
    return StepCapacities(**d)


def _policy_to_json(policy: "PushPolicy | None") -> dict | None:
    return None if policy is None else dataclasses.asdict(policy)


def _policy_from_json(d: dict | None) -> "PushPolicy | None":
    return None if d is None else PushPolicy(**d)


# ---------------------------------------------------------------------------
# layer 4: push scheduling policy
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PushPolicy:
    """When a subscriber's pending batch is routed through the fused pass.

    Real consumers want per-subscriber cadences, not lock-step evaluation at
    every changeset (cf. the SPARQL refresh-scheduling literature): a slow
    replica can absorb k changesets per push, a dashboard wants every update
    immediately, a mirror only bounds staleness.

    ``every_k``           fire once k changesets are pending (1 = eager;
                          None disables count-based firing).
    ``max_staleness_s``   fire once this many seconds have passed since the
                          subscriber's last push (None disables).
    ``priority``          priority lane: fire at every changeset and run
                          before non-priority work in the pass order.

    A subscriber with nothing pending never fires; :meth:`Broker.flush`
    drains pending batches regardless of policy.
    """

    every_k: Optional[int] = 1
    max_staleness_s: Optional[float] = None
    priority: bool = False

    @staticmethod
    def every(k: int) -> "PushPolicy":
        """Batch k changesets between pushes (slow-consumer cadence)."""
        return PushPolicy(every_k=k)

    @staticmethod
    def priority_lane() -> "PushPolicy":
        """Evaluate at every changeset, ahead of non-priority subscribers."""
        return PushPolicy(every_k=1, priority=True)

    @staticmethod
    def max_staleness(seconds: float) -> "PushPolicy":
        """Fire only when the replica's staleness bound is reached."""
        return PushPolicy(every_k=None, max_staleness_s=seconds)

    def fires(self, pending: int, staleness_s: float) -> bool:
        if pending <= 0:
            return False
        if self.priority:
            return True
        if self.every_k is not None and pending >= self.every_k:
            return True
        return (
            self.max_staleness_s is not None
            and staleness_s >= self.max_staleness_s
        )


# ---------------------------------------------------------------------------
# layer 1: per-cohort jitted step
# ---------------------------------------------------------------------------

def make_cohort_step(
    plan: CompiledInterest,
    caps: StepCapacities,
    id_capacity: int,
    matcher: Optional[Callable] = None,
    delta: bool = False,
) -> Callable:
    """Build the jitted fused step for ONE shape-homogeneous cohort,
    spanning every deferred frontier that fires in the same call.

    ``plan`` supplies only static structure (kinds, slots, const masks); the
    pattern *values*, lane maps, bank array, target stores, frontier
    changesets, and member mask are traced inputs, so one compiled
    executable serves any cohort of this shape — across subscription churn,
    bank growth, re-subscription, and any assignment of members to
    frontiers.

    Signature (``Nc`` = padded member count across all frontiers, ``Nu`` =
    padded unique-target count, ``Fp`` = padded frontier count, ``W`` =
    padded bank words)::

        step(d_sets,           # Fp-tuple of TripleStore — deleted side per
                               #   frontier (padding slots: empty stores)
             d_words,          # Fp-tuple of uint32[|D|, W] bank bitsets
             a_sets,           # Fp-tuple of TripleStore — added side
             bank_dev,         # int32[32 W, 3] padded pattern bank
             uniq_taus,        # Nu-tuple of TripleStore — unique replicas
             f_map,            # int32[Nc] member -> frontier slot
             tgt_map,          # int32[Nc] member -> unique replica slot
             rhos,             # Nc-tuple of TripleStore
             pats,             # int32[Nc, nt, 3] pattern values per member
             lanes,            # int32[Nc, nt] bank lane per local pattern
             active,           # bool[Nc] member mask (False = padding lane)
        ) -> (tau1s, rho1s, outs)   # Nc-tuples, per member

    The frontier dimension is folded into the member axis rather than a
    nested batch: every member gathers its own frontier's (D, A, D-words)
    slice via ``f_map`` and the whole cohort — across however many deferred
    frontiers fired together — runs as ONE vmapped executable call. A
    single-frontier fire is simply ``Fp == 1`` with an all-zero ``f_map``,
    so the eager path and the stacked flush path share executables of the
    same shape family (cached separately per ``Fp``).

    Member stores go in and come out as *tuples*: stacking for the vmap and
    per-member unstacking happen inside the traced step, so the host pays
    one executable call per cohort instead of O(members) eager stack/slice
    dispatches per changeset. The added side routes through the fused
    match+route kernel (:func:`repro.kernels.ops.pattern_lane_bits_batched`)
    — one pass over each member's ``I_k`` rows regardless of bank width.

    ``build_index(τ)`` runs once per *unique* target replica and is fanned
    out to members via ``tgt_map`` — subscribers attached to one target
    dataset share the index build. Inactive (padding) members contribute
    zero pattern bits and empty outputs.

    ``delta=True`` builds the **delta-chain** variant: the per-frontier
    ``d_sets`` tuple is replaced by ONE shared union store (the distinct D
    rows across every fired frontier,
    :class:`~repro.core.propagation.FrontierChain`), and ``d_words``
    carries the per-frontier *membership-masked* words over the union rows
    (one segmented bank pass upstream instead of one stacked pass per
    frontier). Every member evaluates the same union store; its ``f_map``
    slot selects its frontier's masked words, and rows outside that
    frontier carry zero bits — which the evaluator turns into "no
    candidates, no signature scatters, no outputs", exactly the sharded
    path's ``row_mask`` discipline — so outputs stay bit-identical to the
    stacked per-frontier evaluation while each distinct changeset row is
    matched (and its store gathered) once instead of once per frontier::

        step(d_union,      # TripleStore — union D rows, shared by members
             d_words,      # Fp-tuple of uint32[|U|, W] masked union words
             a_sets, bank_dev, uniq_taus, f_map, tgt_map, rhos,
             pats, lanes, active) -> (tau1s, rho1s, outs)

    Each phase of both variants runs under one device scope of
    :data:`repro.core.tracing.SCOPES` (``cohort.gather``, ``cohort.lanes``,
    ``cohort.build_index``, ``cohort.eval_removed``, ``cohort.eval_added``,
    ``cohort.combine``, ``cohort.unstack``), which
    :func:`repro.core.tracing.scope_table` maps back from the trace.
    """
    eval_kw = dict(
        id_capacity=id_capacity,
        fanout=caps.fanout,
        pull_capacity=caps.pulls,
        matcher=matcher,
        dedup_candidates=caps.dedup_candidates,
        dynamic_patterns=True,
    )
    eval_d = make_side_evaluator(plan, out_capacity=caps.n_removed, **eval_kw)
    eval_a = make_side_evaluator(plan, out_capacity=caps.n_i, **eval_kw)

    if delta:

        @jax.jit
        def step_delta(
            d_union: TripleStore,
            d_words: Tuple[jax.Array, ...],
            a_sets: Tuple[TripleStore, ...],
            bank_dev: jax.Array,
            uniq_taus: Tuple[TripleStore, ...],
            f_map: jax.Array,
            tgt_map: jax.Array,
            rhos: Tuple[TripleStore, ...],
            pats: jax.Array,
            lanes: jax.Array,
            active: jax.Array,
        ):
            nc = lanes.shape[0]
            with jax.named_scope("cohort.gather"):
                rhos_s = tree_stack(list(rhos))
                uniq_s = tree_stack(list(uniq_taus))
                a_stack = tree_stack(list(a_sets))
                w_stack = jnp.stack(list(d_words))

                a_mem = tree_gather(a_stack, f_map)
                i_sets, ovf_i = jax.vmap(lambda a, r: union(a, r, caps.n_i))(
                    a_mem, rhos_s
                )
            with jax.named_scope("cohort.lanes"):
                a_bits = kops.pattern_lane_bits_batched(
                    i_sets.spo, bank_dev, lanes, active, matcher=matcher
                )
                # each member reads its frontier's membership-masked union
                # words; the union STORE itself is one closed-over constant
                # — no per-member store gather, no stacked per-frontier
                # copies
                d_bits = kops.lane_bits_batched(
                    jnp.take(w_stack, f_map, axis=0), lanes, active=active
                )

            with jax.named_scope("cohort.build_index"):
                tgts_u = jax.vmap(build_index)(uniq_s)
            with jax.named_scope("cohort.gather"):
                tgts = tree_gather(tgts_u, tgt_map)
                taus = tree_gather(uniq_s, tgt_map)

            with jax.named_scope("cohort.eval_removed"):
                d_res = jax.vmap(
                    lambda tgt, bits, p: eval_d(d_union, tgt, bits, p)
                )(tgts, d_bits, pats)
            with jax.named_scope("cohort.eval_added"):
                a_res = jax.vmap(
                    lambda i_set, tgt, bits, p: eval_a(i_set, tgt, bits, p)
                )(i_sets, tgts, a_bits, pats)
            with jax.named_scope("cohort.combine"):
                tau1, rho1, out = jax.vmap(
                    lambda dr, ar, t, r, o: combine_side_results(
                        dr, ar, t, r, caps, o
                    )
                )(d_res, a_res, taus, rhos_s, ovf_i)
            with jax.named_scope("cohort.unstack"):
                return (
                    tuple(tree_index(tau1, i) for i in range(nc)),
                    tuple(tree_index(rho1, i) for i in range(nc)),
                    tuple(tree_index(out, i) for i in range(nc)),
                )

        return step_delta

    @jax.jit
    def step(
        d_sets: Tuple[TripleStore, ...],
        d_words: Tuple[jax.Array, ...],
        a_sets: Tuple[TripleStore, ...],
        bank_dev: jax.Array,
        uniq_taus: Tuple[TripleStore, ...],
        f_map: jax.Array,
        tgt_map: jax.Array,
        rhos: Tuple[TripleStore, ...],
        pats: jax.Array,
        lanes: jax.Array,
        active: jax.Array,
    ):
        nc = lanes.shape[0]
        with jax.named_scope("cohort.gather"):
            rhos_s = tree_stack(list(rhos))
            uniq_s = tree_stack(list(uniq_taus))
            d_stack = tree_stack(list(d_sets))
            a_stack = tree_stack(list(a_sets))
            w_stack = jnp.stack(list(d_words))

            # every member reads its own frontier's composed changeset
            d_mem = tree_gather(d_stack, f_map)
            a_mem = tree_gather(a_stack, f_map)
            # I_k = A_f(k) ∪ ρ_k (Def 14)
            i_sets, ovf_i = jax.vmap(lambda a, r: union(a, r, caps.n_i))(
                a_mem, rhos_s
            )
        with jax.named_scope("cohort.lanes"):
            # fused bank match + bitset-lane routing + member mask in one
            # pass (padding members masked to zero so they see no
            # candidates at all)
            a_bits = kops.pattern_lane_bits_batched(
                i_sets.spo, bank_dev, lanes, active, matcher=matcher
            )
            d_bits = kops.lane_bits_batched(
                jnp.take(w_stack, f_map, axis=0), lanes, active=active
            )

        # one build_index(τ) per unique target replica, gathered per member
        with jax.named_scope("cohort.build_index"):
            tgts_u = jax.vmap(build_index)(uniq_s)
        with jax.named_scope("cohort.gather"):
            tgts = tree_gather(tgts_u, tgt_map)
            taus = tree_gather(uniq_s, tgt_map)

        with jax.named_scope("cohort.eval_removed"):
            d_res = jax.vmap(
                lambda d_set, tgt, bits, p: eval_d(d_set, tgt, bits, p)
            )(d_mem, tgts, d_bits, pats)
        with jax.named_scope("cohort.eval_added"):
            a_res = jax.vmap(
                lambda i_set, tgt, bits, p: eval_a(i_set, tgt, bits, p)
            )(i_sets, tgts, a_bits, pats)
        with jax.named_scope("cohort.combine"):
            tau1, rho1, out = jax.vmap(
                lambda dr, ar, t, r, o: combine_side_results(
                    dr, ar, t, r, caps, o
                )
            )(d_res, a_res, taus, rhos_s, ovf_i)
        # unstack inside the trace: per-member outputs, no eager slicing
        with jax.named_scope("cohort.unstack"):
            return (
                tuple(tree_index(tau1, i) for i in range(nc)),
                tuple(tree_index(rho1, i) for i in range(nc)),
                tuple(tree_index(out, i) for i in range(nc)),
            )

    return step


def make_sharded_cohort_step(
    plan: CompiledInterest,
    caps: StepCapacities,
    id_capacity: int,
    mesh,
    *,
    axis: str,
    n_shards: int,
    matcher: Optional[Callable] = None,
    delta: bool = False,
    n_frontiers: int = 1,
) -> Callable:
    """:func:`make_cohort_step` with the member evaluations inside shard_map.

    One cohort pass — all frontiers, all members — distributed over the
    whole mesh, bit-identical to the single-device step by construction:

    * each member's **τ replica is hash-partitioned** across the shards
      (SPO by subject, OPS by object — ``distributed.prepare_target_shards``,
      host-prepared and cached by the broker per (subscription, capacity));
      candidate-assertion probes route to the owner shard via the batched
      all_to_all probe (``distributed.make_routed_probe_batched``, one
      collective per hop spanning the whole member axis).  The partition key
      equals the probe's bound slot, so the owner holds the complete prefix
      range and even the fanout truncation order matches the unpartitioned
      index;
    * the **changeset rows stay replicated** but every shard *owns* only the
      rows whose subject hashes to it: the bank match passes are block-sliced
      across shards (1/n_shards of the match work each), the blocks
      all_gathered and stitched back at static offsets, then each shard
      zeroes the bits of rows it does not own (``row_mask`` in
      :func:`repro.kernels.ops.lane_bits_batched`).  Zero bits mean a row
      contributes no candidates, no signature scatters, and no outputs, so
      the masks partition the whole downstream evaluation without reshaping
      any executable input;
    * signature / edge tables OR-reduce across shards
      (``table_reduce`` hook), so gating decisions are global while
      candidate generation and classification stay shard-local;
    * per-shard outputs re-enter canonical form through one
      ``from_array`` per member (sorted + deduped + compacted), which erases
      the shard decomposition entirely — the merged stores, Δ/Υ algebra, and
      overflow flags match the single-device cohort step bit for bit.

    Signature matches :func:`make_cohort_step` except that the bank words
    are computed in-graph (no ``d_words`` operand) and the per-member τ
    partitions ride alongside the full replicas (which Υ still needs)::

        step(d_sets, a_sets, bank_dev, uniq_taus,
             uniq_tau_spo,   # int32[Nu, n_shards, t_cap, 3] subject-hashed
             uniq_tau_ops,   # int32[Nu, n_shards, t_cap, 3] object-hashed
             f_map, tgt_map, rhos, pats, lanes, active)
          -> (tau1s, rho1s, outs)

    Candidate dedup (``caps.dedup_candidates``) is rejected here: its pool
    overflow is counted per shard over shard-local candidate subsets, so a
    global pool overflow that no single shard sees would skip the broker's
    capacity-doubling retry and break bit-identity exactly in the overflow
    regime. Sharded dedup needs a count-reduce hook (ROADMAP follow-on).

    ``delta=True`` is the delta-chain variant (see :func:`make_cohort_step`):
    the per-frontier ``d_sets`` tuple is replaced by the shared union store
    plus its int32 membership bitmap (bits = the ``n_frontiers`` local
    frontier slots), and each shard's block-split bank pass consumes the
    UNION rows through one segmented match
    (:func:`repro.kernels.ops.pattern_bitmask_words_segmented`) — one
    compare pass per block regardless of how many frontiers fired, with the
    per-frontier word planes composed by masking in registers before the
    block gather-stitch::

        step(d_union,  # TripleStore — union D rows (replicated)
             d_seg,    # int32[|U|] membership bitmap, bit = frontier slot
             a_sets, bank_dev, uniq_taus, uniq_tau_spo, uniq_tau_ops,
             f_map, tgt_map, rhos, pats, lanes, active)
    """
    if caps.dedup_candidates:
        raise ValueError(
            "sharded cohort evaluation requires dedup_candidates == 0 "
            "(per-shard pools cannot detect global dedup overflow)"
        )
    eval_kw = dict(
        id_capacity=id_capacity,
        fanout=caps.fanout,
        pull_capacity=caps.pulls,
        matcher=matcher,
        dedup_candidates=caps.dedup_candidates,
        dynamic_patterns=True,
        probe_impl=make_routed_probe_batched(axis, n_shards),
        table_reduce=make_or_reduce(axis),
    )
    eval_d = make_side_evaluator(plan, out_capacity=caps.n_removed, **eval_kw)
    eval_a = make_side_evaluator(plan, out_capacity=caps.n_i, **eval_kw)

    def added_side_bits(my, i_spo, bank, lanes, active):
        """Block-sliced fused match+route over I rows, block-gathered and
        stitched at static offsets, then subject-hash ownership-masked —
        the per-shard lane-bits discipline shared by both shard bodies."""
        n_i_cap = i_spo.shape[1]
        blk_i = -(-n_i_cap // n_shards)
        starts_i = [min(i * blk_i, n_i_cap - blk_i) for i in range(n_shards)]
        i_loc = jax.lax.dynamic_slice_in_dim(i_spo, my * blk_i, blk_i, axis=1)
        a_loc = kops.pattern_lane_bits_batched(
            i_loc, bank, lanes, active, matcher=matcher
        )
        a_gather = jax.lax.all_gather(a_loc, axis)  # (n, Nc, blk_i)
        a_full = jnp.zeros((i_spo.shape[0], n_i_cap), jnp.uint32)
        for i in range(n_shards):
            a_full = jax.lax.dynamic_update_slice(
                a_full, a_gather[i], (0, starts_i[i])
            )
        own_i = (i_spo[:, :, 0] != PAD) & (i_spo[:, :, 0] % n_shards == my)
        return jnp.where(own_i, a_full, jnp.uint32(0))

    def local_tau_indexes(uq_spo, uq_ops, tgt_map):
        """This shard's τ partitions as per-member indexes (pre-sorted
        host-side), gathered from the unique-replica axis."""
        uqs, uqo = uq_spo[:, 0], uq_ops[:, 0]
        tgts_u = TripleIndex(
            spo=TripleStore(
                spo=uqs,
                n=jnp.sum(uqs[:, :, 0] != PAD, axis=1).astype(jnp.int32),
            ),
            ops=TripleStore(
                spo=uqo,
                n=jnp.sum(uqo[:, :, 0] != PAD, axis=1).astype(jnp.int32),
            ),
        )
        return tree_gather(tgts_u, tgt_map)

    def shard_body(
        d_spo, d_ns, i_spo, i_ns, uq_spo, uq_ops,
        bank, f_map, tgt_map, pats, lanes, active,
    ):
        my = jax.lax.axis_index(axis)
        nfp, d_cap = d_spo.shape[0], d_spo.shape[1]

        # deleted-side bank words: each shard matches one row block; the
        # blocks all_gather at 1/n_shards the full-tensor volume and stitch
        # back at static offsets (the tail shards' clamped blocks overlap,
        # but overlapping rows carry identical words, so overwrite is exact)
        blk_d = -(-d_cap // n_shards)
        starts_d = [min(i * blk_d, d_cap - blk_d) for i in range(n_shards)]
        d_loc = jax.lax.dynamic_slice_in_dim(d_spo, my * blk_d, blk_d, axis=1)
        w_loc = jax.vmap(
            lambda s: kops.pattern_bitmask_words(s, bank, matcher=matcher)
        )(d_loc)
        w_gather = jax.lax.all_gather(w_loc, axis)  # (n, nfp, blk_d, W)
        d_words = jnp.zeros((nfp, d_cap, w_loc.shape[-1]), jnp.uint32)
        for i in range(n_shards):
            d_words = jax.lax.dynamic_update_slice_in_dim(
                d_words, w_gather[i], starts_d[i], axis=1
            )

        # per-member views + subject-hash ownership masks
        d_mem_spo = jnp.take(d_spo, f_map, axis=0)
        own_d = (d_mem_spo[:, :, 0] != PAD) & (
            d_mem_spo[:, :, 0] % n_shards == my
        )
        d_bits = kops.lane_bits_batched(
            jnp.take(d_words, f_map, axis=0), lanes,
            active=active, row_mask=own_d,
        )

        a_bits = added_side_bits(my, i_spo, bank, lanes, active)
        tgt_mem = local_tau_indexes(uq_spo, uq_ops, tgt_map)
        d_store = TripleStore(spo=d_mem_spo, n=jnp.take(d_ns, f_map, axis=0))
        i_store = TripleStore(spo=i_spo, n=i_ns)
        d_res = jax.vmap(
            lambda m, t, b, p: eval_d(m, t, b, p)
        )(d_store, tgt_mem, d_bits, pats)
        a_res = jax.vmap(
            lambda m, t, b, p: eval_a(m, t, b, p)
        )(i_store, tgt_mem, a_bits, pats)
        return jax.tree.map(lambda t: t[None], (d_res, a_res))

    def shard_body_delta(
        du_spo, du_n, d_seg, i_spo, i_ns, uq_spo, uq_ops,
        bank, f_map, tgt_map, pats, lanes, active,
    ):
        my = jax.lax.axis_index(axis)
        d_cap = du_spo.shape[0]
        nc = lanes.shape[0]

        # union-side bank words: ONE segmented match per row block (the
        # per-frontier planes are composed by masking in registers), blocks
        # all_gathered at 1/n_shards the volume and stitched at static
        # offsets exactly like the stacked pass (overlapping clamped tail
        # blocks carry identical planes, so overwrite is exact)
        blk_d = -(-d_cap // n_shards)
        starts_d = [min(i * blk_d, d_cap - blk_d) for i in range(n_shards)]
        rows_loc = jax.lax.dynamic_slice_in_dim(
            du_spo, my * blk_d, blk_d, axis=0
        )
        seg_loc = jax.lax.dynamic_slice_in_dim(
            d_seg, my * blk_d, blk_d, axis=0
        )
        w_loc = kops.pattern_bitmask_words_segmented(
            rows_loc, bank, seg_loc, n_frontiers, matcher=matcher
        )  # (F, blk_d, W)
        w_gather = jax.lax.all_gather(w_loc, axis)  # (n, F, blk_d, W)
        d_words = jnp.zeros(
            (n_frontiers, d_cap, w_loc.shape[-1]), jnp.uint32
        )
        for i in range(n_shards):
            d_words = jax.lax.dynamic_update_slice_in_dim(
                d_words, w_gather[i], starts_d[i], axis=1
            )

        # every member evaluates the same union rows; subject-hash
        # ownership masks partition the downstream work across shards
        own_d = (du_spo[:, 0] != PAD) & (du_spo[:, 0] % n_shards == my)
        d_bits = kops.lane_bits_batched(
            jnp.take(d_words, f_map, axis=0), lanes,
            active=active, row_mask=jnp.broadcast_to(own_d[None], (nc, d_cap)),
        )

        a_bits = added_side_bits(my, i_spo, bank, lanes, active)
        tgt_mem = local_tau_indexes(uq_spo, uq_ops, tgt_map)
        d_store = TripleStore(spo=du_spo, n=du_n)  # shared union store
        i_store = TripleStore(spo=i_spo, n=i_ns)
        d_res = jax.vmap(
            lambda t, b, p: eval_d(d_store, t, b, p)
        )(tgt_mem, d_bits, pats)
        a_res = jax.vmap(
            lambda m, t, b, p: eval_a(m, t, b, p)
        )(i_store, tgt_mem, a_bits, pats)
        return jax.tree.map(lambda t: t[None], (d_res, a_res))

    store_spec = TripleStore(spo=P(axis), n=P(axis))
    side_spec = SideResult(
        interesting=store_spec, potential=store_spec, pulls=store_spec,
        overflow=P(axis),
    )
    rep = P()
    if delta:
        sharded_passes = jax.shard_map(
            shard_body_delta,
            mesh=mesh,
            check_vma=False,
            in_specs=(
                rep, rep, rep, rep, rep,
                P(None, axis), P(None, axis),
                rep, rep, rep, rep, rep, rep,
            ),
            out_specs=(side_spec, side_spec),
        )
    else:
        sharded_passes = jax.shard_map(
            shard_body,
            mesh=mesh,
            check_vma=False,
            in_specs=(
                rep, rep, rep, rep,
                P(None, axis), P(None, axis),
                rep, rep, rep, rep, rep, rep,
            ),
            out_specs=(side_spec, side_spec),
        )

    # every operand and result has one fixed sharding — τ partitions split
    # over the mesh, everything else replicated — so the broker's cached
    # AOT executable is always called with the shardings it was lowered
    # for, whether an operand is fresh host data or a previous fire's τ/ρ
    replicated = NamedSharding(mesh, P())
    tau_parts = NamedSharding(mesh, P(None, axis))

    def sharded_jit(fn, n_args: int, parts_at: Tuple[int, int]):
        return jax.jit(
            fn,
            in_shardings=tuple(
                tau_parts if i in parts_at else replicated
                for i in range(n_args)
            ),
            out_shardings=replicated,
        )

    def merge_side(res: SideResult, out_cap: int, pull_cap: int) -> SideResult:
        """Union the per-shard results back into canonical per-member form."""

        def merge_store(st: TripleStore, cap: int):
            rows = jnp.swapaxes(st.spo, 0, 1).reshape(st.spo.shape[1], -1, 3)
            return jax.vmap(lambda r: from_array(r, cap))(rows)

        inter, ovf_i = merge_store(res.interesting, out_cap)
        pot, ovf_q = merge_store(res.potential, out_cap)
        pulls, ovf_p = merge_store(res.pulls, pull_cap)
        overflow = jnp.any(res.overflow, axis=0) | ovf_i | ovf_q | ovf_p
        return SideResult(
            interesting=inter, potential=pot, pulls=pulls, overflow=overflow
        )

    if delta:

        def step_delta(
            d_union: TripleStore,
            d_seg: jax.Array,
            a_sets: Tuple[TripleStore, ...],
            bank_dev: jax.Array,
            uniq_taus: Tuple[TripleStore, ...],
            uniq_tau_spo: jax.Array,
            uniq_tau_ops: jax.Array,
            f_map: jax.Array,
            tgt_map: jax.Array,
            rhos: Tuple[TripleStore, ...],
            pats: jax.Array,
            lanes: jax.Array,
            active: jax.Array,
        ):
            nc = lanes.shape[0]
            rhos_s = tree_stack(list(rhos))
            uniq_s = tree_stack(list(uniq_taus))
            a_stack = tree_stack(list(a_sets))
            a_mem = tree_gather(a_stack, f_map)
            i_sets, ovf_i = jax.vmap(lambda a, r: union(a, r, caps.n_i))(
                a_mem, rhos_s
            )
            d_res_sh, a_res_sh = sharded_passes(
                d_union.spo, d_union.n, d_seg, i_sets.spo, i_sets.n,
                uniq_tau_spo, uniq_tau_ops,
                bank_dev, f_map, tgt_map, pats, lanes, active,
            )
            d_res = merge_side(d_res_sh, caps.n_removed, caps.pulls)
            a_res = merge_side(a_res_sh, caps.n_i, caps.pulls)
            taus = tree_gather(uniq_s, tgt_map)
            tau1, rho1, out = jax.vmap(
                lambda dr, ar, t, r, o: combine_side_results(
                    dr, ar, t, r, caps, o
                )
            )(d_res, a_res, taus, rhos_s, ovf_i)
            return (
                tuple(tree_index(tau1, i) for i in range(nc)),
                tuple(tree_index(rho1, i) for i in range(nc)),
                tuple(tree_index(out, i) for i in range(nc)),
            )

        return sharded_jit(step_delta, 13, (5, 6))

    def step(
        d_sets: Tuple[TripleStore, ...],
        a_sets: Tuple[TripleStore, ...],
        bank_dev: jax.Array,
        uniq_taus: Tuple[TripleStore, ...],
        uniq_tau_spo: jax.Array,
        uniq_tau_ops: jax.Array,
        f_map: jax.Array,
        tgt_map: jax.Array,
        rhos: Tuple[TripleStore, ...],
        pats: jax.Array,
        lanes: jax.Array,
        active: jax.Array,
    ):
        nc = lanes.shape[0]
        rhos_s = tree_stack(list(rhos))
        uniq_s = tree_stack(list(uniq_taus))
        d_stack = tree_stack(list(d_sets))
        a_stack = tree_stack(list(a_sets))
        a_mem = tree_gather(a_stack, f_map)
        i_sets, ovf_i = jax.vmap(lambda a, r: union(a, r, caps.n_i))(
            a_mem, rhos_s
        )
        d_res_sh, a_res_sh = sharded_passes(
            d_stack.spo, d_stack.n, i_sets.spo, i_sets.n,
            uniq_tau_spo, uniq_tau_ops,
            bank_dev, f_map, tgt_map, pats, lanes, active,
        )
        d_res = merge_side(d_res_sh, caps.n_removed, caps.pulls)
        a_res = merge_side(a_res_sh, caps.n_i, caps.pulls)
        taus = tree_gather(uniq_s, tgt_map)
        tau1, rho1, out = jax.vmap(
            lambda dr, ar, t, r, o: combine_side_results(dr, ar, t, r, caps, o)
        )(d_res, a_res, taus, rhos_s, ovf_i)
        return (
            tuple(tree_index(tau1, i) for i in range(nc)),
            tuple(tree_index(rho1, i) for i in range(nc)),
            tuple(tree_index(out, i) for i in range(nc)),
        )

    return sharded_jit(step, 12, (4, 5))


def _assemble_cohort_statics(
    pat_rows: Sequence[np.ndarray],
    lane_rows: Sequence[Sequence[int]],
    tgt: Sequence[int],
    fmap: Sequence[int],
    ncp: int,
    nt: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """(f_map, tgt_map, pats, lanes, active) device inputs for one padded
    cohort.

    Single definition of the dummy-lane encoding (zeros + active=False),
    shared by the Broker's cached path and the frozen make_broker_step
    wrapper so the two can never diverge.
    """
    nm = len(pat_rows)
    f_map = np.zeros((ncp,), np.int32)
    tgt_map = np.zeros((ncp,), np.int32)
    pats = np.zeros((ncp, nt, 3), np.int32)
    lanes = np.zeros((ncp, nt), np.int32)
    active = np.zeros((ncp,), bool)
    for pos in range(nm):
        f_map[pos] = fmap[pos]
        tgt_map[pos] = tgt[pos]
        pats[pos] = pat_rows[pos]
        lanes[pos] = np.asarray(lane_rows[pos], np.int32)
        active[pos] = True
    return (
        jnp.asarray(f_map),
        jnp.asarray(tgt_map),
        jnp.asarray(pats),
        jnp.asarray(lanes),
        jnp.asarray(active),
    )


@partial(jax.jit, static_argnames=("slots",))
def _seg_local_bits(seg: jax.Array, slots: tuple) -> jax.Array:
    """Remap a frontier-chain membership bitmap from global frontier
    indices to a cohort's dense local frontier slots: output bit ``l`` is
    input bit ``slots[l]``. The sharded delta step's segmented pass reads
    local slots (they key ``f_map``), while the chain is built once per
    flush over the global frontier order."""
    out = jnp.zeros_like(seg)
    for l, fi in enumerate(slots):
        out = out | (((seg >> fi) & 1) << l)
    return out


_EMPTY_STORES: Dict[tuple, TripleStore] = {}


def _empty_cached(capacity: int, device=None) -> TripleStore:
    """Shared immutable empty store per (capacity, device) — cohort padding
    lanes; the placed broker keeps one copy committed per mesh device so
    padding slots never re-transfer at fire time."""
    key = (capacity, device)
    store = _EMPTY_STORES.get(key)
    if store is None:
        store = empty(capacity)
        if device is not None:
            store = jax.device_put(store, device)
        store = _EMPTY_STORES.setdefault(key, store)
    return store


_EMPTY_OUTPUTS: Dict[StepCapacities, EvalOutputs] = {}


def _empty_outputs(caps: StepCapacities) -> EvalOutputs:
    """Canonical all-empty :class:`EvalOutputs` at one capacity family.

    The broker's empty-batch fast path returns this for a fired frontier
    whose composed changeset has zero rows on both sides and whose
    subscribers hold empty potential sets ρ — nothing was added, removed
    or pending, so nothing propagates and no executable runs. Store
    capacities match what the full evaluation would produce (``r``/``r_i``
    at ``n_removed``, ``r'`` at ``pulls``, ``a`` at ``n_i + pulls``,
    ``a_i`` at ``n_i``), so downstream consumers see identical shapes.
    """
    out = _EMPTY_OUTPUTS.get(caps)
    if out is None:
        out = _EMPTY_OUTPUTS.setdefault(
            caps,
            EvalOutputs(
                r=_empty_cached(caps.n_removed),
                r_i=_empty_cached(caps.n_removed),
                r_prime=_empty_cached(caps.pulls),
                a=_empty_cached(caps.n_i + caps.pulls),
                a_i=_empty_cached(caps.n_i),
                overflow=jnp.zeros((), bool),
            ),
        )
    return out


def _padded_bank_dev(patterns: np.ndarray) -> jax.Array:
    """Pad a bank array to a power-of-two (>= 32) lane count; the padding
    rows are all-PAD patterns that can never match a valid triple."""
    n_pad = max(32, next_pow2(patterns.shape[0]))
    out = np.full((n_pad, 3), PAD, np.int32)
    out[: patterns.shape[0]] = patterns
    return jnp.asarray(out)


def make_broker_step(
    bank: PatternBank,
    plans: Sequence[CompiledInterest],
    caps_list: Sequence[StepCapacities],
    id_capacities: Sequence[int],
    matcher: Optional[Callable] = None,
) -> Callable:
    """(D, A, (τ_k,), (ρ_k,)) -> ((τ'_k,), (ρ'_k,), (out_k,)) for a frozen
    subscriber set — the PR 1 entry point, now a thin composition of
    :func:`make_cohort_step` executables over a padded bank.

    Kept for golden/property tests and one-shot uses; the :class:`Broker`
    manages the same cohort steps through its executable cache instead, so
    membership churn does not rebuild unrelated cohorts.
    """
    n_subs = len(plans)
    assert n_subs == len(caps_list) == len(id_capacities) == len(bank.lanes)
    bank_dev = _padded_bank_dev(np.asarray(bank.patterns, np.int32))

    groups: Dict[tuple, List[int]] = {}
    for k, (plan, caps, id_cap) in enumerate(
        zip(plans, caps_list, id_capacities)
    ):
        key = (_plan_shape_key(plan), caps, id_cap)
        groups.setdefault(key, []).append(k)
    cohorts = [
        (tuple(idxs), plans[idxs[0]], caps_list[idxs[0]], id_capacities[idxs[0]])
        for idxs in groups.values()
    ]
    steps = [
        make_cohort_step(plan, caps, id_cap, matcher=matcher)
        for _, plan, caps, id_cap in cohorts
    ]
    # membership is frozen here, so the per-cohort static inputs (pattern
    # values, lane maps, member mask, identity tgt_map: no τ sharing in the
    # one-shot wrapper, single-frontier f_map) upload once
    statics = [
        _assemble_cohort_statics(
            [plans[k].patterns for k in idxs],
            [bank.lanes[k] for k in idxs],
            list(range(len(idxs))),
            [0] * len(idxs),
            next_pow2(len(idxs)),
            plan.n_total,
        )
        for idxs, plan, caps, _ in cohorts
    ]

    def step(
        d_set: TripleStore,
        a_set: TripleStore,
        taus: Tuple[TripleStore, ...],
        rhos: Tuple[TripleStore, ...],
    ):
        # fused pass 1: deleted side, shared by every cohort
        d_words = kops.pattern_bitmask_words(
            d_set.spo, bank_dev, matcher=matcher
        )
        tau1s = [None] * n_subs
        rho1s = [None] * n_subs
        outs = [None] * n_subs
        for (idxs, plan, caps, _), fn, (
            f_map,
            tgt_map,
            pats,
            lanes,
            active,
        ) in zip(cohorts, steps, statics):
            nm = len(idxs)
            ncp = next_pow2(nm)
            taus_c = tuple(taus[k] for k in idxs) + (
                _empty_cached(caps.tau),
            ) * (ncp - nm)
            rhos_c = tuple(rhos[k] for k in idxs) + (
                _empty_cached(caps.rho),
            ) * (ncp - nm)
            tau1_c, rho1_c, out_c = fn(
                (d_set,),
                (d_words,),
                (a_set,),
                bank_dev,
                taus_c,
                f_map,
                tgt_map,
                rhos_c,
                pats,
                lanes,
                active,
            )
            for pos, k in enumerate(idxs):
                tau1s[k] = tau1_c[pos]
                rho1s[k] = rho1_c[pos]
                outs[k] = out_c[pos]
        return tuple(tau1s), tuple(rho1s), tuple(outs)

    return step


class BrokerSubscription:
    """One registered interest inside the broker: plan, caps, policy, τ, ρ."""

    _serial_counter = itertools.count()

    def __init__(
        self,
        expr: InterestExpr,
        dictionary: Dictionary,
        caps: StepCapacities,
        policy: PushPolicy | None = None,
    ):
        self.expr = expr
        self.dictionary = dictionary
        self.caps = caps
        self.policy = policy if policy is not None else PushPolicy()
        # monotonic identity for host-side cache signatures (unlike id(),
        # never reused after garbage collection); plan_version tracks
        # recompiles the same way
        self.serial = next(BrokerSubscription._serial_counter)
        self.plan_version = 0
        self.plan = compile_interest(expr, dictionary)
        # cohort-grouping key, cached: rebuilding it per fire costs O(plan
        # rows) python per subscriber, which dominates large-fanout flushes
        self.shape_key = _plan_shape_key(self.plan)
        self.id_capacity = dictionary.id_capacity * caps.id_headroom
        self.tau = empty(caps.tau)
        self.rho = empty(caps.rho)
        # bumped on every τ assignment; keys the broker's τ-shard partition
        # cache, so only touched replicas ever re-partition
        self.tau_version = 0
        self.lanes: Tuple[int, ...] = ()  # bank lane map (broker-managed)
        self.since = 1  # first unconsumed changeset id (broker-managed)
        self.last_push_t = time.perf_counter()
        # shared-τ lineage: subscriptions attached to one target replica
        # share `share_tag`; `epoch` hashes the consumption history, so two
        # subscriptions share a build_index(τ) in the cohort step exactly
        # when their replica state is provably identical.
        self.share_tag: object = self
        self.epoch: int = 0
        # canonical lane-group signature (canonical-form key, caps, policy)
        # — the broker's automatic exact-duplicate collapse index; None when
        # the lattice is off
        self.canon_sig: Optional[tuple] = None
        # durable identity: broker-assigned, journaled, stable across
        # recovery (unlike `serial`, which is process-local)
        self.jid: int = -1
        # per-subscriber delivery callback (overrides the channel default);
        # ephemeral — not journaled, re-attach after recover()
        self.transport: Optional[Callable] = None

    def recompile(self, caps: StepCapacities | None = None) -> None:
        """Refresh plan/capacities after dictionary or capacity growth."""
        if caps is not None:
            self.caps = caps
        self.plan_version += 1
        self.plan = compile_interest(self.expr, self.dictionary)
        self.shape_key = _plan_shape_key(self.plan)
        self.id_capacity = self.dictionary.id_capacity * self.caps.id_headroom
        self.tau, _ = union(empty(self.caps.tau), self.tau, self.caps.tau)
        self.rho, _ = union(empty(self.caps.rho), self.rho, self.caps.rho)
        self.tau_version += 1

    def init_target(self, triples: np.ndarray) -> bool:
        """Load the initial RDFSlice-style subset into τ. True if caps grew."""
        grew = False
        while True:
            store, overflow = store_from_np(triples, self.caps.tau)
            if not overflow:
                self.tau = store
                self.tau_version += 1
                return grew
            self.recompile(self.caps.doubled())
            grew = True


@dataclasses.dataclass
class BrokerStats:
    """Per-call accounting for the fused pass (all evaluated subscribers)."""

    changeset_id: int
    n_subscribers: int
    n_lanes: int  # allocated bank lanes (incl. tombstones)
    n_lanes_raw: int  # sum of per-interest pattern counts
    total_removed: int
    total_added: int
    interesting_removed: int  # Σ_k |r_k| over evaluated subscribers
    interesting_added: int  # Σ_k |a_k| over evaluated subscribers
    elapsed_s: float  # wall time incl. rejit_s
    rejit_s: float = 0.0  # executable compile / bank rebuild time
    n_evaluated: int = 0  # subscribers whose policy fired
    n_deferred: int = 0  # subscribers whose batch kept accumulating
    n_cohort_passes: int = 0  # cohort executables invoked
    batch_grows: int = 0  # cumulative ChangesetBatch pow2 doublings
    batch_shrinks: int = 0  # cumulative ChangesetBatch decay re-homes
    # D-side bank-match volume this call: rows run through a match pass vs
    # the distinct rows across the fired frontiers. The stacked pass
    # re-matches shared suffix rows once per frontier (matched ≈ F × the
    # union on overlap-heavy streams); the delta chain matches each
    # distinct row once (matched == distinct), making dedup efficacy
    # directly observable. Counts repeat on capacity-overflow retries
    # (honest work accounting); single-changeset frontiers report their
    # raw-row upper bound, mirroring the capacity guards.
    rows_matched: int = 0
    rows_distinct: int = 0
    # lattice efficacy this call: cohort slots actually evaluated vs
    # subscriber deliveries those slots fanned out to. With the
    # subsumption lattice on, identical interests collapse into one lane
    # group, so distinct_interests tracks the distinct-interest pool while
    # fanout_copies tracks subscribers — their ratio is the O(1)-copies
    # win. Lattice off: one slot per subscriber, so the two are equal.
    # Counts repeat on capacity-overflow retries (honest work accounting).
    distinct_interests: int = 0
    fanout_copies: int = 0
    # unified sequence clock after this call (journal seq when journaling:
    # ingests, subscribes, and committed fires each consume one tick)
    seq: int = 0
    # fires this call that fell back to the per-interest seed path after
    # the bounded overflow-retry ceiling (degraded, still bit-identical)
    degraded_fires: int = 0
    # backend compiles during the call, persistent-cache loads and eager
    # operations included (tracing.compile_count: process-wide, so a
    # compile on another thread meanwhile counts too)
    compiles: int = 0


@dataclasses.dataclass
class _FrontierInput:
    """One fired consumption frontier, abstracted over residency.

    ``d_store`` / ``a_store`` produce the frontier's composed (D, A) at a
    requested capacity; the device-resident path re-homes sorted device
    stores (no transfer), the baseline path re-uploads host arrays.
    ``d_rows`` / ``a_rows`` bound the valid rows for the capacity guards.
    ``since`` is the frontier's first composed changeset id (its age — the
    delta chain picks the oldest fired frontier as the distinct-row
    union), and ``d_native`` hands out the composed D store at its native
    batch capacity for chain membership probes (None on the host
    round-trip baseline, which never chains).
    """

    idxs: List[int]
    d_rows: int
    a_rows: int
    d_store: Callable[[int], TripleStore]
    a_store: Callable[[int], TripleStore]
    since: int = 0
    d_native: Optional[Callable[[], TripleStore]] = None


def _stores_equal(a: TripleStore, b: TripleStore) -> bool:
    """Bit-equality of two canonical stores' valid rows (capacity-agnostic).

    Stores are lex-sorted and deduplicated, so set equality and row-array
    equality coincide; the common all-empty case short-circuits on the row
    counts without pulling the arrays to host.
    """
    if a is b:
        return True
    na, nb = int(a.n), int(b.n)
    if na != nb:
        return False
    if na == 0:
        return True
    return bool(np.array_equal(to_numpy(a), to_numpy(b)))


def _as_rows(arr) -> np.ndarray:
    """Normalize a changeset side to an int32 (N, 3) array; empty-friendly."""
    out = np.asarray(arr, dtype=np.int32)
    if out.size == 0:
        return np.zeros((0, 3), np.int32)
    if out.ndim != 2 or out.shape[1] != 3:
        raise ValueError(f"expected (N, 3) triples, got {out.shape}")
    return out


class Broker:
    """Host orchestrator batching all registered interests into fused passes.

    Drop-in counterpart of :class:`~repro.core.propagation.IrapEngine` for
    the many-subscriber regime: ``subscribe`` replaces ``register_interest``
    and ``process_changeset`` evaluates every *due* subscription (per its
    :class:`PushPolicy`) through cached per-cohort executables.

    ``cache_executables=False`` reproduces the PR 1 lifecycle — every
    membership change discards all compiled steps — and exists as the
    baseline for ``benchmarks/broker_churn.py``.

    ``deferred_device_resident=False`` reproduces the PR 2 deferred path —
    every scheduled fire round-trips its composed batch device→host→device
    and distinct frontiers run one sequential pass each — and exists as a
    baseline for ``benchmarks/broker_flush.py``. The default keeps composed
    batches on device end-to-end (:meth:`ChangesetBatch.device_stores` +
    :func:`repro.core.triples.rehome`) and stacks same-shape cohorts fired
    from different frontiers into one batched executable call.

    ``delta_frontiers=False`` reproduces the PR 3 *stacked* multi-frontier
    flush — one deleted-side bank pass per fired frontier, per-frontier
    store tuples gathered per member — and exists as the other baseline
    for ``benchmarks/broker_flush.py``. The default delta-encodes
    overlapping fired frontiers (module docstring, layer 4): one segmented
    bank pass over the distinct-row union, per-frontier words by
    membership masks, one shared union store per cohort — homed at the
    union's own pow2 row bucket rather than the per-subscriber guard
    capacity, so the D-side evaluation shapes track the distinct row
    volume the chain just proved. Dedup efficacy
    is observable through ``BrokerStats.rows_matched`` /
    ``rows_distinct`` (and the cumulative ``Broker.rows_matched`` /
    ``rows_distinct`` totals).

    ``subsume_interests=False`` reproduces the PR 5 *per-subscriber*
    broker — raw expressions, opt-in ``share_target`` only, one cohort
    slot per subscriber, no virtual bank lanes — and exists as the
    baseline for ``benchmarks/broker_fanout.py``. The default builds the
    interest-subsumption lattice (module docstring, layer 3): canonical
    expressions, automatic exact-duplicate lane groups with host-side
    result fanout, and containment-refined virtual lanes
    (:func:`repro.kernels.ops.lane_refine`). Lattice efficacy is
    observable through ``BrokerStats.distinct_interests`` /
    ``fanout_copies`` (and the cumulative broker totals of the same
    names).

    ``mesh`` (a 1-D jax device mesh) turns on multi-device evaluation:

    * by default every cohort is *placed* on one mesh device per the
      :class:`~repro.core.distributed.CohortPlacement` policy in
      ``placement`` (round-robin / load-balanced / pinned); the frontier
      pass dispatches cohort calls grouped by device, so same-fire cohorts
      run concurrently across the mesh and each cohort's τ/ρ state stays
      resident on its device between fires;
    * ``shard_cohorts=True`` instead runs every cohort pass *inside*
      shard_map over the whole mesh (:func:`make_sharded_cohort_step`):
      τ replicas hash-partition across the shards (partitions cached per
      (subscription, τ-version, capacity) so churn never re-partitions
      untouched replicas), bank matching block-splits across shards with
      block-gathered reassembly, and candidate probes route via all_to_all.

    Both modes are asserted bit-identical to the single-device broker
    (tests/test_broker_sharded.py, benchmarks/broker_shard.py). Per-frontier
    composed batches remain the delivery windows — the natural cross-host
    boundary for a future multi-process deployment.
    """

    def __init__(
        self,
        dictionary: Dictionary | None = None,
        matcher: Optional[Callable] = None,
        cache_executables: bool = True,
        deferred_device_resident: bool = True,
        delta_frontiers: bool = True,
        subsume_interests: bool = True,
        mesh=None,
        placement: CohortPlacement | None = None,
        shard_cohorts: bool = False,
        decay_patience: int = 2,
        journal: ChangesetJournal | None = None,
        channel=None,
        max_fire_retries: int = 8,
    ):
        self.dictionary = dictionary if dictionary is not None else Dictionary()
        self.matcher = matcher
        self.subs: List[BrokerSubscription] = []
        self.stats: List[BrokerStats] = []
        self.subsume_interests = subsume_interests
        self.bank = self._new_bank()
        # canonical lane-group signature -> lineage root (auto-collapse)
        self._share_index: Dict[tuple, BrokerSubscription] = {}
        self.cache_executables = cache_executables
        self.deferred_device_resident = deferred_device_resident
        self.delta_frontiers = delta_frontiers
        self.mesh = mesh
        self.shard_cohorts = shard_cohorts
        if mesh is not None:
            if len(mesh.axis_names) != 1:
                raise ValueError("Broker expects a 1-D device mesh")
            self._shard_axis = mesh.axis_names[0]
            self._n_shards = int(mesh.shape[self._shard_axis])
            self._devices = list(np.asarray(mesh.devices).reshape(-1))
        else:
            self._shard_axis = None
            self._n_shards = 1
            self._devices = []
        self.placement = (
            placement if placement is not None else CohortPlacement()
        )
        self.decay_patience = decay_patience
        self.device_passes: Dict[int, int] = {}  # device idx -> cohort passes
        self.batch_grows = 0  # ChangesetBatch pow2 doublings (cumulative)
        self.batch_shrinks = 0  # ChangesetBatch decay re-homes (cumulative)
        # cumulative D-side match volume vs distinct rows (dedup efficacy)
        self.rows_matched = 0
        self.rows_distinct = 0
        self._rows_matched_acc = 0
        self._rows_distinct_acc = 0
        # cumulative lattice efficacy: cohort slots evaluated vs subscriber
        # deliveries fanned out from them (see BrokerStats)
        self.distinct_interests = 0
        self.fanout_copies = 0
        self._distinct_acc = 0
        self._fanout_acc = 0
        # Σ plan.n_total over live subscriptions, maintained incrementally
        # (recomputing it per stats record is O(subscribers) python)
        self._lanes_raw = 0
        self._grow_seen: Dict[int, int] = {}  # frontier id -> folded grows
        # τ-shard partitions per (sub serial, τ version, cap, n_shards)
        self._tau_parts_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._empty_parts_cache: Dict[tuple, jax.Array] = {}
        self._bank_dev_for: Dict[tuple, jax.Array] = {}  # (version, dev idx)
        # LRU-bounded: superseded keys (outgrown caps, old padded sizes)
        # eventually fall out instead of holding XLA executables forever;
        # evicting a hot key only costs a recompile, never correctness
        self._exec_cache: "OrderedDict[tuple, Callable]" = OrderedDict()
        self.exec_cache_max = 128
        # membership-static device arrays per (cohort, membership signature)
        self._static_arrays_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        # exact consumption-history interning: (epoch, first, last) -> new
        # epoch id, so equal histories — and only equal histories — share
        # an epoch (no probabilistic hash comparison). Only subscriptions
        # whose share_tag is held by >= 2 members track epochs (it exists
        # purely to group shared-τ replicas), ids are monotonic so pruning
        # can never alias a held epoch, and unreachable entries are pruned
        # at a size threshold.
        self._epoch_intern: Dict[tuple, int] = {}
        self._epoch_next = 0
        self.epoch_intern_max = 4096
        self._bank_dev: jax.Array | None = None
        # real-rows-only padded bank + (parents, residual) refine operands
        # for the deleted-side words pass (== _bank_dev / None without
        # virtual lanes); refreshed together with _bank_dev per version
        self._bank_real_dev: jax.Array | None = None
        self._refine_dev: Optional[Tuple[jax.Array, jax.Array]] = None
        self._bank_version = -1
        self._batches: Dict[int, ChangesetBatch] = {}
        # durability tier (module docstring, layer 6): one monotonic
        # sequence clock shared by stats, frontiers, and the journal —
        # subscribe/unsubscribe/ingest/committed-fire each consume a tick
        # whether or not a journal is attached, so journal-on and
        # journal-off brokers assign identical ids
        self.journal = journal
        self.channel = channel
        self.max_fire_retries = max_fire_retries
        self._seq = journal.last_seq if journal is not None else 0
        self._last_cid = 0  # seq of the last ingested changeset
        self._jid_next = 0  # durable subscriber ids (journaled)
        self._last_snapshot_seq = 0
        self._snapshot_keep_from = 1  # compaction floor (advanced by snapshot)
        self._replaying = False  # recovery replay: suppress journal/delivery
        self.degraded_fires = 0  # cumulative seed-path fallback fires
        self._degraded_acc = 0
        self._rejit_acc = 0.0
        self._compiles0 = 0  # tracing.compile_count() at the call's start
        self.rejit_count = 0  # executable compiles (cohort + bank words)
        self.cohort_compiles: Dict[tuple, int] = {}  # per cohort key

    # -- interest manager ---------------------------------------------------

    def _new_bank(self):
        return (
            SubsumptionBank() if self.subsume_interests
            else IncrementalPatternBank()
        )

    def subscribe(
        self,
        expr: InterestExpr,
        caps: StepCapacities = StepCapacities(),
        initial_target: np.ndarray | None = None,
        policy: PushPolicy | None = None,
        share_target: bool = False,
        transport: Optional[Callable] = None,
        _jid: int | None = None,
    ) -> BrokerSubscription:
        """Register an interest; only its own cohort will (re)compile.

        With the subsumption lattice on (the default) the expression is
        replaced by its canonical form
        (:func:`repro.core.interest.canonicalize_expr`) before compiling, so
        expressions differing only in pattern order / variable naming share
        plans, bank lanes, and — via the automatic lineage join below —
        cohort slots. A new subscription auto-joins an existing lane group
        when its canonical key, capacities, policy, consumption frontier,
        and τ/ρ state are all provably equal to the group root's (the join
        is then a pure optimization: the evaluation it skips would have
        produced bit-identical results); from then on the group occupies
        one cohort slot per fire and results fan out to every member.

        ``share_target=True`` keeps its shared-replica semantics: the new
        subscription *adopts* an existing identical subscription's current
        τ/ρ state and frontier (rather than requiring them to match), the
        paper's many-readers-of-one-target-dataset case. Falls back to an
        independent subscription when no compatible root exists.
        """
        if self.shard_cohorts and caps.dedup_candidates:
            raise ValueError(
                "shard_cohorts=True requires caps.dedup_candidates == 0 "
                "(see make_sharded_cohort_step)"
            )
        # WAL discipline: consume one sequence tick and journal the call's
        # raw arguments *before* mutating broker state, so the durable
        # prefix at any boundary is replayable (replay re-runs this method
        # with the recorded args and lands on identical state)
        jid = self._jid_next if _jid is None else _jid
        self._seq += 1
        if self.journal is not None and not self._replaying:
            arrays = {}
            if initial_target is not None and np.asarray(initial_target).size:
                arrays["initial_target"] = np.asarray(
                    initial_target, np.int32
                )
            self.journal.append(
                "subscribe",
                meta={
                    "jid": jid,
                    "expr": _expr_to_json(expr),
                    "caps": _caps_to_json(caps),
                    "policy": _policy_to_json(policy),
                    "share_target": bool(share_target),
                },
                arrays=arrays,
                seq=self._seq,
            )
        self._jid_next = max(self._jid_next, jid + 1)
        canon_key = None
        if self.subsume_interests:
            expr, canon_key = canonicalize_expr(expr)
        sub = BrokerSubscription(expr, self.dictionary, caps, policy=policy)
        sub.jid = jid
        sub.transport = transport
        sub.since = self._seq + 1
        root = self._find_share_root(sub) if share_target else None
        if root is not None:
            sub.tau, sub.rho = root.tau, root.rho
            sub.share_tag, sub.epoch = root.share_tag, root.epoch
            sub.since, sub.last_push_t = root.since, root.last_push_t
        elif initial_target is not None and initial_target.size:
            sub.init_target(initial_target)
        if canon_key is not None:
            # init_target may have doubled caps, so the signature reads the
            # final capacities
            sub.canon_sig = (canon_key, sub.caps, sub.policy)
            if root is None:
                auto = self._auto_join_root(sub)
                if auto is not None:
                    sub.tau, sub.rho = auto.tau, auto.rho
                    sub.share_tag, sub.epoch = auto.share_tag, auto.epoch
            self._share_index.setdefault(sub.canon_sig, sub)
        sub.lanes = self.bank.add_plan(sub.plan)
        self.subs.append(sub)
        self._lanes_raw += sub.plan.n_total
        if not self.cache_executables:
            self._exec_cache.clear()  # PR 1 full-rebuild baseline behavior
        return sub

    def _auto_join_root(
        self, sub: BrokerSubscription
    ) -> BrokerSubscription | None:
        """The lane-group root ``sub`` may join without changing semantics.

        Joining shares the root's τ-lineage tag and epoch, which is sound
        exactly when the new subscription's observable state already equals
        the root's: same canonical interest + capacities + policy (the
        index key), same consumption frontier, and bit-equal τ/ρ. Anything
        less keeps the subscription independent — a missed collapse, never
        a wrong one.
        """
        root = self._share_index.get(sub.canon_sig)
        if (
            root is None
            or root.caps != sub.caps  # root may have outgrown the signature
            or not self._frontier_equal(root.since, sub.since)
            or not _stores_equal(root.tau, sub.tau)
            or not _stores_equal(root.rho, sub.rho)
        ):
            return None
        return root

    def _frontier_equal(self, a: int, b: int) -> bool:
        """Do two consumption frontiers denote the same pending suffix?

        Exactly equal frontiers trivially do. Beyond that, the unified
        sequence clock assigns non-changeset events (subscribes, fires)
        their own ticks, so two frontiers that both point past the last
        ingested changeset have *empty* pending suffixes and are
        equivalent — the next ingest re-keys both onto its cid
        (see :meth:`_apply_ingest`).
        """
        return a == b or min(a, b) > self._last_cid

    def _find_share_root(
        self, sub: BrokerSubscription
    ) -> BrokerSubscription | None:
        for s in self.subs:
            if (
                s.expr == sub.expr
                and s.caps == sub.caps
                and s.policy == sub.policy
                and np.array_equal(s.plan.patterns, sub.plan.patterns)
            ):
                return s
        return None

    def unsubscribe(self, sub: BrokerSubscription) -> None:
        """Remove one subscription; unrelated cohorts keep their executables."""
        self._seq += 1
        if self.journal is not None and not self._replaying:
            self.journal.append(
                "unsubscribe", meta={"jid": sub.jid}, seq=self._seq
            )
        if self.channel is not None:
            self.channel.forget(sub)
        self.subs.remove(sub)
        self.bank.remove_plan(sub.lanes)
        sub.lanes = ()
        self._lanes_raw -= sub.plan.n_total
        sig = sub.canon_sig
        if sig is not None and self._share_index.get(sig) is sub:
            # another member of the lane group (if any) becomes the root
            # future duplicates are checked against
            repl = next(
                (s for s in self.subs if s.canon_sig == sig), None
            )
            if repl is None:
                del self._share_index[sig]
            else:
                self._share_index[sig] = repl
        if not self.subs:
            # no live lane maps reference the bank: reset it outright so a
            # later first subscription starts from a fresh, compact bank
            self.bank = self._new_bank()
            self._bank_version = -1
            self._batches.clear()
        else:
            remap = self.bank.maybe_compact()
            if remap is not None:
                for s in self.subs:
                    s.lanes = tuple(remap[l] for l in s.lanes)
            self._sweep_batches(drained=False)
        if not self.cache_executables:
            self._exec_cache.clear()  # PR 1 full-rebuild baseline behavior

    # -- executable cache ---------------------------------------------------

    def _ensure_bank_dev(self, dev: int | None = None) -> jax.Array:
        if self._bank_dev is None or self._bank_version != self.bank.version:
            self._bank_dev = jnp.asarray(self.bank.patterns_padded())
            self._bank_real_dev = self._bank_dev
            self._refine_dev = None
            if isinstance(self.bank, SubsumptionBank):
                ra = self.bank.refine_arrays()
                if ra is not None:
                    self._bank_real_dev = jnp.asarray(
                        self.bank.real_padded()
                    )
                    self._refine_dev = (
                        jnp.asarray(ra[0]), jnp.asarray(ra[1])
                    )
            self._bank_version = self.bank.version
            self._bank_dev_for.clear()
        if dev is None:
            return self._bank_dev
        key = (self._bank_version, dev)
        placed = self._bank_dev_for.get(key)
        if placed is None:
            placed = self._bank_dev_for.setdefault(
                key, jax.device_put(self._bank_dev, self._devices[dev])
            )
        return placed

    def _tau_partitions(self, sub: BrokerSubscription, cap: int) -> tuple:
        """Hash-partitioned (SPO, OPS) shards of one subscription's τ.

        Cached per (subscription serial, τ version, capacity, mesh size):
        membership churn, bank churn, and fires of *other* subscriptions
        leave the key untouched, so only replicas whose τ actually changed
        (or whose capacity grew) ever re-partition. The host-side partition
        pass (``prepare_target_shards``) is the device-residency boundary of
        the sharded path — one τ pull per version, amortized over every fire
        until the next update.
        """
        key = (sub.serial, sub.tau_version, cap, self._n_shards)
        hit = self._tau_parts_cache.get(key)
        if hit is not None:
            self._tau_parts_cache.move_to_end(key)
            return hit
        spo, ops, _ = prepare_target_shards(
            to_numpy(sub.tau), self._n_shards, cap
        )  # shard cap == replica cap, so a partition can never overflow
        parts = (jnp.asarray(spo), jnp.asarray(ops))
        self._tau_parts_cache[key] = parts
        while len(self._tau_parts_cache) > self.exec_cache_max:
            self._tau_parts_cache.popitem(last=False)
        return parts

    def _empty_parts(self, cap: int) -> jax.Array:
        """All-PAD τ partition block for padded unique-target slots."""
        key = (cap, self._n_shards)
        block = self._empty_parts_cache.get(key)
        if block is None:
            block = self._empty_parts_cache.setdefault(
                key, jnp.full((self._n_shards, cap, 3), PAD, jnp.int32)
            )
        return block

    def _build_exec(self, key: tuple, builder: Callable, args: tuple):
        """Fetch-or-compile one executable; compile time goes to rejit_s.

        On a miss the step is AOT-lowered against the concrete ``args`` so
        the recorded time is pure compilation (evaluation stays outside),
        and the executable is registered for :func:`tracing.scope_table`.
        A lowering or compile error raises here, where it happens.
        """
        fn = self._exec_cache.get(key)
        if fn is not None:
            self._exec_cache.move_to_end(key)
            return fn
        t0 = time.perf_counter()
        fn = builder().lower(*args).compile()
        tracing.register(fn)
        self._exec_cache[key] = fn
        while len(self._exec_cache) > self.exec_cache_max:
            self._exec_cache.popitem(last=False)
        self._rejit_acc += time.perf_counter() - t0
        self.rejit_count += 1
        return fn

    # -- changeset manager + scheduler --------------------------------------

    def process_changeset(
        self, removed: np.ndarray, added: np.ndarray
    ) -> List[Optional[EvalOutputs]]:
        """Ingest one changeset; evaluate every subscriber whose policy fires.

        Returns one entry per subscriber, in subscription order: the
        :class:`EvalOutputs` of its (possibly batched) evaluation — each
        bit-identical to what the seed per-interest engine would produce for
        the same composed changeset — or None when the subscriber's policy
        deferred it (its pending batch keeps accumulating). An empty broker
        and 0-row ``removed``/``added`` sides are all well-defined: a fire
        whose composed batch is empty on both sides skips statics and
        executables entirely and returns canonical all-empty outputs (τ/ρ
        untouched — an empty changeset propagates nothing).
        """
        removed, added = _as_rows(removed), _as_rows(added)
        if self.channel is not None and not self._replaying:
            # backpressure: pump due retries first, and block (on the
            # channel's injected clock) while the in-flight retry queue is
            # over its bound — each pumped retry either acks or progresses
            # toward quarantine, both of which shrink the queue
            self._service_channel()
        self._seq += 1
        cid = self._seq
        with tracing.call_span("broker.process_changeset", cid):
            compiles0 = tracing.compile_count()
            if self.journal is not None and not self._replaying:
                # write-ahead: the changeset is durable before any batch
                # sees it
                self.journal.append(
                    "ingest",
                    arrays={"removed": removed, "added": added},
                    seq=cid,
                )
            if not self.subs:
                self._last_cid = cid
                return []
            t0 = time.perf_counter()
            self._reset_accumulators(compiles0)

            with tracing.span("broker.compose"):
                self._apply_ingest(removed, added, cid)

            now = time.perf_counter()
            ch = self.channel
            fired = []
            for k, s in enumerate(self.subs):
                batch = self._batches.get(s.since)
                if batch is not None and s.policy.fires(
                    batch.n_changesets, now - s.last_push_t
                ):
                    if ch is not None and not ch.eligible(s):
                        continue  # quarantined / backing off: frontier pins
                    fired.append(k)
            results, n_passes = self._fire(fired)
            with tracing.span("broker.fanout"):
                self._sweep_batches(drained=bool(fired))
            with tracing.span("broker.record_stats"):
                self._record_stats(
                    cid, removed, added, results, fired, n_passes, t0
                )
            return results

    def _reset_accumulators(self, compiles0: int) -> None:
        """Zero the per-call counters that :meth:`_record_stats` reads."""
        self._compiles0 = compiles0
        self._rejit_acc = 0.0
        self._rows_matched_acc = self._rows_distinct_acc = 0
        self._distinct_acc = self._fanout_acc = 0
        self._degraded_acc = 0

    def _apply_ingest(
        self, removed: np.ndarray, added: np.ndarray, cid: int
    ) -> None:
        """Layer 4: accumulate one changeset into every pending frontier.

        The unified clock makes changeset ids non-contiguous (subscribe and
        fire events consume ticks too), so a frontier pointing at a
        non-changeset seq — a fresh subscription, or a fully-drained
        subscriber — *re-keys* onto the first changeset that actually
        arrives: any subscriber with ``since <= cid`` and no pending batch
        provably has an empty pending suffix (every ingested changeset
        with id >= its frontier is in a batch it references), so adopting
        ``since = cid`` is the identity on its pending window.
        """
        for batch in self._batches.values():
            batch.extend(removed, added, cid)
        waiting = [
            s
            for s in self.subs
            if s.since not in self._batches and s.since <= cid
        ]
        if waiting:
            self._batches[cid] = ChangesetBatch.fresh(removed, added, cid)
            for s in waiting:
                s.since = cid
        self._last_cid = cid

    def _service_channel(self) -> None:
        """Pump due delivery retries; block while the retry queue is full.

        Called on the ingest path before consuming a sequence tick. Every
        flush of due subscribers either acks them (clearing their pending
        state) or fails them one step closer to quarantine, so the
        backpressure loop strictly drains and terminates.
        """
        ch = self.channel
        due = [s for s in self.subs if ch.retry_due(s)]
        if due:
            self.flush(due)
        if ch.max_in_flight is None:
            return
        while ch.in_flight() >= ch.max_in_flight:
            ch.wait_for_retry()
            due = [s for s in self.subs if ch.retry_due(s)]
            if not due:
                break
            self.flush(due)

    def flush(
        self, subs: Sequence[BrokerSubscription] | None = None
    ) -> List[Optional[EvalOutputs]]:
        """Drain pending batches now, regardless of policy.

        Evaluates every given subscription (default: all) that has at least
        one pending changeset; returns one entry per subscriber in
        subscription order (None where nothing was pending). Stale handles
        (already unsubscribed) are skipped, consistent with None semantics.
        A flush with nothing pending — and any fired frontier whose
        composed batch is empty — returns without building statics or
        touching executables (zero cohort passes).
        """
        if subs is None:
            targets = list(range(len(self.subs)))
        else:
            wanted = {id(s) for s in subs}
            targets = [
                k for k, s in enumerate(self.subs) if id(s) in wanted
            ]
        # the flush delivers up to the newest ingested changeset: its spans
        # carry that changeset's seq
        with tracing.call_span("broker.flush", self._last_cid):
            t0 = time.perf_counter()
            self._reset_accumulators(tracing.compile_count())
            fired = [
                k for k in targets if self.subs[k].since in self._batches
            ]
            if self.channel is not None and not self._replaying:
                fired = [
                    k for k in fired if self.channel.eligible(self.subs[k])
                ]
            results, n_passes = self._fire(fired)
            with tracing.span("broker.fanout"):
                self._sweep_batches(drained=bool(fired))
            if fired:
                # the committed fire consumed its own sequence tick (and
                # journal record) inside _fire, so stats see the advanced
                # clock
                z = np.zeros((0, 3), np.int32)
                with tracing.span("broker.record_stats"):
                    self._record_stats(
                        self._seq, z, z, results, fired, n_passes, t0
                    )
            return results

    def _fire(
        self, fired: List[int]
    ) -> Tuple[List[Optional[EvalOutputs]], int]:
        results: List[Optional[EvalOutputs]] = [None] * len(self.subs)
        if not fired:
            return results, 0
        groups: Dict[int, List[int]] = {}
        for k in fired:
            groups.setdefault(self.subs[k].since, []).append(k)

        def group_order(since: int):
            # priority lanes drain first, then oldest frontier
            has_priority = any(
                self.subs[k].policy.priority for k in groups[since]
            )
            return (not has_priority, since)

        ordered = sorted(groups, key=group_order)
        # empty-batch fast path: a composed batch with zero rows on both
        # sides, fired for subscribers whose potential sets ρ are empty,
        # delivers nothing — skip statics, executables, and passes
        # entirely and hand its subscribers canonical empty outputs (their
        # τ/ρ are untouched; consuming the batch is composition-neutral,
        # <∅, ∅> composed with any future changeset is that changeset).
        # A non-empty ρ takes the full pass: the seed engine re-reports
        # ρ's potential rows as a_i even for an empty changeset.
        outs: Dict[int, EvalOutputs] = {}
        fronts = []
        for since in ordered:
            batch = self._batches[since]
            d_rows, a_rows = batch.row_bounds()
            if d_rows == 0 and a_rows == 0 and all(
                int(self.subs[k].rho.n) == 0 for k in groups[since]
            ):
                for k in groups[since]:
                    outs[k] = _empty_outputs(self.subs[k].caps)
                continue
            fronts.append(self._frontier_input(groups[since], batch))
        staged: Dict[int, Tuple[TripleStore, TripleStore]] = {}
        if not fronts:
            n_passes = 0
        elif self.deferred_device_resident:
            # all fired frontiers in one evaluation: same-shape cohorts
            # stack across frontiers into one batched executable call
            with tracing.span("broker.evaluate"):
                o, staged, n_passes = self._evaluate_frontiers(fronts)
            outs.update(o)
        else:
            # PR 2 baseline: one sequential pass per frontier
            n_passes = 0
            for fr in fronts:
                with tracing.span("broker.evaluate"):
                    o, st, passes = self._evaluate_frontiers([fr])
                outs.update(o)
                staged.update(st)
                n_passes += passes

        # delivery gate (module docstring, layer 6): outputs are handed to
        # the channel BEFORE any state commits, so a failed delivery needs
        # no rollback — the subscriber is simply not committed: its τ/ρ
        # stay, its frontier pins, its batch keeps composing, and the next
        # eligible fire re-delivers the composed window (idempotent for
        # the receiver by Def-6 composition). Without a channel — and
        # during recovery replay — every fired subscriber acks.
        deliver = self.channel is not None and not self._replaying
        acked: List[int] = []
        for since in ordered:
            for k in groups[since]:
                if not deliver or self.channel.deliver(
                    self.subs[k], outs[k]
                ):
                    acked.append(k)
        with tracing.span("broker.commit"):
            if acked:
                # commit point: the fire consumes one sequence tick,
                # durably recording exactly the acked frontier advances; a
                # crash before this append re-fires (at-least-once), a
                # crash after it replays the evaluation without
                # re-delivering
                self._seq += 1
                if self.journal is not None and not self._replaying:
                    self.journal.append(
                        "fire",
                        meta={
                            "fires": [
                                [
                                    self.subs[k].jid,
                                    self._batches[self.subs[k].since].last_id
                                    + 1,
                                ]
                                for k in acked
                            ]
                        },
                        seq=self._seq,
                    )
            self._commit_staged(
                {k: staged[k] for k in acked if k in staged}
            )
        with tracing.span("broker.fanout"):
            self._fan_out(ordered, groups, set(acked), outs, results)
        return results, n_passes

    def _fan_out(
        self,
        ordered: List[int],
        groups: Dict[int, List[int]],
        acked_set: set,
        outs: Dict[int, EvalOutputs],
        results: List[Optional[EvalOutputs]],
    ) -> None:
        """Hand the acked subscribers their outputs and advance their
        frontiers and shared-τ epochs."""
        now = time.perf_counter()
        tag_refs: Dict[int, int] = {}
        for s in self.subs:
            tag_refs[id(s.share_tag)] = tag_refs.get(id(s.share_tag), 0) + 1
        for since in ordered:
            batch = self._batches[since]
            for k in groups[since]:
                if k not in acked_set:
                    continue
                results[k] = outs[k]
                s = self.subs[k]
                s.since = batch.last_id + 1
                s.last_push_t = now
                if tag_refs[id(s.share_tag)] > 1:
                    hist = (s.epoch, batch.first_id, batch.last_id)
                    epoch = self._epoch_intern.get(hist)
                    if epoch is None:
                        self._epoch_next += 1
                        epoch = self._epoch_intern[hist] = self._epoch_next
                    s.epoch = epoch
        if len(self._epoch_intern) > self.epoch_intern_max:
            # entries whose parent epoch no subscriber holds can never be
            # looked up again (lookups key on a live subscriber's epoch)
            held = {s.epoch for s in self.subs}
            self._epoch_intern = {
                hist: e
                for hist, e in self._epoch_intern.items()
                if hist[0] in held
            }

    def _frontier_input(
        self, idxs: List[int], batch: ChangesetBatch
    ) -> "_FrontierInput":
        """One fired frontier as evaluator input.

        Device-resident (default): the batch's already-lex-sorted composed
        device stores re-home (pad/slice, never re-sort, never transfer) to
        whatever capacity the evaluation needs. Round-trip baseline: the
        composed batch is pulled to host and re-uploaded/re-sorted per fire
        (the PR 2 behavior).
        """
        if self.deferred_device_resident:
            d_rows, a_rows = batch.row_bounds()
            return _FrontierInput(
                idxs=idxs,
                d_rows=d_rows,
                a_rows=a_rows,
                d_store=lambda cap: rehome(batch.device_stores()[0], cap),
                a_store=lambda cap: rehome(batch.device_stores()[1], cap),
                since=batch.first_id,
                d_native=lambda: batch.device_stores()[0],
            )
        d_np, a_np = batch.arrays()
        return _FrontierInput(
            idxs=idxs,
            d_rows=int(d_np.shape[0]),
            a_rows=int(a_np.shape[0]),
            d_store=lambda cap: from_array(jnp.asarray(d_np, jnp.int32), cap)[0],
            a_store=lambda cap: from_array(jnp.asarray(a_np, jnp.int32), cap)[0],
            since=batch.first_id,
        )

    def _sweep_batches(self, drained: bool) -> None:
        """Batch lifecycle bookkeeping at one orchestration point.

        Folds every live batch's capacity-doubling count into the broker
        totals (before GC, so growth on a just-consumed frontier is not
        lost), drops batches no subscriber references, and — only when this
        call actually drained something, keeping the per-changeset ingest
        path free of device-scalar syncs — runs the capacity-decay check on
        the surviving deferred frontiers
        (:meth:`~repro.core.propagation.ChangesetBatch.maybe_decay`).
        """
        for since, b in self._batches.items():
            seen = self._grow_seen.get(since, 0)
            if b.grow_count > seen:
                self.batch_grows += b.grow_count - seen
                self._grow_seen[since] = b.grow_count
        live = {s.since for s in self.subs}
        self._batches = {
            since: b for since, b in self._batches.items() if since in live
        }
        self._grow_seen = {
            since: g
            for since, g in self._grow_seen.items()
            if since in self._batches
        }
        if drained:
            for b in self._batches.values():
                if b.maybe_decay(self.decay_patience):
                    self.batch_shrinks += 1

    # -- evaluator ----------------------------------------------------------

    def _static_arrays(
        self,
        ckey: tuple,
        fk: List[Tuple[int, int]],
        f_list: List[int],
        upos: Dict[int, int],
        ncp: int,
        nt: int,
        device=None,
    ):
        """Membership-static device inputs for one cohort invocation.

        f_map / pats / lanes / tgt_map / active change only with membership,
        frontier grouping, plan recompiles, bank compaction, or shared-τ
        regrouping — all covered by the cache key below — so the
        steady-state path skips the per-call numpy rebuild and
        host-to-device transfers. Keyed by the full membership signature
        (not just the cohort), so same-shape cohorts fired from different
        frontier combinations (mixed cadences) each keep their own entry
        instead of evicting one another; the LRU bound reclaims superseded
        signatures.
        """
        subs = self.subs
        key = (
            ckey,
            tuple(subs[k].serial for _, k in fk),
            tuple(subs[k].plan_version for _, k in fk),
            tuple(upos[k] for _, k in fk),
            tuple(f_list),
            self.bank.version,
        )
        cached = self._static_arrays_cache.get(key)
        if cached is not None:
            self._static_arrays_cache.move_to_end(key)
            return cached
        if isinstance(self.bank, SubsumptionBank):
            # encoded lane ids (virtual >= REFINE_BASE) -> dense extended
            # row indices; the cache key's bank.version covers validity
            lane_rows = [
                self.bank.resolve_lanes(subs[k].lanes) for _, k in fk
            ]
        else:
            lane_rows = [subs[k].lanes for _, k in fk]
        arrays = _assemble_cohort_statics(
            [subs[k].plan.patterns for _, k in fk],
            lane_rows,
            [upos[k] for _, k in fk],
            f_list,
            ncp,
            nt,
        )
        if device is not None:
            # committed to the cohort's placed device once, re-used per fire
            arrays = jax.device_put(arrays, device)
        self._static_arrays_cache[key] = arrays
        while len(self._static_arrays_cache) > self.exec_cache_max:
            self._static_arrays_cache.popitem(last=False)
        return arrays

    def _evaluate_frontiers(
        self, fronts: List[_FrontierInput]
    ) -> Tuple[
        Dict[int, EvalOutputs],
        Dict[int, Tuple[TripleStore, TripleStore]],
        int,
    ]:
        """All fired frontiers through every due cohort; staged results.

        Returns ``(outs, staged, n_passes)``: per-subscriber outputs, the
        staged (τ', ρ') updates, and the executable pass count. Nothing is
        committed here — :meth:`_fire` commits the staged state only for
        subscribers whose delivery acked (:meth:`_commit_staged`), which is
        what makes a failed delivery rollback-free.

        The frontier axis is folded into each cohort's member axis: one
        stacked bank pass covers every frontier's deleted side, and each
        shape cohort runs ONE executable call spanning all frontiers it
        fires from (members gather their frontier's slices via ``f_map``).
        The round-trip baseline calls this with single-frontier lists, so
        both paths share executables, statics, and commit discipline.

        With a mesh the pass is placement-aware: cohort calls are grouped
        by their :class:`~repro.core.distributed.CohortPlacement` device —
        dispatched in device order with fully committed inputs, so the
        asynchronously-running executables overlap across the mesh — or,
        under ``shard_cohorts=True``, every cohort call runs inside
        shard_map over the whole mesh with hash-partitioned τ shards.
        """
        subs = self.subs
        # matcher identity is baked into compiled steps, so it must be part
        # of every executable key (caches may be shared across brokers)
        mkey = id(self.matcher) if self.matcher is not None else None
        sharded = self.mesh is not None and self.shard_cohorts
        placed = self.mesh is not None and not self.shard_cohorts
        # delta-chain eligibility: >= 2 overlapping frontiers on the
        # device-resident path (a single frontier has nothing to dedup and
        # keeps the eager executables untouched); the int32 membership
        # bitmap caps the chain at 32 frontier slots
        delta_ok = (
            self.delta_frontiers
            and self.deferred_device_resident
            and len(fronts) >= 2
            and next_pow2(len(fronts)) <= 32
            and all(fr.d_native is not None for fr in fronts)
        )
        n_passes = 0  # counts abandoned overflow-retry attempts too
        n_retries = 0  # whole-fire overflow re-runs (bounded ceiling)
        front_of = {k: fr for fr in fronts for k in fr.idxs}
        while True:
            with tracing.span("broker.statics"):
                for fr in fronts:
                    for k in fr.idxs:  # host-side capacity guard
                        s = subs[k]
                        while (
                            fr.d_rows > s.caps.n_removed
                            or fr.a_rows > s.caps.n_added
                        ):
                            s.recompile(s.caps.doubled())
                    for k in fr.idxs:  # dictionary growth guard
                        if self.dictionary.id_capacity > subs[k].id_capacity:
                            subs[k].recompile()
                bank_dev = self._ensure_bank_dev()
                n_words_p = bank_dev.shape[0] // 32
                # deleted-side words inputs: when the subsumption bank holds
                # virtual lanes, the words pass runs over the REAL rows only
                # and lane_refine produces the virtual planes (parent word AND
                # residual compare), concatenated after the real planes — the
                # result reproduces the extended-bank word layout bit for bit,
                # at residual cost instead of full bank width
                bank_real = self._bank_real_dev
                refine = self._refine_dev
                n_words_r = bank_real.shape[0] // 32

                all_idx = [k for fr in fronts for k in fr.idxs]
                d_cap = max(subs[k].caps.n_removed for k in all_idx)
                nf = len(fronts)
                nfp = next_pow2(nf)

                # delta-encoded frontier chain: the fired frontiers' D sides
                # overlap (suffix composition), so build the distinct-row
                # union + per-frontier membership bitmap and match each row
                # ONCE; fall back to the stacked pass if containment fails
                # (the chain proves it instead of assuming Def-6 nesting).
                # The union is homed at its own pow2 row bucket, NOT the
                # per-subscriber guard capacity: one store serves every
                # member, so the whole D-side evaluation — candidate vectors,
                # probes, pull sorts — runs at distinct-row shapes instead of
                # F guard-capacity stores (the containment check doubles as
                # the proof that the bucket holds every frontier's rows)
                chain = None
                u_cap = d_cap
                if delta_ok:
                    base_fi = min(range(nf), key=lambda i: fronts[i].since)
                    u_cap = max(64, next_pow2(fronts[base_fi].d_rows))
                    c = build_frontier_chain(
                        [fr.d_native() for fr in fronts], base_fi, u_cap
                    )
                    if c.covered:
                        chain = c
                    else:
                        u_cap = d_cap
                if chain is not None:
                    matched = distinct = fronts[base_fi].d_rows
                else:
                    matched = sum(fr.d_rows for fr in fronts)
                    distinct = max((fr.d_rows for fr in fronts), default=0)
                self._rows_matched_acc += matched
                self._rows_distinct_acc += distinct
                self.rows_matched += matched
                self.rows_distinct += distinct

                # fused pass 1 over the deleted side. Delta chain: ONE
                # segmented bank pass over the union rows emits every
                # frontier's membership-masked words (padding slots' bits are
                # simply absent from the bitmap). Stacked fallback: one bank
                # pass per frontier, sliced per cohort; padding slots carry
                # empty stores. The sharded path computes its words in-graph
                # instead (block-split across shards, block-gather-stitched),
                # so it skips this pass either way.
                d_stores = None
                if chain is None:
                    d_stores = [fr.d_store(d_cap) for fr in fronts]

                # per-frontier added sides, cached per cohort capacity
                a_cache: Dict[Tuple[int, int], TripleStore] = {}

                def a_of(fi: int, cap: int) -> TripleStore:
                    if (fi, cap) not in a_cache:
                        a_cache[(fi, cap)] = fronts[fi].a_store(cap)
                    return a_cache[(fi, cap)]

                cohorts: Dict[tuple, List[Tuple[int, int]]] = {}
                for fi, fr in enumerate(fronts):
                    for k in fr.idxs:
                        s = subs[k]
                        key = (s.shape_key, s.caps, s.id_capacity)
                        cohorts.setdefault(key, []).append((fi, k))

                # placement: sticky cohort -> device assignment, calls grouped
                # (and therefore dispatched) by device so the mesh runs cohorts
                # concurrently; the sharded path spans every device per call
                cohort_items = list(cohorts.items())
                cohort_dev: Dict[tuple, Optional[int]] = {}
                for key, fk in cohort_items:
                    if placed:
                        cohort_dev[key] = self.placement.assign(
                            key, next_pow2(len(fk)), len(self._devices)
                        )
                    else:
                        cohort_dev[key] = None
                if placed:
                    cohort_items.sort(key=lambda kv: cohort_dev[kv[0]])

                staged: Dict[int, Tuple[TripleStore, TripleStore]] = {}
                outs: Dict[int, EvalOutputs] = {}
                overflowed: List[int] = []
            with tracing.span("broker.bank_pass"):
                d_words_all = None
                if not sharded and chain is not None:
                    wkey = (
                        "words-seg", u_cap, n_words_p, n_words_r, nfp, mkey
                    )
                    if refine is None:
                        def words_builder():
                            return jax.jit(
                                lambda spo, seg, b: (
                                    kops.pattern_bitmask_words_segmented(
                                        spo, b, seg, nfp, matcher=self.matcher
                                    )
                                )
                            )

                        wargs = (chain.union.spo, chain.seg, bank_real)
                    else:
                        # refined planes inherit each frontier's membership
                        # mask for free: a union row outside frontier f has
                        # zero real bits, so its parent bit — and therefore
                        # its refined bit — is already zero
                        def words_builder():
                            def f(spo, seg, b, par, res):
                                w = kops.pattern_bitmask_words_segmented(
                                    spo, b, seg, nfp, matcher=self.matcher
                                )
                                wv = jax.vmap(
                                    lambda plane: kops.lane_refine(
                                        spo, plane, par, res
                                    )
                                )(w)
                                return jnp.concatenate([w, wv], axis=-1)

                            return jax.jit(f)

                        wargs = (
                            chain.union.spo, chain.seg, bank_real
                        ) + refine
                    words_fn = self._build_exec(wkey, words_builder, wargs)
                    # (nfp, u_cap, W): frontier fi's words over the UNION
                    # rows
                    d_words_all = words_fn(*wargs)
                elif not sharded:
                    d_spos = tuple(st.spo for st in d_stores) + (
                        _empty_cached(d_cap).spo,
                    ) * (nfp - nf)
                    wkey = ("words", d_cap, n_words_p, n_words_r, nfp, mkey)
                    if refine is None:
                        def words_builder():
                            return jax.jit(
                                lambda spos, b: jax.vmap(
                                    lambda spo: kops.pattern_bitmask_words(
                                        spo, b, matcher=self.matcher
                                    )
                                )(jnp.stack(spos))
                            )

                        wargs = (d_spos, bank_real)
                    else:
                        def words_builder():
                            def one(spo, b, par, res):
                                w = kops.pattern_bitmask_words(
                                    spo, b, matcher=self.matcher
                                )
                                return jnp.concatenate(
                                    [w, kops.lane_refine(spo, w, par, res)],
                                    axis=-1,
                                )

                            return jax.jit(
                                lambda spos, b, par, res: jax.vmap(
                                    lambda spo: one(spo, b, par, res)
                                )(jnp.stack(spos))
                            )

                        wargs = (d_spos, bank_real) + refine
                    words_fn = self._build_exec(wkey, words_builder, wargs)
                    d_words_all = words_fn(*wargs)  # (nfp, d_cap, W)

            for (skey, caps, id_cap), fk in cohort_items:
                with tracing.span("broker.statics"):
                    dev = cohort_dev[(skey, caps, id_cap)]
                    device = self._devices[dev] if dev is not None else None
                    rep = subs[fk[0][1]]
                    nt = rep.plan.n_total
                    # frontier slots this cohort actually uses -> dense local
                    # slots, so the padded frontier axis stays minimal
                    fs_used = sorted({fi for fi, _ in fk})
                    fslot = {fi: i for i, fi in enumerate(fs_used)}
                    nfc = len(fs_used)
                    nfcp = next_pow2(nfc)
                    # unique target replicas (shared-τ lane groups) in this
                    # cohort; rep_fk holds each group's first (frontier, sub)
                    ugroups: List[List[int]] = []
                    rep_fk: List[Tuple[int, int]] = []
                    upos: Dict[int, int] = {}
                    seen: Dict[tuple, int] = {}
                    for fi, k in fk:
                        s = subs[k]
                        gk = (fi, id(s.share_tag), s.epoch)
                        if gk not in seen:
                            seen[gk] = len(ugroups)
                            ugroups.append([])
                            rep_fk.append((fi, k))
                        upos[k] = seen[gk]
                        ugroups[seen[gk]].append(k)
                    if self.subsume_interests:
                        # lattice group collapse: ONE cohort slot per lane
                        # group. Members of a group provably share plan
                        # values, lanes, caps, τ, ρ, and frontier — that is
                        # exactly what the (share_tag, epoch) lineage
                        # certifies — so their slots would compute identical
                        # results; the commit loop below fans the
                        # representative's outputs out to every member, making
                        # executable work a function of distinct interests and
                        # delivery O(1) copies per interest.
                        eval_fk = rep_fk
                        eval_upos = {
                            k: i for i, (_, k) in enumerate(rep_fk)
                        }
                    else:
                        eval_fk, eval_upos = fk, upos
                    members = [k for _, k in eval_fk]
                    f_list = [fslot[fi] for fi, _ in eval_fk]
                    nm, nu = len(members), len(ugroups)
                    ncp, nup = next_pow2(nm), next_pow2(nu)
                    self._distinct_acc += nm
                    self._fanout_acc += len(fk)
                    self.distinct_interests += nm
                    self.fanout_copies += len(fk)

                    d_sets = None
                    if chain is None:
                        d_sets = tuple(
                            TripleStore(
                                spo=d_stores[fi].spo[: caps.n_removed],
                                n=d_stores[fi].n,
                            )
                            for fi in fs_used
                        ) + (_empty_cached(caps.n_removed, device),) * (
                            nfcp - nfc
                        )
                    a_sets = tuple(
                        a_of(fi, caps.n_added) for fi in fs_used
                    ) + (_empty_cached(caps.n_added, device),) * (nfcp - nfc)
                    uniq_taus = tuple(subs[g[0]].tau for g in ugroups) + (
                        _empty_cached(caps.tau, device),
                    ) * (nup - nu)
                    rhos_c = tuple(subs[k].rho for k in members) + (
                        _empty_cached(caps.rho, device),
                    ) * (ncp - nm)
                    if sharded:
                        if chain is not None:
                            ckey = (
                                "cohort-sh-delta", skey, caps, id_cap, ncp,
                                nup, nfcp, n_words_p, u_cap, self._n_shards,
                                mkey,
                            )
                        else:
                            ckey = (
                                "cohort-sh", skey, caps, id_cap, ncp, nup,
                                nfcp, n_words_p, self._n_shards, mkey,
                            )
                        (
                            f_map_d, tgt_map_d, pats_d, lanes_d, active_d,
                        ) = self._static_arrays(
                            ckey, eval_fk, f_list, eval_upos, ncp, nt
                        )
                        parts = [
                            self._tau_partitions(subs[g[0]], caps.tau)
                            for g in ugroups
                        ]
                        pad_part = [self._empty_parts(caps.tau)] * (nup - nu)
                        uniq_spo_sh = jnp.stack(
                            [p[0] for p in parts] + pad_part
                        )
                        uniq_ops_sh = jnp.stack(
                            [p[1] for p in parts] + pad_part
                        )
                        if chain is not None:
                            # membership bits remapped to this cohort's dense
                            # local frontier slots (they key f_map)
                            seg_local = _seg_local_bits(
                                chain.seg, tuple(fs_used)
                            )
                            args = (
                                chain.union,
                                seg_local,
                                a_sets,
                                bank_dev,
                                uniq_taus,
                                uniq_spo_sh,
                                uniq_ops_sh,
                                f_map_d,
                                tgt_map_d,
                                rhos_c,
                                pats_d,
                                lanes_d,
                                active_d,
                            )
                            builder = (
                                lambda nfcp=nfcp: make_sharded_cohort_step(
                                    rep.plan, caps, id_cap, self.mesh,
                                    axis=self._shard_axis,
                                    n_shards=self._n_shards,
                                    matcher=self.matcher,
                                    delta=True, n_frontiers=nfcp,
                                )
                            )
                        else:
                            args = (
                                d_sets,
                                a_sets,
                                bank_dev,
                                uniq_taus,
                                uniq_spo_sh,
                                uniq_ops_sh,
                                f_map_d,
                                tgt_map_d,
                                rhos_c,
                                pats_d,
                                lanes_d,
                                active_d,
                            )
                            builder = (
                                lambda: make_sharded_cohort_step(
                                    rep.plan, caps, id_cap, self.mesh,
                                    axis=self._shard_axis,
                                    n_shards=self._n_shards,
                                    matcher=self.matcher,
                                )
                            )
                    elif chain is not None:
                        # delta chain: ONE union store for the whole cohort at
                        # the union's own row bucket u_cap; per-frontier
                        # membership-masked words over the union rows (a row
                        # outside a member's frontier carries zero bits, so
                        # the shared store adds no candidates — no
                        # per-frontier
                        # slices, no per-member store gather, and the whole
                        # D-side evaluation runs at distinct-row shapes)
                        d_words = tuple(d_words_all[fi] for fi in fs_used)
                        if nfcp > nfc:
                            zero_w = jnp.zeros((u_cap, n_words_p), jnp.uint32)
                            d_words = d_words + (zero_w,) * (nfcp - nfc)
                        ckey = (
                            "cohort-delta", skey, caps, id_cap, ncp, nup, nfcp,
                            n_words_p, u_cap, mkey, dev,
                        )
                        (
                            f_map_d, tgt_map_d, pats_d, lanes_d, active_d,
                        ) = self._static_arrays(
                            ckey, eval_fk, f_list, eval_upos, ncp, nt,
                            device=device,
                        )
                        args = (
                            chain.union,
                            d_words,
                            a_sets,
                            self._ensure_bank_dev(dev) if placed else bank_dev,
                            uniq_taus,
                            f_map_d,
                            tgt_map_d,
                            rhos_c,
                            pats_d,
                            lanes_d,
                            active_d,
                        )
                        if placed:
                            args = jax.device_put(args, device)
                        builder = lambda: make_cohort_step(  # noqa: E731
                            rep.plan, caps, id_cap, matcher=self.matcher,
                            delta=True,
                        )
                    else:
                        d_words = tuple(
                            d_words_all[fi, : caps.n_removed] for fi in fs_used
                        )
                        if nfcp > nfc:
                            zero_w = jnp.zeros(
                                (caps.n_removed, n_words_p), jnp.uint32
                            )
                            d_words = d_words + (zero_w,) * (nfcp - nfc)
                        ckey = (
                            "cohort", skey, caps, id_cap, ncp, nup, nfcp,
                            n_words_p, mkey, dev,
                        )
                        (
                            f_map_d, tgt_map_d, pats_d, lanes_d, active_d,
                        ) = self._static_arrays(
                            ckey, eval_fk, f_list, eval_upos, ncp, nt,
                            device=device,
                        )
                        args = (
                            d_sets,
                            d_words,
                            a_sets,
                            self._ensure_bank_dev(dev) if placed else bank_dev,
                            uniq_taus,
                            f_map_d,
                            tgt_map_d,
                            rhos_c,
                            pats_d,
                            lanes_d,
                            active_d,
                        )
                        if placed:
                            # commit every operand to the cohort's device:
                            # resident state (τ/ρ, statics, bank, padding) is
                            # already there, so only the frontier slices move
                            args = jax.device_put(args, device)
                        builder = lambda: make_cohort_step(  # noqa: E731
                            rep.plan, caps, id_cap, matcher=self.matcher
                        )
                with tracing.span("broker.cohort_dispatch"):
                    miss = ckey not in self._exec_cache
                    fn = self._build_exec(ckey, builder, args)
                    if miss:
                        self.cohort_compiles[ckey] = (
                            self.cohort_compiles.get(ckey, 0) + 1
                        )
                    tau1_c, rho1_c, out_c = fn(*args)
                n_passes += 1
                if sharded:
                    for i in range(len(self._devices)):
                        self.device_passes[i] = (
                            self.device_passes.get(i, 0) + 1
                        )
                else:
                    self.device_passes[dev or 0] = (
                        self.device_passes.get(dev or 0, 0) + 1
                    )
                for ug, g in enumerate(ugroups):
                    pos0 = members.index(g[0])
                    out = out_c[pos0]
                    with tracing.span("broker.await_device"):
                        overflow = bool(out.overflow)
                    if overflow:
                        overflowed.extend(g)
                        continue
                    for k in g:  # shared-τ members adopt one state object
                        outs[k] = out
                        staged[k] = (tau1_c[pos0], rho1_c[pos0])

            if overflowed:
                n_retries += 1
                if n_retries > self.max_fire_retries:
                    # bounded degradation: past the ceiling, evaluate the
                    # still-overflowing subscribers through the seed
                    # per-interest path (bit-identical by the oracle
                    # discipline; it doubles only the one subscriber's
                    # caps) instead of re-running the whole multi-frontier
                    # fire while capacities grow without limit
                    degraded = sorted(set(overflowed))
                    for k in degraded:
                        tau1, rho1, out = self._degraded_eval(
                            k, front_of[k], mkey
                        )
                        outs[k] = out
                        staged[k] = (tau1, rho1)
                        n_passes += 1
                    self.degraded_fires += len(degraded)
                    self._degraded_acc += len(degraded)
                    return outs, staged, n_passes
                # grow only the subscribers that overflowed, then re-run the
                # whole fire (staged updates are discarded: atomic commit)
                for k in sorted(set(overflowed)):
                    subs[k].recompile(subs[k].caps.doubled())
                continue
            return outs, staged, n_passes

    def _degraded_eval(
        self, k: int, fr: _FrontierInput, mkey
    ) -> Tuple[TripleStore, TripleStore, EvalOutputs]:
        """Seed-path fallback for one subscriber whose cohort fire kept
        overflowing past ``max_fire_retries``: the per-interest
        :func:`~repro.core.propagation.make_interest_step` evaluation of
        its composed frontier, doubling only its own capacities until the
        outputs fit. Outputs and staged state are bit-identical to the
        cohort path (the same oracle every broker layer is pinned
        against); only throughput degrades."""
        s = self.subs[k]
        while fr.d_rows > s.caps.n_removed or fr.a_rows > s.caps.n_added:
            s.recompile(s.caps.doubled())
        if self.dictionary.id_capacity > s.id_capacity:
            s.recompile()
        for _ in range(64):
            d = fr.d_store(s.caps.n_removed)
            a = fr.a_store(s.caps.n_added)
            key = ("seed", s.serial, s.plan_version, s.caps, mkey)
            fn = self._build_exec(
                key,
                lambda: make_interest_step(
                    s.plan,
                    id_capacity=s.id_capacity,
                    caps=s.caps,
                    matcher=self.matcher,
                ),
                (d, a, s.tau, s.rho),
            )
            tau1, rho1, out = fn(d, a, s.tau, s.rho)
            if not bool(out.overflow):
                return tau1, rho1, out
            s.recompile(s.caps.doubled())
        raise RuntimeError(
            "degraded seed-path fire failed to converge after 64 doublings"
        )

    def _commit_staged(
        self, staged: Dict[int, Tuple[TripleStore, TripleStore]]
    ) -> None:
        """Commit staged (τ', ρ') for the acked subscribers.

        Only the sharded path consults the τ-partition cache, and only
        an actually-changed replica should invalidate it — a fire
        whose changesets missed this interest commits a bit-identical
        τ, and re-partitioning it would waste the exact host round
        trip the cache exists to amortize. Comparisons memoize on the
        (old, new) array pair, so a shared-τ group syncs once.
        """
        subs = self.subs
        sharded = self.mesh is not None and self.shard_cohorts
        unchanged_cache: Dict[Tuple[int, int], bool] = {}
        for k, (tau1, rho1) in staged.items():
            s = subs[k]
            unchanged = False
            if sharded:
                pair = (id(s.tau.spo), id(tau1.spo))
                unchanged = unchanged_cache.get(pair)
                if unchanged is None:
                    unchanged = s.tau.spo.shape == tau1.spo.shape and bool(
                        jnp.all(s.tau.spo == tau1.spo)
                    )
                    unchanged_cache[pair] = unchanged
            if not unchanged:
                s.tau_version += 1
            s.tau, s.rho = tau1, rho1
        if staged:
            # block on every cohort's output so elapsed_s covers all
            # work; lane-group members alias one τ array, so block on
            # each distinct array once, not per delivery
            jax.block_until_ready(
                list({
                    id(tau1.spo): tau1.spo
                    for tau1, _ in staged.values()
                }.values())
            )

    # -- durability: snapshot / recovery / compaction -----------------------

    def snapshot(self, store) -> int:
        """Persist full broker state into a :class:`CheckpointStore`.

        Keyed by the current journal sequence (atomic tmp-dir+rename, see
        ``checkpoint/store.py``), so replay after a restore is bounded to
        the journal tail past this seq — plus the pre-snapshot *ingest*
        records still pending on some subscriber's consumption frontier,
        which is exactly what :meth:`compact_journal` keeps. τ/ρ are saved
        as canonical host row arrays (lex-sorted valid rows), so restoring
        through ``from_array`` reproduces them bit for bit.
        """
        state = {
            "subs": {
                str(s.jid): {
                    "tau": to_numpy(s.tau),
                    "rho": to_numpy(s.rho),
                }
                for s in self.subs
            }
        }
        extra = {
            "seq": self._seq,
            "jid_next": self._jid_next,
            "last_cid": self._last_cid,
            "subs": [
                {
                    "jid": s.jid,
                    "expr": _expr_to_json(s.expr),
                    "caps": _caps_to_json(s.caps),
                    "policy": _policy_to_json(s.policy),
                    "since": s.since,
                }
                for s in self.subs
            ],
        }
        store.save(self._seq, state, extra)
        self._last_snapshot_seq = self._seq
        self._snapshot_keep_from = min(
            [s.since for s in self.subs] + [self._seq + 1]
        )
        return self._seq

    def compact_journal(self) -> int:
        """Drop journal segments replay can never need; returns segments
        removed. Safe exactly when a snapshot exists: replay needs (a)
        records after the last snapshot and (b) ingest records at or after
        the snapshot's oldest live consumption frontier — without a
        snapshot everything from seq 1 is needed, so nothing is dropped.
        """
        if self.journal is None:
            return 0
        return self.journal.compact(self._snapshot_keep_from)

    @classmethod
    def recover(
        cls,
        journal: ChangesetJournal,
        store=None,
        dictionary: Dictionary | None = None,
        **broker_kwargs,
    ) -> "Broker":
        """Rebuild a broker from its journal (+ optional snapshot store).

        Picks the newest snapshot whose seq is <= the journal's durable
        ``last_seq`` (a snapshot ahead of the durable prefix reflects
        un-journaled state and is skipped), restores every subscription's
        τ/ρ/frontier from it, then replays the journal tail: pre-snapshot
        *ingest* records rebuild the pending :class:`ChangesetBatch`es
        (self-gating — only changesets at or past a restored frontier
        land in a batch), and post-snapshot records re-run their original
        operations with journaling and delivery suppressed. Fires replay
        exactly the recorded acked subscribers, so a delivery that failed
        before the crash stays un-committed after recovery. The result is
        bit-identical broker state: same τ/ρ rows, same frontiers, same
        pending batches, same sequence clock.

        ``dictionary`` must be the same dictionary the crashed broker
        encoded with (term↔id growth happens in the caller and is not
        journaled). Per-subscriber transports and channel retry state are
        ephemeral — re-attach transports after recovery; quarantine is
        re-earned. Lane-group/share lineage of *restored* subscriptions is
        not reconstructed (a missed collapse only — values stay
        bit-identical); subscriptions replayed from post-snapshot records
        rebuild their lineage normally.
        """
        broker = cls(
            dictionary=dictionary, journal=journal, **broker_kwargs
        )
        broker._seq = 0
        snap_step = 0
        extra: Dict = {}
        if store is not None:
            usable = [s for s in store.steps() if s <= journal.last_seq]
            if usable:
                snap_step = usable[-1]
                arrays, extra = store.load_raw(snap_step)
                broker._replaying = True
                try:
                    for meta in extra["subs"]:
                        broker._restore_sub(meta, arrays)
                finally:
                    broker._replaying = False
                broker._seq = int(extra["seq"])
                broker._jid_next = int(extra["jid_next"])
                broker._last_snapshot_seq = snap_step
        min_since = min(
            [s.since for s in broker.subs] + [snap_step + 1]
        )
        records = list(journal.records())
        if records and records[0].seq > min(min_since, snap_step + 1):
            raise RuntimeError(
                f"journal starts at seq {records[0].seq} but replay needs "
                f"seq {min(min_since, snap_step + 1)}: a needed segment "
                "was compacted away or lost"
            )
        broker._replaying = True
        try:
            for rec in records:
                if rec.seq <= snap_step:
                    # pre-snapshot: only ingests still pending on some
                    # restored frontier matter; everything else is already
                    # reflected in the snapshot
                    if rec.kind == "ingest" and rec.seq >= min_since:
                        broker._apply_ingest(
                            rec.arrays["removed"], rec.arrays["added"],
                            rec.seq,
                        )
                    continue
                broker._seq = rec.seq - 1
                if rec.kind == "ingest":
                    broker._seq = rec.seq
                    broker._apply_ingest(
                        rec.arrays["removed"], rec.arrays["added"], rec.seq
                    )
                elif rec.kind == "subscribe":
                    broker.subscribe(
                        _expr_from_json(rec.meta["expr"]),
                        caps=_caps_from_json(rec.meta["caps"]),
                        initial_target=rec.arrays.get("initial_target"),
                        policy=_policy_from_json(rec.meta["policy"]),
                        share_target=bool(rec.meta["share_target"]),
                        _jid=int(rec.meta["jid"]),
                    )
                elif rec.kind == "unsubscribe":
                    broker.unsubscribe(
                        broker._sub_by_jid(int(rec.meta["jid"]))
                    )
                elif rec.kind == "fire":
                    broker._replay_fire(rec)
                else:
                    raise RuntimeError(
                        f"unknown journal record kind {rec.kind!r}"
                    )
        finally:
            broker._replaying = False
        if extra:
            broker._last_cid = max(
                broker._last_cid, int(extra["last_cid"])
            )
        broker._seq = max(broker._seq, journal.last_seq)
        broker._sweep_batches(drained=False)
        return broker

    def _restore_sub(self, meta: Dict, arrays: Dict) -> None:
        """One snapshot subscription back to life (no journaling)."""
        sub = BrokerSubscription(
            _expr_from_json(meta["expr"]),
            self.dictionary,
            _caps_from_json(meta["caps"]),
            policy=_policy_from_json(meta["policy"]),
        )
        sub.jid = int(meta["jid"])
        sub.since = int(meta["since"])
        prefix = f"subs/{sub.jid}/"
        tau_rows = arrays[prefix + "tau"]
        rho_rows = arrays[prefix + "rho"]
        if tau_rows.size:
            sub.tau, _ = from_array(
                jnp.asarray(tau_rows, jnp.int32), sub.caps.tau
            )
        if rho_rows.size:
            sub.rho, _ = from_array(
                jnp.asarray(rho_rows, jnp.int32), sub.caps.rho
            )
        sub.lanes = self.bank.add_plan(sub.plan)
        self.subs.append(sub)
        self._lanes_raw += sub.plan.n_total

    def _sub_by_jid(self, jid: int) -> BrokerSubscription:
        for s in self.subs:
            if s.jid == jid:
                return s
        raise RuntimeError(f"journal references unknown subscriber {jid}")

    def _replay_fire(self, rec) -> None:
        """Re-run one committed fire for exactly the recorded subscribers.

        Re-evaluates the recorded frontiers (delivery suppressed — the
        receivers already have these outputs; a re-send would be harmless
        anyway, see the Def-6 idempotence contract in the module
        docstring) and commits their staged τ/ρ and frontier advances.
        The recorded ``new_since`` values double as an integrity check.
        """
        by_jid = {int(j): int(ns) for j, ns in rec.meta["fires"]}
        ks = [
            k for k, s in enumerate(self.subs) if s.jid in by_jid
        ]
        if len(ks) != len(by_jid):
            missing = set(by_jid) - {self.subs[k].jid for k in ks}
            raise RuntimeError(
                f"fire record {rec.seq} references unknown "
                f"subscribers {sorted(missing)}"
            )
        self._fire(ks)
        for k in ks:
            s = self.subs[k]
            if s.since != by_jid[s.jid]:
                raise RuntimeError(
                    f"replayed fire {rec.seq} advanced subscriber "
                    f"{s.jid} to {s.since}, journal recorded "
                    f"{by_jid[s.jid]}"
                )

    # -- accounting ---------------------------------------------------------

    def _record_stats(
        self,
        changeset_id: int,
        removed: np.ndarray,
        added: np.ndarray,
        results: List[Optional[EvalOutputs]],
        fired: List[int],
        n_passes: int,
        t0: float,
    ) -> None:
        # fanned-out deliveries share one EvalOutputs per lane group: fetch
        # each distinct result once and weight by its member count, so stats
        # stay O(distinct interests) host syncs per call. A fired subscriber
        # whose delivery failed has no committed result (None): its work is
        # counted when the retry eventually acks.
        uniq: Dict[int, Tuple[EvalOutputs, int]] = {}
        for k in fired:
            o = results[k]
            if o is None:
                continue
            ent = uniq.get(id(o))
            uniq[id(o)] = (o, 1 if ent is None else ent[1] + 1)
        self.stats.append(
            BrokerStats(
                changeset_id=changeset_id,
                n_subscribers=len(self.subs),
                n_lanes=self.bank.n_lanes,
                n_lanes_raw=self._lanes_raw,
                total_removed=int(removed.shape[0]),
                total_added=int(added.shape[0]),
                interesting_removed=sum(
                    int(o.r.n) * c for o, c in uniq.values()
                ),
                interesting_added=sum(
                    int(o.a.n) * c for o, c in uniq.values()
                ),
                elapsed_s=time.perf_counter() - t0,
                rejit_s=self._rejit_acc,
                n_evaluated=len(fired),
                n_deferred=len(self.subs) - len(fired),
                n_cohort_passes=n_passes,
                batch_grows=self.batch_grows,
                batch_shrinks=self.batch_shrinks,
                rows_matched=self._rows_matched_acc,
                rows_distinct=self._rows_distinct_acc,
                distinct_interests=self._distinct_acc,
                fanout_copies=self._fanout_acc,
                seq=self._seq,
                degraded_fires=self._degraded_acc,
                compiles=tracing.compile_count() - self._compiles0,
            )
        )
