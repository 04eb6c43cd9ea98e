"""Interest evaluation combination and update propagation (Defs 6, 13-18).

``make_interest_step`` builds the fully jitted per-changeset step for one
interest expression:

    d(i, D)        -> <r, r_i, r'>          (Def 13, over deleted triples)
    α(i, A ∪ ρ)    -> <a, a_i, a'>          (Def 14, over added ∪ potential)
    Δ(τ) = <r ∪ r', a>                      (Def 16)
    Δ(ρ) = <r_i, a_i ∪ r'>                  (Def 17)
    Υ: τ' = (τ \\ (r ∪ r')) ∪ a             (Def 18)
       ρ' = ((ρ \\ r_i) ∪ a_i ∪ r') \\ a    (Def 17 + promotion fix, DESIGN §1)

The host-side :class:`IrapEngine` owns the capacities, re-jits on overflow
(store growth) or dictionary growth, and exposes per-changeset statistics —
the production control loop around the pure functional core.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .dictionary import Dictionary
from .evaluation import SideResult, TripleIndex, build_index, make_side_evaluator
from .interest import (
    CompiledInterest,
    InterestExpr,
    compile_interest,
    next_pow2,
)
from .triples import (
    PAD,
    TripleStore,
    apply_changeset,
    difference,
    empty,
    from_array,
    member,
    rehome,
    store_from_np,
    to_numpy,
    union,
)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["r", "r_i", "r_prime", "a", "a_i", "overflow"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class EvalOutputs:
    """The named sets of Definitions 13-17 for one changeset."""

    r: TripleStore  # interesting removed
    r_i: TripleStore  # potentially interesting removed
    r_prime: TripleStore  # τ triples that become potentially interesting
    a: TripleStore  # interesting added (incl. τ completions)
    a_i: TripleStore  # potentially interesting added
    overflow: jax.Array


@dataclasses.dataclass(frozen=True)
class StepCapacities:
    n_removed: int = 1024  # D capacity
    n_added: int = 1024  # A capacity
    tau: int = 4096
    rho: int = 4096
    pulls: int = 2048
    fanout: int = 4
    # §Perf HC-C: candidate-dedup probe pool cap (0 = paper-faithful naive)
    dedup_candidates: int = 0
    # re-jit headroom: signature tables sized to headroom x dictionary size
    id_headroom: int = 4

    @property
    def n_i(self) -> int:  # I = A ∪ ρ
        return self.n_added + self.rho

    def doubled(self) -> "StepCapacities":
        return dataclasses.replace(
            self,
            n_removed=self.n_removed * 2,
            n_added=self.n_added * 2,
            tau=self.tau * 2,
            rho=self.rho * 2,
            pulls=self.pulls * 2,
            dedup_candidates=self.dedup_candidates * 2,
        )


def combine_side_results(
    d_res: SideResult,
    a_res: SideResult,
    tau: TripleStore,
    rho: TripleStore,
    caps: StepCapacities,
    extra_overflow,
) -> Tuple[TripleStore, TripleStore, EvalOutputs]:
    """Combine the two side evaluations into Δ(τ), Δ(ρ), Υ (Defs 16-18).

    Shared by the single-interest step and the multi-subscriber broker's
    fused step (:mod:`repro.core.broker`) so both paths are the same traced
    computation — the broker's per-subscriber outputs stay bit-identical to
    N independent :func:`make_interest_step` runs by construction.

    τ′ is committed by :func:`repro.core.triples.apply_changeset`, whose
    work follows the changed rows, not τ's capacity. ρ′ keeps the four
    whole-store steps (``difference`` / ``union``): their overflow flags
    are raised on intermediate results (``ovf_r1``, ``ovf_r2``), which one
    delta apply would not reproduce, and ρ is a small store (2^15 rows
    against τ's 2^20 in the benchmark's cells), so each pass costs about
    1/32 of what one over τ did.
    """
    a_cap = caps.n_i + caps.pulls
    r, r_i, r_prime = d_res.interesting, d_res.potential, d_res.pulls
    a, ovf_a = union(a_res.interesting, a_res.pulls, a_cap)
    a_i = a_res.potential

    # Υ (Def 18): target first removes r ∪ r', then adds a, by the changed
    # rows alone: τ holds up to 2^20 rows, a changeset changes hundreds
    tau1, ovf_t = apply_changeset(tau, (r, r_prime), a, caps.tau)

    # ρ' = ((ρ \ r_i) ∪ a_i ∪ r') \ a   (promotion fix)
    rho1 = difference(rho, r_i)
    rho1, ovf_r1 = union(rho1, a_i, caps.rho)
    rho1, ovf_r2 = union(rho1, r_prime, caps.rho)
    rho1 = difference(rho1, a)

    overflow = (
        d_res.overflow
        | a_res.overflow
        | extra_overflow
        | ovf_a
        | ovf_t
        | ovf_r1
        | ovf_r2
    )
    out = EvalOutputs(
        r=r, r_i=r_i, r_prime=r_prime, a=a, a_i=a_i, overflow=overflow
    )
    return tau1, rho1, out


@partial(jax.jit, static_argnames=("capacity",))
def compose_changesets(
    d1: TripleStore,
    a1: TripleStore,
    d2: TripleStore,
    a2: TripleStore,
    capacity: int,
) -> Tuple[TripleStore, TripleStore, jax.Array]:
    """Sequential composition of two changesets under Definition 6.

    Applying ``<D1, A1>`` then ``<D2, A2>`` to any store equals applying the
    single composed changeset ``<D1 ∪ D2, (A1 \\ D2) ∪ A2>`` (delete-first
    ordering makes late adds win over early deletes and late deletes cancel
    early adds). The broker's push scheduler uses this to accumulate pending
    deltas host-side for slow-cadence subscribers, so a policy firing after k
    changesets routes **one** batched evaluation through the fused pass.

    Returns ``(d, a, overflowed)`` at the given output capacity.
    """
    d, ovf_d = union(d1, d2, capacity)
    a, ovf_a = union(difference(a1, d2), a2, capacity)
    return d, a, ovf_d | ovf_a


@dataclasses.dataclass(frozen=True)
class FrontierChain:
    """Delta-encoded view of the D sides of several overlapping frontiers.

    Flush frontiers overlap by construction: every live
    :class:`ChangesetBatch` composes a *suffix* of the changeset stream, so
    a row deleted once appears in the composed D of every frontier whose
    suffix covers it. Evaluating each frontier's D independently therefore
    re-matches the shared rows once per frontier. The chain factors that
    redundancy out into

    ``union``
        the lex-sorted store of the **distinct** D rows across all chained
        frontiers (under Definition 6 the D sides compose by pure union, so
        the union of a set of suffix-frontiers *is* the oldest frontier's
        composed D — the chain re-homes it, never re-sorts);

    ``seg``
        int32 per-row membership bitmap over the union rows: bit ``f`` set
        iff union row ``i`` is in frontier ``f``'s composed D. Membership
        is established by per-frontier binary-search probes of the union
        rows against each frontier's own store — **not** by a prefix-OR
        over the chain: the A sides compose non-monotonically (a row
        added, removed, then re-added flips membership between frontiers),
        so masks-by-probe is the primitive that stays correct for any
        store handed in, and ``covered`` proves the D-side containment
        instead of assuming it.

    ``covered``
        host bool: True iff every chained frontier's store is fully
        contained in the union (``|union ∩ D_f| == |D_f|`` for all f).
        The broker falls back to the stacked per-frontier pass when this
        fails, so a chain can never silently drop rows.

    One segmented bank-match pass over ``union``
    (:func:`repro.kernels.ops.pattern_bitmask_words_segmented`) then yields
    every frontier's match words — each distinct row is matched exactly
    once, and rows outside a frontier carry zero words, which the
    evaluator's zero-bits discipline turns into "contributes no candidates,
    no signatures, no outputs".
    """

    union: TripleStore  # distinct D rows across the chained frontiers
    seg: jax.Array  # int32[cap] membership bitmap (bit f = frontier f)
    covered: bool  # every frontier's rows found in the union
    n_frontiers: int


@jax.jit
def _chain_membership(
    union: TripleStore, stores: Tuple[TripleStore, ...]
) -> Tuple[jax.Array, jax.Array]:
    """(seg bitmap over union rows, all-frontiers-covered flag)."""
    valid = union.spo[:, 0] != PAD
    seg = jnp.zeros((union.spo.shape[0],), jnp.int32)
    covered = jnp.ones((), bool)
    for f, st in enumerate(stores):
        m = member(st, union.spo) & valid
        seg = seg | (m.astype(jnp.int32) << f)
        covered = covered & (jnp.sum(m, dtype=jnp.int32) == st.n)
    return seg, covered


def build_frontier_chain(
    d_stores: Sequence[TripleStore], base: int, capacity: int
) -> FrontierChain:
    """Chain the D sides of the fired frontiers for one segmented pass.

    ``d_stores`` are the frontiers' composed device stores (any
    capacities, any order — index ``f`` becomes membership bit ``f``);
    ``base`` names the frontier whose store is the distinct-row union
    (the oldest fired frontier under Definition 6 suffix composition).
    The union re-homes to ``capacity`` (pad/slice, never re-sort; the
    caller's capacity guard ensures the base rows fit) and membership is
    probed per frontier, so the result is correct — or reports
    ``covered=False`` — even for stores that violate the suffix-nesting
    assumption. Syncs one device bool per call (at fire points only,
    matching :meth:`ChangesetBatch.row_bounds` discipline).
    """
    union = rehome(d_stores[base], capacity)
    # re-home every store to the flush capacity so the jitted membership
    # pass sees ONE shape signature per (capacity, n_frontiers) — batch
    # buckets vary per round and would otherwise retrace every flush
    homed = tuple(rehome(st, capacity) for st in d_stores)
    seg, covered = _chain_membership(union, homed)
    return FrontierChain(
        union=union,
        seg=seg,
        covered=bool(covered),
        n_frontiers=len(d_stores),
    )


@dataclasses.dataclass
class ChangesetBatch:
    """Host-managed accumulator of composed, not-yet-delivered changesets
    (the composition itself runs through the device triple-set algebra).

    One batch exists per distinct consumption frontier (`first_id`): every
    subscriber whose push policy has deferred the same suffix of the stream
    shares one batch, so accumulation cost scales with the number of distinct
    cadences, not subscribers. Capacities double transparently on overflow
    and *decay* back down at drain points: a long-lived slow-cadence
    frontier that once absorbed a burst would otherwise hold its peak pow2
    bucket forever, so the broker calls :meth:`maybe_decay` after each fire
    and the batch re-homes to the smaller bucket once its live rows have
    padded below half the allocation for ``patience`` consecutive checks
    (:func:`repro.core.triples.rehome` makes the shrink a device-side
    slice — no re-sort, no transfer). ``grow_count`` and
    :meth:`maybe_decay`'s return value feed the broker's capacity
    accounting (``BrokerStats.batch_grows`` / ``batch_shrinks``).

    **Device-resident contract.** Once composed (``n_changesets > 1``, or
    after :meth:`device_stores`), the batch owns two lex-sorted, deduped
    device :class:`~repro.core.triples.TripleStore` values at a power-of-two
    ``capacity``; they are immutable between :meth:`extend` calls, and the
    only host state kept alongside is bookkeeping (`first_id`/`last_id`,
    ``n_changesets``) plus the valid-row counts behind :meth:`row_bounds`
    (synced lazily — two device scalars read once per *fire*, never on the
    per-changeset ingest path). A scheduled fire therefore consumes the batch without a
    device→host→device round trip: :meth:`device_stores` hands the sorted
    stores straight to the evaluator, which re-homes them with
    :func:`repro.core.triples.rehome` (pad/slice, never re-sort) only when
    a cohort's padded capacity differs. ``arrays()`` remains the host
    escape hatch for the round-trip baseline path and external consumers.

    **Row provenance across composition.** ``first_id``/``last_id`` name
    the exact changeset suffix a batch has composed, and Definition 6
    composes the D sides by pure union — so when several frontiers fire
    together, the batch with the smallest ``first_id`` provably holds the
    distinct-row union of every fired D side, and each row's provenance
    (which frontiers contain it) is recoverable by a lex probe of its own
    sorted store. :func:`build_frontier_chain` packages exactly that as a
    :class:`FrontierChain` — union store + per-frontier int32 membership
    bitmap + a containment proof — so the flush evaluator can match each
    distinct row once and compose per-frontier bitsets by masking instead
    of re-matching the shared suffix rows once per frontier.
    """

    removed: TripleStore | None  # composed D (device); None while n == 1
    added: TripleStore | None  # composed A (device); None while n == 1
    removed_np: np.ndarray  # raw first changeset (fast path for n == 1)
    added_np: np.ndarray
    n_changesets: int
    first_id: int
    last_id: int
    capacity: int
    # valid rows of the composed stores, synced lazily by row_bounds()
    # (None = stale); raw-row upper bounds while the batch holds one raw
    # changeset
    d_rows: int | None = None
    a_rows: int | None = None
    # capacity lifecycle accounting: pow2 doublings since creation
    grow_count: int = 0
    _decay_streak: int = 0

    @staticmethod
    def fresh(
        removed: np.ndarray, added: np.ndarray, changeset_id: int
    ) -> "ChangesetBatch":
        cap = max(64, int(removed.shape[0]), int(added.shape[0]))
        return ChangesetBatch(
            removed=None,
            added=None,
            # copy: the batch may outlive the caller's (reusable) buffers
            removed_np=np.array(removed, np.int32, copy=True),
            added_np=np.array(added, np.int32, copy=True),
            n_changesets=1,
            first_id=changeset_id,
            last_id=changeset_id,
            capacity=next_pow2(cap),
        )

    def _materialize(self) -> None:
        while True:
            d, ovf_d = store_from_np(self.removed_np, self.capacity)
            a, ovf_a = store_from_np(self.added_np, self.capacity)
            if not (ovf_d or ovf_a):
                self.removed, self.added = d, a
                self.d_rows = self.a_rows = None
                return
            self.capacity *= 2
            self.grow_count += 1

    def extend(
        self, removed: np.ndarray, added: np.ndarray, changeset_id: int
    ) -> None:
        """Fold one more raw changeset into the composed batch."""
        if self.removed is None:
            self._materialize()
        need = max(int(removed.shape[0]), int(added.shape[0]))
        while self.capacity < need:
            self.capacity *= 2
            self.grow_count += 1
        d2, _ = store_from_np(removed, self.capacity)
        a2, _ = store_from_np(added, self.capacity)
        while True:
            d, a, overflow = compose_changesets(
                self.removed, self.added, d2, a2, self.capacity
            )
            if not bool(overflow):
                break
            self.capacity *= 2
            self.grow_count += 1
        self.removed, self.added = d, a
        self.d_rows = self.a_rows = None  # synced lazily at fire time
        self.n_changesets += 1
        self.last_id = changeset_id

    def row_bounds(self) -> Tuple[int, int]:
        """(D rows, A rows) of the composed batch, for capacity guards.

        Exact valid-row counts once composed (synced from the device
        scalars on first use after an :meth:`extend`, i.e. once per fire);
        raw-row upper bounds while the batch still holds a single raw
        changeset.
        """
        if self.removed is None:
            return int(self.removed_np.shape[0]), int(self.added_np.shape[0])
        if self.d_rows is None:
            self.d_rows = int(self.removed.n)
            self.a_rows = int(self.added.n)
        return self.d_rows, self.a_rows

    def maybe_decay(self, patience: int = 2, floor: int = 64) -> bool:
        """Re-home to a smaller pow2 bucket after sustained under-fill.

        Called by the broker at drain points (fires / flushes — never on the
        per-changeset ingest path, so no extra device-scalar syncs there).
        When the composed live rows would pad to at most *half* the current
        allocation for ``patience`` consecutive checks, both stores re-home
        to that smaller power-of-two bucket via
        :func:`repro.core.triples.rehome` — a pure pad/slice, so the shrink
        costs no re-sort and no host transfer. A single burst therefore
        never thrashes the capacity down (the streak resets on any
        well-filled check), while a frontier that has genuinely quieted
        releases its peak allocation. Returns True when a shrink happened.
        """
        if self.removed is None:
            return False
        d_rows, a_rows = self.row_bounds()
        want = max(floor, next_pow2(max(d_rows, a_rows, 1)))
        if want > self.capacity // 2:
            self._decay_streak = 0
            return False
        self._decay_streak += 1
        if self._decay_streak < patience:
            return False
        self.removed = rehome(self.removed, want)
        self.added = rehome(self.added, want)
        self.capacity = want
        self._decay_streak = 0
        return True

    def device_stores(self) -> Tuple[TripleStore, TripleStore]:
        """The composed batch as device stores (D, A) — no host transfer
        beyond the one-time upload of a single-changeset batch."""
        if self.removed is None:
            self._materialize()
        return self.removed, self.added

    def arrays(self) -> Tuple[np.ndarray, np.ndarray]:
        """The composed batch as dense host arrays (D, A)."""
        if self.removed is None:
            return self.removed_np, self.added_np
        return to_numpy(self.removed), to_numpy(self.added)


def make_interest_step(
    plan: CompiledInterest,
    *,
    id_capacity: int,
    caps: StepCapacities,
    matcher=None,
) -> Callable:
    """Jitted (D, A, τ, ρ) -> (τ', ρ', EvalOutputs) for one interest."""
    eval_d = make_side_evaluator(
        plan,
        id_capacity=id_capacity,
        fanout=caps.fanout,
        out_capacity=caps.n_removed,
        pull_capacity=caps.pulls,
        matcher=matcher,
        dedup_candidates=caps.dedup_candidates,
    )
    eval_a = make_side_evaluator(
        plan,
        id_capacity=id_capacity,
        fanout=caps.fanout,
        out_capacity=caps.n_i,
        pull_capacity=caps.pulls,
        matcher=matcher,
        dedup_candidates=caps.dedup_candidates,
    )
    @jax.jit
    def step(
        d_set: TripleStore,
        a_set: TripleStore,
        tau: TripleStore,
        rho: TripleStore,
    ):
        tgt = build_index(tau)
        d_res = eval_d(d_set, tgt)
        i_set, ovf_i = union(a_set, rho, caps.n_i)
        a_res = eval_a(i_set, tgt)
        return combine_side_results(d_res, a_res, tau, rho, caps, ovf_i)

    return step


@dataclasses.dataclass
class ChangesetStats:
    changeset_id: int
    total_removed: int
    total_added: int
    interesting_removed: int
    interesting_added: int
    potential_size: int
    target_size: int
    elapsed_s: float


class InterestSubscription:
    """One registered interest: its plan, τ, ρ, and jitted step."""

    def __init__(
        self,
        expr: InterestExpr,
        dictionary: Dictionary,
        caps: StepCapacities,
        matcher=None,
    ):
        self.expr = expr
        self.dictionary = dictionary
        self.caps = caps
        self.matcher = matcher
        self.plan = compile_interest(expr, dictionary)
        self.id_capacity = dictionary.id_capacity * caps.id_headroom
        self.tau = empty(caps.tau)
        self.rho = empty(caps.rho)
        self._step = make_interest_step(
            self.plan, id_capacity=self.id_capacity, caps=caps, matcher=matcher
        )

    def _rebuild(self, caps: StepCapacities | None = None):
        if caps is not None:
            self.caps = caps
        # recompile plan so late-registered dictionary constants resolve
        self.plan = compile_interest(self.expr, self.dictionary)
        self.id_capacity = self.dictionary.id_capacity * self.caps.id_headroom
        self._step = make_interest_step(
            self.plan,
            id_capacity=self.id_capacity,
            caps=self.caps,
            matcher=self.matcher,
        )
        # re-home stores into (possibly) larger capacities
        self.tau, _ = union(empty(self.caps.tau), self.tau, self.caps.tau)
        self.rho, _ = union(empty(self.caps.rho), self.rho, self.caps.rho)

    def init_target(self, triples: np.ndarray):
        """Load the initial RDFSlice-style subset into τ (paper §2)."""
        while True:
            store, overflow = from_array(
                jnp.asarray(triples, jnp.int32), self.caps.tau
            )
            if not bool(overflow):
                self.tau = store
                return
            self._rebuild(self.caps.doubled())

    def apply(self, d_np: np.ndarray, a_np: np.ndarray) -> EvalOutputs:
        if self.dictionary.id_capacity > self.id_capacity:
            self._rebuild()
        while True:
            caps = self.caps
            if d_np.shape[0] > caps.n_removed or a_np.shape[0] > caps.n_added:
                self._rebuild(caps.doubled())
                continue
            d_store, _ = from_array(jnp.asarray(d_np, jnp.int32), caps.n_removed)
            a_store, _ = from_array(jnp.asarray(a_np, jnp.int32), caps.n_added)
            tau1, rho1, out = self._step(d_store, a_store, self.tau, self.rho)
            if bool(out.overflow):
                self._rebuild(caps.doubled())
                continue
            self.tau, self.rho = tau1, rho1
            return out


class IrapEngine:
    """Host orchestrator: Interest Manager + Changeset Manager + Evaluator.

    Mirrors the iRap architecture (paper §3): interests are registered, then
    changesets stream through ``process_changeset`` and every subscription's
    τ / ρ stores are updated; per-changeset stats are collected.
    """

    def __init__(self, dictionary: Dictionary | None = None):
        # NB: `dictionary or Dictionary()` would discard an *empty* dict
        # (Dictionary defines __len__), silently splitting the id space.
        self.dictionary = dictionary if dictionary is not None else Dictionary()
        self.subs: List[InterestSubscription] = []
        self.stats: List[ChangesetStats] = []
        self._counter = 0

    def register_interest(
        self,
        expr: InterestExpr,
        caps: StepCapacities = StepCapacities(),
        initial_target: np.ndarray | None = None,
        matcher=None,
    ) -> InterestSubscription:
        sub = InterestSubscription(expr, self.dictionary, caps, matcher=matcher)
        if initial_target is not None and initial_target.size:
            sub.init_target(initial_target)
        self.subs.append(sub)
        return sub

    def process_changeset(
        self, removed: np.ndarray, added: np.ndarray
    ) -> List[ChangesetStats]:
        self._counter += 1
        out_stats = []
        for sub in self.subs:
            t0 = time.perf_counter()
            out = sub.apply(removed, added)
            jax.block_until_ready(sub.tau.spo)
            elapsed = time.perf_counter() - t0
            st = ChangesetStats(
                changeset_id=self._counter,
                total_removed=int(removed.shape[0]),
                total_added=int(added.shape[0]),
                interesting_removed=int(out.r.n),
                interesting_added=int(out.a.n),
                potential_size=int(sub.rho.n),
                target_size=int(sub.tau.n),
                elapsed_s=elapsed,
            )
            out_stats.append(st)
            self.stats.append(st)
        return out_stats
