"""Interest evaluation over changesets (Definitions 11-15, DESIGN.md §1-2).

The evaluator is built per ``CompiledInterest`` by :func:`make_side_evaluator`
— a factory closing over the static plan — and classifies one side of a
changeset (the removed set D, or I = A ∪ ρ for the added side) into

  * interesting triples  (full BGP match over M ∪ τ with >= 1 triple from M),
  * potentially interesting triples (partial match),
  * pulls — the π' candidate-assertion retrievals from the target dataset τ
    (missing BGP patterns + OGP patterns of full bindings; these are r' for
    the delete side and the τ-completion part of `a` for the add side).

Dataflow (all fixed-shape, jit-compiled):
  1. pattern bitset over M            (triple_match kernel / XLA fallback)
  2. generation signature table       (scatter bits per binding  — π, Def 11)
  3. candidate pools + τ probes       (blocked sort-merge probes — π', Def 12)
  4. tree semijoin gating             (child_ok / edge_ok / full / linked_full)
  5. per-triple classification + fixed-capacity compaction
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from .interest import CompiledInterest
from .triples import (
    PAD,
    TripleStore,
    compact,
    from_array,
    lex_sort,
    prefix_range,
    select,
)


@partial(jax.tree_util.register_dataclass, data_fields=["spo", "ops"], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class TripleIndex:
    """Two sort orders over the same triple set (the SPO / OPS indexes)."""

    spo: TripleStore  # rows (s, p, o), lex-sorted
    ops: TripleStore  # rows permuted to (o, p, s), lex-sorted in that order


def build_index(store: TripleStore) -> TripleIndex:
    ops_rows = lex_sort(store.spo[:, jnp.array([2, 1, 0])])
    return TripleIndex(spo=store, ops=TripleStore(spo=ops_rows, n=store.n))


# ---------------------------------------------------------------------------
# cohort pytree helpers (the broker's stacked/batched evaluation plumbing)
# ---------------------------------------------------------------------------

def tree_stack(trees):
    """Stack identical pytrees along a new leading (cohort-member) axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def tree_index(tree, i):
    """Slice one member out of a leading-axis-stacked pytree."""
    return jax.tree.map(lambda x: x[i], tree)


def tree_gather(tree, idx: jax.Array):
    """Gather members of a stacked pytree by a (traced) index vector.

    Used by the broker's shared-τ path: target indexes are built once per
    *unique* target dataset and fanned out to every cohort member via this
    gather, so K subscribers of one replica pay for one ``build_index``.
    """
    return jax.tree.map(lambda x: jnp.take(x, idx, axis=0), tree)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["interesting", "potential", "pulls", "overflow"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class SideResult:
    interesting: TripleStore
    potential: TripleStore
    pulls: TripleStore
    overflow: jax.Array  # bool — any output capacity exceeded


# ---------------------------------------------------------------------------
# target-dataset probe (candidate assertion primitive)
# ---------------------------------------------------------------------------

def probe(
    index: TripleIndex,
    pattern: np.ndarray,  # (3,) int32 host constants, -1 for variable slots
    bound_slot: int,
    bound_vals: jax.Array,  # int32[B]; PAD entries are masked out
    fanout: int,
) -> Tuple[jax.Array, jax.Array]:
    """Retrieve up to ``fanout`` τ rows matching ``pattern`` with one slot bound.

    Returns (rows int32[B, K, 3] in (s, p, o) order, valid bool[B, K]).
    Probes use the SPO index for subject-bound patterns and the OPS index for
    object-bound ones; non-prefix constant slots are post-filtered.
    """
    return probe_dyn(
        index,
        pattern,
        jnp.asarray(pattern, jnp.int32),
        bound_slot,
        bound_vals,
        fanout,
    )


def probe_dyn(
    index: TripleIndex,
    pattern_host: np.ndarray,  # (3,) int32 host row — static const/var structure
    pattern_dev: jax.Array,  # (3,) int32 traced row — comparison values
    bound_slot: int,
    bound_vals: jax.Array,
    fanout: int,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`probe` with traced pattern *values* and static structure.

    The broker's batched (vmapped) path evaluates whole cohorts of
    same-shape interests at once, so the constant slots' values must be
    traced operands (they differ per subscriber) while which slots are
    constant — probe depth, index choice, post-filter set — stays static
    (identical across the cohort by construction). Produces exactly the
    values of :func:`probe` for equal inputs.
    """
    if bound_slot == 1:
        raise ValueError("predicate-bound probes are unsupported (compile-time)")
    const = [int(pattern_host[k]) >= 0 for k in range(3)]
    vals = [pattern_dev[k] for k in range(3)]
    if bound_slot == 0:
        store = index.spo
        (c1_const, c1_val), (c2_const, c2_val) = (
            (const[1], vals[1]),
            (const[2], vals[2]),
        )
    else:
        store = index.ops
        (c1_const, c1_val), (c2_const, c2_val) = (
            (const[1], vals[1]),
            (const[0], vals[0]),
        )
    depth = 1 + (1 if c1_const else 0) + (1 if (c1_const and c2_const) else 0)

    b = bound_vals.shape[0]
    cap = store.capacity
    zero = jnp.zeros((), jnp.int32)
    prefix = jnp.stack(
        [
            bound_vals,
            jnp.broadcast_to(c1_val if c1_const else zero, (b,)),
            jnp.broadcast_to(c2_val if c2_const else zero, (b,)),
        ],
        axis=1,
    )
    start, end = prefix_range(store, prefix, jnp.full((b,), depth, jnp.int32))
    offs = jnp.arange(fanout, dtype=jnp.int32)
    idx = start[:, None] + offs[None, :]
    rows = jnp.take(store.spo, jnp.clip(idx, 0, cap - 1), axis=0)
    valid = (idx < end[:, None]) & (bound_vals != PAD)[:, None]
    if bound_slot == 2:
        rows = rows[..., jnp.array([2, 1, 0])]
    for k in range(3):
        if const[k]:
            valid = valid & (rows[..., k] == vals[k])
    valid = valid & (rows[..., bound_slot] == bound_vals[:, None])
    return rows, valid


# ---------------------------------------------------------------------------
# side evaluator factory
# ---------------------------------------------------------------------------

def make_side_evaluator(
    plan: CompiledInterest,
    *,
    id_capacity: int,
    fanout: int = 4,
    out_capacity: int,
    pull_capacity: int,
    matcher: Callable[[jax.Array, jax.Array], jax.Array] | None = None,
    probe_impl: Callable | None = None,
    table_reduce: Callable[[jax.Array], jax.Array] | None = None,
    dedup_candidates: int = 0,
    dynamic_patterns: bool = False,
) -> Callable[[TripleStore, TripleIndex], SideResult]:
    """Build the jitted one-side evaluator for a compiled interest.

    ``probe_impl``/``table_reduce`` are the distribution hooks
    (core/distributed.py): the sharded evaluator swaps in an all_to_all
    routed probe and an OR-all-reduce over the signature tables; the local
    evaluator uses :func:`probe` / :func:`probe_dyn` and identity.

    ``dynamic_patterns=True`` builds the evaluator for the broker's batched
    cohort path: the returned callable takes the pattern *values* as a
    traced ``patterns`` argument so a whole cohort of same-shape interests
    can be vmapped; ``plan`` then only supplies the static structure (kinds,
    slots, const masks).  The hooks compose with it — the broker's sharded
    cohort step routes cohort probes across the mesh — but the probe hook
    contract changes with the mode, because the pattern constants are traced
    per member:

      static  (default)          ``probe_impl(index, pattern, bound_slot,
                                 bound_vals, fanout)`` — :func:`probe`-shaped,
                                 e.g. ``distributed.make_routed_probe``;
      dynamic (``dynamic_patterns=True``)
                                 ``probe_impl(index, pattern_host,
                                 pattern_dev, bound_slot, bound_vals,
                                 fanout)`` — :func:`probe_dyn`-shaped, e.g.
                                 ``distributed.make_routed_probe_batched``.

    ``table_reduce`` sees boolean signature tables in both modes and must
    batch under ``jax.vmap`` when the cohort path is in play
    (``distributed.make_or_reduce`` does).

    **Refined-lane contract (subsumption lattice).** The evaluator never
    inspects how its per-row lane bits were produced: the broker may hand
    it bits from a *virtual* bank lane — a parent row's word ANDed with a
    residual predicate by ``kernels.ops.lane_refine`` instead of a
    materialized bank row. That substitution is sound only under the
    invariant ``interest.SubsumptionBank`` maintains: the residual binds
    exactly the slots where the parent row has a variable, so
    ``parent AND residual`` equals the bits a materialized child row
    would produce, and everything downstream (candidate extraction,
    probes, output construction) is bit-identical by construction.
    """
    matcher = matcher or kops.pattern_bitmask
    probe_dyn_impl = (probe_impl or probe_dyn) if dynamic_patterns else None
    probe_impl = probe_impl or probe
    table_reduce = table_reduce or (lambda t: t)
    dedup_cap = dedup_candidates

    def maybe_dedup(vec: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Sort-unique a candidate vector to ``dedup_cap`` slots (§Perf HC-C).

        The paper-faithful baseline probes one τ lookup per (M row x
        pattern); bindings repeat heavily (every triple of an entity yields
        the same binding), so deduplicating before the probe collapses the
        probe pool by the mean entity degree. Returns (vec', overflowed).
        """
        if not dedup_cap:
            return vec, jnp.zeros((), bool)
        s = jnp.sort(vec)
        first = jnp.concatenate(
            [jnp.ones((1,), bool), s[1:] != s[:-1]]
        ) & (s != PAD)
        uniq, count = compact(s, first)
        return uniq[:dedup_cap], count > dedup_cap
    R = id_capacity
    K = fanout
    nt = plan.n_total
    patterns_dev = jnp.asarray(plan.patterns)
    kinds = plan.kinds
    anchor = plan.anchor_slot
    cslot = plan.child_slot
    cvar = plan.child_var
    n_children = plan.n_children

    root_js = [j for j in range(nt) if kinds[j] == "root"]
    edge_js = [j for j in range(nt) if kinds[j] == "edge"]
    child_js = [j for j in range(nt) if kinds[j] == "child"]
    bgp_root_js = [j for j in root_js if j < plan.n_bgp]
    bgp_edge_js = [j for j in edge_js if j < plan.n_bgp]
    child_bgp_stars = {
        cv: [j for j in child_js if cvar[j] == cv and j < plan.n_bgp]
        for cv in range(n_children)
    }
    child_all_stars = {
        cv: [j for j in child_js if cvar[j] == cv] for cv in range(n_children)
    }
    edges_of = {
        cv: [e for e in edge_js if cvar[e] == cv] for cv in range(n_children)
    }

    def evaluate(
        m: TripleStore,
        tgt: TripleIndex,
        bits: jax.Array | None = None,
        patterns: jax.Array | None = None,
    ) -> SideResult:
        """Classify one changeset side.

        ``bits`` (optional) is a precomputed uint32[N] pattern bitset in this
        plan's local numbering — the broker's fused path computes one bank
        bitset per changeset side and routes lanes here, skipping the
        per-interest matcher pass. Must equal ``matcher(m.spo, patterns)``.

        ``patterns`` (dynamic_patterns mode only) carries the traced
        (n_total, 3) pattern values for this cohort member.
        """
        pats = patterns if patterns is not None else patterns_dev

        def run_probe(j: int, bound_slot: int, bound_vals: jax.Array):
            if dynamic_patterns:
                return probe_dyn_impl(
                    tgt, plan.patterns[j], pats[j], bound_slot, bound_vals, K
                )
            return probe_impl(tgt, plan.patterns[j], bound_slot, bound_vals, K)

        spo = m.spo
        n = m.capacity
        valid_row = spo[:, 0] != PAD
        if bits is None:
            bits = matcher(spo, pats)
        # repeated-variable-in-pattern equality constraints
        for j, eq in enumerate(plan.eq_pairs):
            if eq is not None:
                ok = spo[:, eq[0]] == spo[:, eq[1]]
                bits = jnp.where(ok, bits, bits & np.uint32(~(1 << j) & 0xFFFFFFFF))

        def bit(j: int) -> jax.Array:
            return ((bits >> j) & 1).astype(bool)

        # -- generation signature table (π) --------------------------------
        sat_gen = jnp.zeros((R, nt), dtype=bool)
        for j in root_js + child_js:
            b = spo[:, anchor[j]]
            idx = jnp.where(bit(j), b, R)  # out-of-range -> dropped
            sat_gen = sat_gen.at[idx, j].max(True, mode="drop")

        sat_gen = table_reduce(sat_gen)

        # -- candidate pools + upward edge discovery -----------------------
        # edge pools: per edge, lists of (b, c, valid, rows, is_pull)
        edge_pool: Dict[int, List[Tuple]] = {e: [] for e in edge_js}
        root_cand_parts = [
            jnp.where(bit(j), spo[:, anchor[j]], PAD) for j in root_js
        ]
        for e in edge_js:
            root_cand_parts.append(jnp.where(bit(e), spo[:, anchor[e]], PAD))
            # M edge rows (not pulls)
            edge_pool[e].append(
                (spo[:, anchor[e]], spo[:, cslot[e]], bit(e), spo, False)
            )
            # upward probes: child-star M bindings -> τ edge rows -> roots
            for j in child_all_stars[cvar[e]]:
                c_vec = jnp.where(bit(j), spo[:, anchor[j]], PAD)
                rows, val = run_probe(e, cslot[e], c_vec)
                rows_f = rows.reshape(-1, 3)
                val_f = val.reshape(-1)
                b_f = rows_f[:, anchor[e]]
                c_f = rows_f[:, cslot[e]]
                edge_pool[e].append((b_f, c_f, val_f, rows_f, True))
                root_cand_parts.append(jnp.where(val_f, b_f, PAD))
        root_cand = (
            jnp.concatenate(root_cand_parts)
            if root_cand_parts
            else jnp.full((n,), PAD, jnp.int32)
        )
        root_cand, ovf_d1 = maybe_dedup(root_cand)

        # -- downward edge probes (per edge, for every root candidate) -----
        for e in edge_js:
            rows, val = run_probe(e, anchor[e], root_cand)
            rows_f = rows.reshape(-1, 3)
            val_f = val.reshape(-1)
            edge_pool[e].append(
                (rows_f[:, anchor[e]], rows_f[:, cslot[e]], val_f, rows_f, True)
            )

        # -- child candidate pools ------------------------------------------
        child_cand: Dict[int, jax.Array] = {}
        for cv in range(n_children):
            parts = [
                jnp.where(bit(j), spo[:, anchor[j]], PAD)
                for j in child_all_stars[cv]
            ]
            for e in edges_of[cv]:
                for b_f, c_f, val_f, rows_f, is_pull in edge_pool[e]:
                    parts.append(jnp.where(val_f, c_f, PAD))
            cc, ovf_dc = maybe_dedup(jnp.concatenate(parts))
            child_cand[cv] = cc
            ovf_d1 = ovf_d1 | ovf_dc

        # -- assertion probes (π') -----------------------------------------
        sat_tgt = jnp.zeros((R, nt), dtype=bool)
        pull_entries = []  # (kind, j, cv, bound, rows, valid)
        for j in child_js:
            cv = cvar[j]
            bound = child_cand[cv]
            rows, val = run_probe(j, anchor[j], bound)
            pull_entries.append(("child", j, cv, bound, rows, val))
            found = jnp.any(val, axis=1)
            sat_tgt = sat_tgt.at[jnp.where(found, bound, R), j].max(
                True, mode="drop"
            )
        for j in root_js:
            rows, val = run_probe(j, anchor[j], root_cand)
            pull_entries.append(("root", j, -1, root_cand, rows, val))
            found = jnp.any(val, axis=1)
            sat_tgt = sat_tgt.at[jnp.where(found, root_cand, R), j].max(
                True, mode="drop"
            )

        sat = sat_gen | table_reduce(sat_tgt)

        # -- tree gating -----------------------------------------------------
        child_ok: Dict[int, jax.Array] = {}
        for cv in range(n_children):
            ok = jnp.ones((R,), dtype=bool)
            for j in child_bgp_stars[cv]:
                ok = ok & sat[:, j]
            child_ok[cv] = ok

        def gather_bool(vec: jax.Array, idx: jax.Array) -> jax.Array:
            return jnp.take(vec, idx, mode="fill", fill_value=False)

        edge_ok: Dict[int, jax.Array] = {}
        for e in edge_js:
            acc = jnp.zeros((R,), dtype=bool)
            for b_f, c_f, val_f, rows_f, is_pull in edge_pool[e]:
                v = val_f & gather_bool(child_ok[cvar[e]], c_f)
                acc = acc.at[jnp.where(v, b_f, R)].max(True, mode="drop")
            edge_ok[e] = table_reduce(acc)

        full = jnp.ones((R,), dtype=bool)
        for j in bgp_root_js:
            full = full & sat[:, j]
        for e in bgp_edge_js:
            full = full & edge_ok[e]
        # only bindings seeded by this changeset can be candidates; ids that
        # never appear keep full=AND(...)=True only if nt==0 — guard:
        if not bgp_root_js and not bgp_edge_js:
            full = jnp.zeros((R,), dtype=bool)

        linked_full: Dict[int, jax.Array] = {}
        for cv in range(n_children):
            acc = jnp.zeros((R,), dtype=bool)
            for e in edges_of[cv]:
                for b_f, c_f, val_f, rows_f, is_pull in edge_pool[e]:
                    v = val_f & gather_bool(full, b_f)
                    acc = acc.at[jnp.where(v, c_f, R)].max(True, mode="drop")
            linked_full[cv] = table_reduce(acc)

        # -- per-triple classification (Defs 8-10) ---------------------------
        inter = jnp.zeros((n,), dtype=bool)
        for j in range(nt):
            bj = bit(j)
            if kinds[j] == "root":
                g = gather_bool(full, spo[:, anchor[j]])
            elif kinds[j] == "edge":
                g = gather_bool(full, spo[:, anchor[j]]) & gather_bool(
                    child_ok[cvar[j]], spo[:, cslot[j]]
                )
            else:
                c = spo[:, anchor[j]]
                g = gather_bool(child_ok[cvar[j]], c) & gather_bool(
                    linked_full[cvar[j]], c
                )
            inter = inter | (bj & g)
        potential = valid_row & (bits != 0) & ~inter

        # -- pull inclusion (π' outputs) --------------------------------------
        pull_rows_parts = []
        pull_mask_parts = []
        for kind, j, cv, bound, rows, val in pull_entries:
            gen_bit_at = jnp.take(
                sat_gen[:, j], bound, mode="fill", fill_value=False
            )
            if kind == "root":
                gate = gather_bool(full, bound) & ~gen_bit_at
            else:
                gate = (
                    gather_bool(child_ok[cv], bound)
                    & gather_bool(linked_full[cv], bound)
                    & ~gen_bit_at
                )
            inc = val & gate[:, None]
            pull_rows_parts.append(rows.reshape(-1, 3))
            pull_mask_parts.append(inc.reshape(-1))
        for e in edge_js:
            for b_f, c_f, val_f, rows_f, is_pull in edge_pool[e]:
                if not is_pull:
                    continue
                inc = (
                    val_f
                    & gather_bool(full, b_f)
                    & gather_bool(child_ok[cvar[e]], c_f)
                )
                pull_rows_parts.append(rows_f)
                pull_mask_parts.append(inc)

        if pull_rows_parts:
            pr = jnp.concatenate(pull_rows_parts, axis=0)
            pm = jnp.concatenate(pull_mask_parts, axis=0)
            pr = jnp.where(pm[:, None], pr, PAD)
        else:
            pr = jnp.full((1, 3), PAD, jnp.int32)
        pulls, ovf_p = from_array(pr, pull_capacity)

        inter_store, ovf_i = select(m, inter, out_capacity)
        pot_store, ovf_q = select(m, potential, out_capacity)

        return SideResult(
            interesting=inter_store,
            potential=pot_store,
            pulls=pulls,
            overflow=ovf_p | ovf_i | ovf_q | ovf_d1,
        )

    return evaluate
