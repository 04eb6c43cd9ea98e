"""Property-based tests: jitted evaluator == pure-python oracle + invariants.

Small dense id universes force binding collisions; the fan-out cap K is sized
above the maximum possible τ fan-out so the capped evaluator is exact
(DESIGN.md §1).
"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core import (
    Dictionary,
    InterestExpr,
    StepCapacities,
    from_array,
    make_interest_step,
    to_set,
)
from repro.core.evaluation import build_index, make_side_evaluator
from repro.core.interest import compile_interest
from repro.core.oracle import OracleEvaluator
from repro.core.triples import (
    PAD,
    apply_changeset,
    difference,
    from_numpy,
    intersection,
    select,
    union,
)

# ---------------------------------------------------------------------------
# fixed mini-universe: subjects s0..s5, predicates p0..p3 + type, objects/classes
# ---------------------------------------------------------------------------
DICT = Dictionary()
TERMS = (
    [f"s{i}" for i in range(6)]
    + ["type", "p0", "p1", "p2", "goals", "label"]
    + [f"o{i}" for i in range(6)]
    + ["Athlete", "Team"]
)
for t in TERMS:
    DICT.encode_term(t)
R_CAP = DICT.id_capacity
K = 8  # >= max τ fan-out given <=8-row τ sets below

PLANS = {
    "star2": InterestExpr.parse(
        "g", "t",
        bgp=[("?a", "type", "Athlete"), ("?a", "goals", "?g")],
    ),
    "star2_ogp": InterestExpr.parse(
        "g", "t",
        bgp=[("?a", "type", "Athlete"), ("?a", "goals", "?g")],
        ogp=[("?a", "p0", "?h")],
    ),
    "single": InterestExpr.parse("g", "t", bgp=[("?a", "goals", "?g")]),
    "football": InterestExpr.parse(
        "g", "t",
        bgp=[
            ("?f", "type", "Athlete"),
            ("?f", "p1", "?t"),
            ("?t", "label", "?n"),
        ],
    ),
    "object_root": InterestExpr.parse(
        "g", "t",
        bgp=[("?x", "p0", "?a"), ("?a", "type", "Athlete")],
    ),
}
COMPILED = {k: compile_interest(e, DICT) for k, e in PLANS.items()}
ORACLES = {k: OracleEvaluator(p) for k, p in COMPILED.items()}
M_CAP, OUT_CAP, PULL_CAP = 16, 64, 4096
EVALS = {
    k: make_side_evaluator(
        p, id_capacity=R_CAP, fanout=K, out_capacity=OUT_CAP,
        pull_capacity=PULL_CAP,
    )
    for k, p in COMPILED.items()
}
CAPS = StepCapacities(n_removed=M_CAP, n_added=M_CAP, tau=64, rho=64,
                      pulls=PULL_CAP, fanout=K)
STEPS = {
    k: make_interest_step(p, id_capacity=R_CAP, caps=CAPS)
    for k, p in COMPILED.items()
}

SUBJ = [DICT.lookup(f"s{i}") for i in range(6)]
PRED = [DICT.lookup(x) for x in ("type", "p0", "p1", "goals", "label")]
OBJ = [DICT.lookup(x) for x in ("Athlete", "Team", "o0", "o1", "o2")] + SUBJ[:3]


def triple_strategy():
    return st.tuples(
        st.sampled_from(SUBJ), st.sampled_from(PRED), st.sampled_from(OBJ)
    )


def triple_set(max_size):
    return st.sets(triple_strategy(), max_size=max_size)


def np_rows(tris):
    if not tris:
        return np.zeros((0, 3), np.int32)
    return np.asarray(sorted(tris), np.int32)


HSETTINGS = settings(
    max_examples=60,
    deadline=None,
    suppress_health_check=[HealthCheck.too_slow],
)


@given(
    plan_key=st.sampled_from(sorted(PLANS)),
    m=triple_set(10),
    tau=triple_set(8),
)
@HSETTINGS
def test_side_evaluation_matches_oracle(plan_key, m, tau):
    ev = EVALS[plan_key]
    orc = ORACLES[plan_key]
    m_store = from_numpy(np_rows(m), M_CAP)
    tau_store = from_numpy(np_rows(tau), 64)
    res = ev(m_store, build_index(tau_store))
    o_inter, o_pot, o_pulls = orc.evaluate_side(set(m), set(tau))
    assert to_set(res.interesting) == o_inter, plan_key
    assert to_set(res.potential) == o_pot, plan_key
    assert to_set(res.pulls) == o_pulls, plan_key
    assert not bool(res.overflow)
    # partition invariants (Defs 8-10): interesting/potential ⊆ M, disjoint
    assert o_inter <= m and o_pot <= m and not (o_inter & o_pot)


@given(
    plan_key=st.sampled_from(sorted(PLANS)),
    d_set=triple_set(8),
    a_set=triple_set(8),
    tau=triple_set(8),
    rho=triple_set(6),
)
@HSETTINGS
def test_full_step_matches_oracle(plan_key, d_set, a_set, tau, rho):
    step = STEPS[plan_key]
    orc = ORACLES[plan_key]
    tau1, rho1, out = step(
        from_numpy(np_rows(d_set), M_CAP),
        from_numpy(np_rows(a_set), M_CAP),
        from_numpy(np_rows(tau), 64),
        from_numpy(np_rows(rho), 64),
    )
    o = orc.step(set(d_set), set(a_set), set(tau), set(rho))
    assert not bool(out.overflow)
    assert to_set(out.r) == o["r"], plan_key
    assert to_set(out.r_i) == o["r_i"], plan_key
    assert to_set(out.r_prime) == o["r_prime"], plan_key
    assert to_set(out.a) == o["a"], plan_key
    assert to_set(out.a_i) == o["a_i"], plan_key
    assert to_set(tau1) == o["tau1"], plan_key
    assert to_set(rho1) == o["rho1"], plan_key
    # τ and ρ stay disjoint-by-role: promoted triples must leave ρ
    assert not (to_set(rho1) & o["a"])


@given(plan_key=st.sampled_from(sorted(PLANS)), tau=triple_set(8), rho=triple_set(6))
@HSETTINGS
def test_empty_changeset_is_identity(plan_key, tau, rho):
    """Identity holds for *reachable* ρ states (no parked full matches —
    α over I = A ∪ ρ legitimately promotes those even when A = ∅)."""
    orc = ORACLES[plan_key]
    promoted, _, _ = orc.evaluate_side(set(rho), set(tau))
    rho = rho - promoted
    step = STEPS[plan_key]
    z = from_numpy(np.zeros((0, 3), np.int32), M_CAP)
    tau1, rho1, out = step(
        z, z, from_numpy(np_rows(tau), 64), from_numpy(np_rows(rho), 64)
    )
    assert to_set(tau1) == tau
    assert to_set(rho1) == rho
    assert int(out.r.n) == 0 and int(out.a.n) == 0


@given(a=triple_set(20), b=triple_set(20))
@HSETTINGS
def test_set_algebra_matches_python(a, b):
    sa = from_numpy(np_rows(a), 32)
    sb = from_numpy(np_rows(b), 32)
    u, ovf = union(sa, sb, 64)
    assert to_set(u) == a | b and not bool(ovf)
    assert to_set(difference(sa, sb)) == a - b
    assert to_set(intersection(sa, sb)) == a & b


@given(
    a=triple_set(20),
    b=triple_set(20),
    cap_b=st.sampled_from([1, 20, 48]),
    cap=st.sampled_from([8, 40, 64]),
)
@HSETTINGS
def test_merge_union_equals_sorting_union(a, b, cap_b, cap):
    """The merge-path union yields exactly the rows, order, PAD tail, count
    and overflow flag that sorting both stores' rows together does."""
    sa, _ = from_array(jnp.asarray(np_rows(a).reshape(-1, 3)), 32)
    sb, _ = from_array(jnp.asarray(np_rows(b).reshape(-1, 3)), cap_b)
    got, ovf = union(sa, sb, cap)
    want, want_ovf = from_array(jnp.concatenate([sa.spo, sb.spo]), cap)
    np.testing.assert_array_equal(np.asarray(got.spo), np.asarray(want.spo))
    assert int(got.n) == int(want.n)
    assert bool(ovf) == bool(want_ovf)


@given(
    a=triple_set(20),
    bits=st.integers(0, 2**32 - 1),
    cap=st.sampled_from([4, 32]),
)
@HSETTINGS
def test_select_equals_from_array_of_kept_rows(a, bits, cap):
    """A store's kept rows without a sort == from_array over the masked
    rows (the evaluator's former path)."""
    sa = from_numpy(np_rows(a), 32)
    keep = jnp.asarray(((bits >> np.arange(32)) & 1).astype(bool))
    got, ovf = select(sa, keep, cap)
    want, want_ovf = from_array(jnp.where(keep[:, None], sa.spo, PAD), cap)
    np.testing.assert_array_equal(np.asarray(got.spo), np.asarray(want.spo))
    assert int(got.n) == int(want.n)
    assert bool(ovf) == bool(want_ovf)


@given(v=triple_set(20), d_set=triple_set(10), a_set=triple_set(10))
@HSETTINGS
def test_changeset_application_def6(v, d_set, a_set):
    """υ(V, Δ) = (V \\ D) ∪ A — Definition 6."""
    sv = from_numpy(np_rows(v), 64)
    sd = from_numpy(np_rows(d_set), 16)
    sa = from_numpy(np_rows(a_set), 16)
    v1, ovf = apply_changeset(sv, sd, sa)
    assert to_set(v1) == (v - d_set) | a_set
    assert not bool(ovf)


def _commit_cases(tau, extra, full, masks):
    """τ (filled to its 12-row capacity when ``full``) and r, r', a drawn by
    bit masks from τ's rows and rows absent from τ, so that removed rows
    miss τ, r and r' share rows, added rows are in τ or re-add removed
    rows, and any delta may be empty."""
    tau = set(tau)
    if full:
        universe = (
            (s, p, o) for s in SUBJ for p in PRED for o in OBJ
        )
        for row in universe:
            if len(tau) == 12:
                break
            tau.add(row)
    pool = sorted(tau) + sorted(set(extra) - tau)
    r, r2, a = (
        {row for i, row in enumerate(pool) if m >> i & 1} for m in masks
    )
    return tau, r, r2, a


@partial(jax.jit, static_argnums=4)
def _commit_by_whole_stores(tau, r, r2, a, cap):
    return union(difference(difference(tau, r), r2), a, cap)


def _assert_same_store(got, want):
    (g, g_ovf), (w, w_ovf) = got, want
    np.testing.assert_array_equal(np.asarray(g.spo), np.asarray(w.spo))
    np.testing.assert_array_equal(np.asarray(g.n), np.asarray(w.n))
    np.testing.assert_array_equal(np.asarray(g_ovf), np.asarray(w_ovf))


@given(
    tau=triple_set(12),
    extra=triple_set(6),
    full=st.booleans(),
    masks=st.tuples(*[st.integers(0, 2**18 - 1)] * 3),
    cap_vs_result=st.sampled_from([-2, 0, 3]),
)
@HSETTINGS
def test_delta_commit_equals_whole_store_commit(
    tau, extra, full, masks, cap_vs_result
):
    """τ' = ((τ \\ r) \\ r') ∪ a by the changed rows alone: the same rows,
    PAD tail, count and overflow flag as the whole-store composition, with
    the output capacity below, at or above the result's size."""
    tau, r, r2, a = _commit_cases(tau, extra, full, masks)
    cap = max(1, len(((tau - r) - r2) | a) + cap_vs_result)
    args = (
        from_numpy(np_rows(tau), 12),
        from_numpy(np_rows(r), 24),
        from_numpy(np_rows(r2), 24),
        from_numpy(np_rows(a), 24),
    )
    want = _commit_by_whole_stores(*args, cap)
    tau_s, r_s, r2_s, a_s = args
    _assert_same_store(apply_changeset(tau_s, (r_s, r2_s), a_s, cap), want)
    assert bool(want[1]) == (len(((tau - r) - r2) | a) > cap)


@given(
    tau=triple_set(8),
    extras=st.tuples(triple_set(6), triple_set(6)),
    masks=st.tuples(*[st.integers(0, 2**18 - 1)] * 6),
)
@HSETTINGS
def test_delta_commit_under_vmap(tau, extras, masks):
    """The commit vmapped over two cohort members, one τ of at most 8 rows
    and one full at 12, each with its own delta, equals each member's
    whole-store composition."""
    stores = []
    for full, extra, m in zip((False, True), extras, (masks[:3], masks[3:])):
        member_tau, r, r2, a = _commit_cases(tau, extra, full, m)
        stores.append((
            from_numpy(np_rows(member_tau), 12),
            from_numpy(np_rows(r), 24),
            from_numpy(np_rows(r2), 24),
            from_numpy(np_rows(a), 24),
        ))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *stores)
    got = jax.vmap(lambda t, r, r2, a: apply_changeset(t, (r, r2), a, 16))(
        *stacked
    )
    for i, args in enumerate(stores):
        _assert_same_store(
            jax.tree.map(lambda x: x[i], got),
            _commit_by_whole_stores(*args, 16),
        )


@given(a=triple_set(30))
@HSETTINGS
def test_union_overflow_flag(a):
    sa = from_numpy(np_rows(a), 32)
    small_cap = max(1, len(a) - 1) if a else 1
    u, ovf = union(sa, sa, small_cap)
    assert bool(ovf) == (len(a) > small_cap)


def test_replica_consistency_over_stream():
    """Mirror-equivalence: for an all-matching interest, iRap == full mirror."""
    d = Dictionary()
    expr = InterestExpr.parse("g", "t", bgp=[("?s", "?p", "?o")])
    plan = compile_interest(expr, d)
    # a single all-wildcard pattern: everything is interesting
    caps = StepCapacities(n_removed=16, n_added=16, tau=128, rho=64, pulls=64)
    step = make_interest_step(plan, id_capacity=64, caps=caps)
    rng = np.random.default_rng(0)
    tau = from_numpy(np.zeros((0, 3), np.int32), 128)
    rho = from_numpy(np.zeros((0, 3), np.int32), 64)
    mirror: set = set()
    for _ in range(6):
        d_rows = rng.integers(0, 8, size=(rng.integers(0, 6), 3)).astype(np.int32)
        a_rows = rng.integers(0, 8, size=(rng.integers(0, 8), 3)).astype(np.int32)
        tau, rho, out = step(
            from_numpy(np.unique(d_rows, axis=0), 16),
            from_numpy(np.unique(a_rows, axis=0), 16),
            tau,
            rho,
        )
        mirror = (mirror - {tuple(r) for r in d_rows.tolist()}) | {
            tuple(r) for r in a_rows.tolist()
        }
        assert to_set(tau) == mirror
        assert int(rho.n) == 0


@given(
    plan_key=st.sampled_from(sorted(PLANS)),
    m=triple_set(10),
    tau=triple_set(8),
)
@HSETTINGS
def test_candidate_dedup_preserves_semantics(plan_key, m, tau):
    """§Perf HC-C: the dedup'd probe pools are a pure optimization."""
    ev = make_side_evaluator(
        COMPILED[plan_key], id_capacity=R_CAP, fanout=K,
        out_capacity=OUT_CAP, pull_capacity=PULL_CAP, dedup_candidates=64,
    )
    m_store = from_numpy(np_rows(m), M_CAP)
    tau_store = from_numpy(np_rows(tau), 64)
    res = ev(m_store, build_index(tau_store))
    base = EVALS[plan_key](m_store, build_index(tau_store))
    assert to_set(res.interesting) == to_set(base.interesting)
    assert to_set(res.potential) == to_set(base.potential)
    assert to_set(res.pulls) == to_set(base.pulls)
    assert not bool(res.overflow)
