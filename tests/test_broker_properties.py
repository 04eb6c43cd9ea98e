"""Property tests for the multi-subscriber broker (fused == looped).

Random interest sets + changesets: the fused broker step must equal running
the per-interest seed step for every subscriber, including bitset-lane
routing through a deduplicated pattern bank and the >32-pattern chunked
path (two uint32 words). Steps are compiled once per plan combination at
module scope, so hypothesis examples only vary data.
"""
import numpy as np
import jax.numpy as jnp
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core import (
    Dictionary,
    InterestExpr,
    StepCapacities,
    build_pattern_bank,
    make_broker_step,
    make_interest_step,
    to_set,
)
from repro.core.interest import compile_interest
from repro.core.triples import from_numpy
from repro.kernels import ops, ref

# ---------------------------------------------------------------------------
# mini-universe (mirrors test_properties.py) + wide predicate space for the
# chunked >32-lane bank
# ---------------------------------------------------------------------------
DICT = Dictionary()
TERMS = (
    [f"s{i}" for i in range(6)]
    + ["type", "goals", "label"]
    + [f"p{i}" for i in range(36)]
    + [f"o{i}" for i in range(4)]
    + ["Athlete", "Team"]
)
for t in TERMS:
    DICT.encode_term(t)
R_CAP = DICT.id_capacity
K = 8
M_CAP, TAU_CAP, RHO_CAP = 10, 48, 32
CAPS = StepCapacities(
    n_removed=M_CAP, n_added=M_CAP, tau=TAU_CAP, rho=RHO_CAP,
    pulls=4096, fanout=K,
)

EXPRS = {
    "star2": InterestExpr.parse(
        "g", "t", bgp=[("?a", "type", "Athlete"), ("?a", "goals", "?g")]
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
        "g", "t", bgp=[("?x", "p0", "?a"), ("?a", "type", "Athlete")]
    ),
}
# three interests of 12 root-star patterns each over disjoint predicates:
# 36 distinct bank lanes -> 2 bitset words (the chunked path)
for c in range(3):
    EXPRS[f"wide{c}"] = InterestExpr.parse(
        "g", "t",
        bgp=[("?a", f"p{12 * c + i}", "?v%d" % i) for i in range(12)],
    )

PLANS = {k: compile_interest(e, DICT) for k, e in EXPRS.items()}
STEPS = {
    k: make_interest_step(p, id_capacity=R_CAP * CAPS.id_headroom, caps=CAPS)
    for k, p in PLANS.items()
}

COMBOS = {
    "dedup_pair": ("star2", "single"),  # shared goals pattern dedups
    "mixed3": ("star2_ogp", "football", "object_root"),
    "twins": ("star2", "star2"),  # identical interests share every lane
    "chunked": ("wide0", "wide1", "wide2", "star2"),  # 38 raw / 36 lanes? >32
}
BANKS = {name: build_pattern_bank([PLANS[k] for k in keys])
         for name, keys in COMBOS.items()}
BROKER_STEPS = {
    name: make_broker_step(
        BANKS[name],
        [PLANS[k] for k in keys],
        [CAPS] * len(keys),
        [R_CAP * CAPS.id_headroom] * len(keys),
    )
    for name, keys in COMBOS.items()
}
assert BANKS["chunked"].n_lanes > 32 and BANKS["chunked"].n_words == 2
assert BANKS["twins"].n_lanes == PLANS["star2"].n_total

SUBJ = [DICT.lookup(f"s{i}") for i in range(6)]
PRED = [DICT.lookup(x) for x in ("type", "goals", "label", "p0", "p1")] + [
    DICT.lookup(f"p{i}") for i in range(0, 36, 5)
]
OBJ = [DICT.lookup(x) for x in ("Athlete", "Team", "o0", "o1")] + SUBJ[:3]


def triple_set(max_size):
    return st.sets(
        st.tuples(
            st.sampled_from(SUBJ), st.sampled_from(PRED), st.sampled_from(OBJ)
        ),
        max_size=max_size,
    )


def np_rows(tris):
    if not tris:
        return np.zeros((0, 3), np.int32)
    return np.asarray(sorted(tris), np.int32)


HSETTINGS = settings(
    max_examples=25,
    deadline=None,
    suppress_health_check=[HealthCheck.too_slow],
)


@given(
    combo=st.sampled_from(sorted(COMBOS)),
    d_set=triple_set(8),
    a_set=triple_set(8),
    taus=st.lists(triple_set(8), min_size=4, max_size=4),
    rhos=st.lists(triple_set(6), min_size=4, max_size=4),
)
@HSETTINGS
def test_fused_equals_looped(combo, d_set, a_set, taus, rhos):
    keys = COMBOS[combo]
    n = len(keys)
    d_store = from_numpy(np_rows(d_set), M_CAP)
    a_store = from_numpy(np_rows(a_set), M_CAP)
    tau_stores = tuple(from_numpy(np_rows(taus[k]), TAU_CAP) for k in range(n))
    rho_stores = tuple(from_numpy(np_rows(rhos[k]), RHO_CAP) for k in range(n))

    tau1s, rho1s, outs = BROKER_STEPS[combo](
        d_store, a_store, tau_stores, rho_stores
    )
    for k, key in enumerate(keys):
        w_tau, w_rho, want = STEPS[key](
            d_store, a_store, tau_stores[k], rho_stores[k]
        )
        assert bool(outs[k].overflow) == bool(want.overflow), (combo, k)
        if bool(want.overflow):
            continue  # host loop would re-jit both paths identically
        for field in ("r", "r_i", "r_prime", "a", "a_i"):
            got_f = getattr(outs[k], field)
            want_f = getattr(want, field)
            assert np.array_equal(
                np.asarray(got_f.spo), np.asarray(want_f.spo)
            ), (combo, k, field)
        assert np.array_equal(np.asarray(tau1s[k].spo), np.asarray(w_tau.spo))
        assert np.array_equal(np.asarray(rho1s[k].spo), np.asarray(w_rho.spo))


@given(
    combo=st.sampled_from(sorted(COMBOS)),
    m=triple_set(10),
)
@HSETTINGS
def test_lane_routing_matches_per_plan_bitmask(combo, m):
    """Bank words + lane gather == each plan's own pattern bitmask."""
    keys = COMBOS[combo]
    bank = BANKS[combo]
    spo = from_numpy(np_rows(m), M_CAP).spo
    words = ops.pattern_bitmask_words(spo, jnp.asarray(bank.patterns))
    assert words.shape == (M_CAP, bank.n_words)
    for k, key in enumerate(keys):
        local = ops.lane_bits(words, bank.lanes[k])
        want = ref.pattern_bitmask_ref(spo, jnp.asarray(PLANS[key].patterns))
        np.testing.assert_array_equal(np.asarray(local), np.asarray(want))


@given(
    n_pat=st.integers(1, 40),
    n_lanes=st.integers(1, 32),
    seed=st.integers(0, 2**31 - 1),
)
@HSETTINGS
def test_lane_bits_random_banks(n_pat, n_lanes, seed):
    """Random banks (possibly >32 lanes) + random lane maps round-trip."""
    rng = np.random.default_rng(seed)
    pats = rng.integers(-1, 5, size=(n_pat, 3)).astype(np.int32)
    spo = jnp.asarray(rng.integers(0, 5, size=(32, 3)), jnp.int32)
    lanes = tuple(int(x) for x in rng.integers(0, n_pat, size=n_lanes))
    words = ops.pattern_bitmask_words(spo, jnp.asarray(pats))
    local = ops.lane_bits(words, lanes)
    want = ref.pattern_bitmask_ref(spo, jnp.asarray(pats[list(lanes)]))
    np.testing.assert_array_equal(np.asarray(local), np.asarray(want))


# ---------------------------------------------------------------------------
# subscription churn: membership changes recompile at most their own cohort
# ---------------------------------------------------------------------------

CHURN_DICT = Dictionary()
for _t in (
    ["type", "Athlete", "Team", "goals", "rank"]
    + [f"e{i}" for i in range(8)]
    + [f"o{i}" for i in range(4)]
):
    CHURN_DICT.encode_term(_t)
CHURN_CAPS = StepCapacities(
    n_removed=8, n_added=8, tau=256, rho=128, pulls=64, fanout=4
)
# executable cache shared across hypothesis examples (cohort keys are pure
# shape keys, so cross-broker reuse is sound and keeps examples cheap); the
# first cold example still exercises the compile-counting path for real.
# Must match Broker's own LRU cache type (OrderedDict).
from collections import OrderedDict

CHURN_EXEC_CACHE: "OrderedDict[tuple, object]" = OrderedDict()

_CHURN_EXPRS = [
    InterestExpr.parse(
        "g", "t0", bgp=[("?a", "type", "Athlete"), ("?a", "goals", "?v")]
    ),
    InterestExpr.parse(
        "g", "t1", bgp=[("?a", "type", "Team"), ("?a", "rank", "?v")]
    ),
    InterestExpr.parse("g", "t2", bgp=[("?a", "goals", "?v")]),
    InterestExpr.parse("g", "t3", bgp=[("?a", "rank", "?v")]),
]

_CHURN_SUBJ = [CHURN_DICT.lookup(f"e{i}") for i in range(8)]
_CHURN_PRED = [CHURN_DICT.lookup(x) for x in ("type", "goals", "rank")]
_CHURN_OBJ = [CHURN_DICT.lookup(x) for x in ("Athlete", "Team", "o0", "o1")]


def _churn_rows(draw, max_size):
    tris = draw(
        st.sets(
            st.tuples(
                st.sampled_from(_CHURN_SUBJ),
                st.sampled_from(_CHURN_PRED),
                st.sampled_from(_CHURN_OBJ),
            ),
            max_size=max_size,
        )
    )
    return np_rows(tris)


@given(data=st.data())
@settings(max_examples=10, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_churn_recompile_bound(data):
    """Random subscribe/unsubscribe/process sequences never exceed one
    cohort recompile per membership change (and none without one)."""
    from repro.core import Broker

    broker = Broker(CHURN_DICT)
    broker._exec_cache = CHURN_EXEC_CACHE
    live = []
    i_next = 0
    ops = data.draw(
        st.lists(st.sampled_from("SUC"), min_size=2, max_size=8)
    )
    for op in ops:
        if op == "U" and live:
            broker.unsubscribe(live.pop(data.draw(
                st.integers(0, len(live) - 1))))
            changed = 1
        elif op == "C" and live:
            changed = 0
        else:  # subscribe (also the fallback when nothing is live)
            live.append(
                broker.subscribe(
                    _CHURN_EXPRS[i_next % len(_CHURN_EXPRS)], CHURN_CAPS
                )
            )
            i_next += 1
            changed = 1
        before = sum(broker.cohort_compiles.values())
        broker.process_changeset(
            _churn_rows(data.draw, 4), _churn_rows(data.draw, 4)
        )
        delta = sum(broker.cohort_compiles.values()) - before
        assert delta <= changed, (op, delta)


@given(combo=st.sampled_from(sorted(COMBOS)))
@HSETTINGS
def test_bank_lane_maps_recover_plan_patterns(combo):
    bank = BANKS[combo]
    for k, key in enumerate(COMBOS[combo]):
        np.testing.assert_array_equal(
            bank.patterns[list(bank.lanes[k])], PLANS[key].patterns
        )
    # dedup never invents patterns: every lane is used by some plan
    used = {lane for lanes in bank.lanes for lane in lanes}
    assert used == set(range(bank.n_lanes))


# ---------------------------------------------------------------------------
# subsumption lattice: distinct-interest evaluation + fanout is invisible.
# Random pools with duplicates and containment, plus subscribe/unsubscribe/
# re-subscribe churn: lattice-on == lattice-off == per-interest seed step,
# bit-identical at every fire.
# ---------------------------------------------------------------------------

from repro.core import Broker, to_numpy
from repro.core.interest import canonicalize_expr

LATT_DICT = Dictionary()
for _t in (
    ["type", "goals", "rank", "Athlete", "Team"]
    + [f"e{i}" for i in range(6)]
    + [f"o{i}" for i in range(4)]
):
    LATT_DICT.encode_term(_t)
LATT_CAPS = StepCapacities(
    n_removed=6, n_added=6, tau=64, rho=32, pulls=64, fanout=4
)
# pool with exact duplicates (0/2), a renaming (0/5), containment (1 and 4
# under 0), and a star reorder (3/6)
_LATT_POOL = [
    InterestExpr.parse("g", "t", bgp=[("?a", "goals", "?v")]),
    InterestExpr.parse("g", "t", bgp=[("e0", "goals", "?v")]),
    InterestExpr.parse("g", "t", bgp=[("?a", "goals", "?v")]),
    InterestExpr.parse(
        "g", "t", bgp=[("?a", "type", "Athlete"), ("?a", "goals", "?v")]
    ),
    InterestExpr.parse("g", "t", bgp=[("e1", "goals", "?v")]),
    InterestExpr.parse("g", "t", bgp=[("?z", "goals", "?w")]),
    InterestExpr.parse(
        "g", "t", bgp=[("?q", "goals", "?r"), ("?q", "type", "Athlete")]
    ),
]
_LATT_ID_CAP = LATT_DICT.id_capacity * LATT_CAPS.id_headroom
_LATT_STEPS = [
    make_interest_step(
        compile_interest(canonicalize_expr(e)[0], LATT_DICT),
        id_capacity=_LATT_ID_CAP,
        caps=LATT_CAPS,
    )
    for e in _LATT_POOL
]
LATT_EXEC_CACHE: "OrderedDict[tuple, object]" = OrderedDict()

_LATT_SUBJ = [LATT_DICT.lookup(f"e{i}") for i in range(6)]
_LATT_PRED = [LATT_DICT.lookup(x) for x in ("type", "goals", "rank")]
_LATT_OBJ = [LATT_DICT.lookup(x) for x in ("Athlete", "Team", "o0", "o1")]


def _latt_rows(draw, max_size):
    tris = draw(
        st.sets(
            st.tuples(
                st.sampled_from(_LATT_SUBJ),
                st.sampled_from(_LATT_PRED),
                st.sampled_from(_LATT_OBJ),
            ),
            max_size=max_size,
        )
    )
    return np_rows(tris)


def _latt_outs(o):
    if o is None:
        return None
    return tuple(
        to_numpy(getattr(o, f)) for f in ("r", "r_i", "r_prime", "a", "a_i")
    )


@given(data=st.data())
@settings(max_examples=10, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_lattice_collapse_is_invisible_under_churn(data):
    """Lattice-on == lattice-off == seed oracle through random churn."""
    b_on = Broker(LATT_DICT, subsume_interests=True)
    b_off = Broker(LATT_DICT, subsume_interests=False)
    b_on._exec_cache = LATT_EXEC_CACHE
    b_off._exec_cache = LATT_EXEC_CACHE
    live = []  # (pool index, sub_on, sub_off, seed tau, seed rho)
    plan = data.draw(st.lists(st.sampled_from("SSUC"), min_size=2, max_size=7))
    for op in plan:
        if op == "U" and live:
            _, s_on, s_off, _, _ = live.pop(
                data.draw(st.integers(0, len(live) - 1))
            )
            b_on.unsubscribe(s_on)
            b_off.unsubscribe(s_off)
        elif op != "C" or not live:
            # subscribing >1 at a time lets fresh duplicates auto-join a
            # lane group (a changeset in between desyncs their frontiers,
            # which must — and does — keep them independent instead)
            for _ in range(data.draw(st.integers(1, 2))):
                i = data.draw(st.integers(0, len(_LATT_POOL) - 1))
                live.append((
                    i,
                    b_on.subscribe(_LATT_POOL[i], LATT_CAPS),
                    b_off.subscribe(_LATT_POOL[i], LATT_CAPS),
                    from_numpy(np.zeros((0, 3), np.int32), LATT_CAPS.tau),
                    from_numpy(np.zeros((0, 3), np.int32), LATT_CAPS.rho),
                ))
        rm = _latt_rows(data.draw, 4)
        ad = _latt_rows(data.draw, 5)
        outs_on = [_latt_outs(o) for o in b_on.process_changeset(rm, ad)]
        outs_off = [_latt_outs(o) for o in b_off.process_changeset(rm, ad)]
        assert len(outs_on) == len(outs_off) == len(live)
        d_store = from_numpy(rm, LATT_CAPS.n_removed)
        a_store = from_numpy(ad, LATT_CAPS.n_added)
        for k, (i, s_on, s_off, tau, rho) in enumerate(live):
            tau, rho, want = _LATT_STEPS[i](d_store, a_store, tau, rho)
            live[k] = (i, s_on, s_off, tau, rho)
            seed = _latt_outs(want)
            assert (outs_on[k] is None) == (outs_off[k] is None)
            if outs_on[k] is None:
                continue
            for f, (x, y, z) in enumerate(
                zip(outs_on[k], outs_off[k], seed)
            ):
                np.testing.assert_array_equal(x, y, err_msg=f"on/off {k}/{f}")
                np.testing.assert_array_equal(x, z, err_msg=f"on/seed {k}/{f}")
    # lattice-off never evaluates fewer slots than subscribers; lattice-on
    # never evaluates more than lattice-off
    assert b_off.distinct_interests == b_off.fanout_copies
    assert b_on.distinct_interests <= b_off.distinct_interests
    assert b_on.fanout_copies == b_off.fanout_copies
