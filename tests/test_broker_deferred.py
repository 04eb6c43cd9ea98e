"""Device-resident deferred evaluation == the host-round-trip baseline.

The PR 2 deferred path pulled every composed batch device→host and
re-uploaded it per fire, running one sequential pass per frontier. The
device-resident path consumes the batches' sorted device stores directly
and stacks same-shape cohorts across frontiers into one executable call.
Outputs (and all replica state) must stay bit-identical between the two —
and therefore to eager evaluation of the composed batches, which
tests/test_broker_scheduling.py pins against the round-trip path.
"""
import numpy as np
import pytest

from repro.core import (
    Broker,
    Dictionary,
    InterestExpr,
    PushPolicy,
    StepCapacities,
)

A = "rdf:type"
CAPS = StepCapacities(n_removed=16, n_added=16, tau=64, rho=64, pulls=32)


def _exprs():
    return [
        InterestExpr.parse(
            "g", "t0", bgp=[("?a", A, "c:Athlete"), ("?a", "p:goals", "?v")]
        ),
        InterestExpr.parse(
            "g", "t1", bgp=[("?a", A, "c:Team"), ("?a", "p:rank", "?v")]
        ),
        InterestExpr.parse("g", "t2", bgp=[("?a", "p:goals", "?v")]),
    ]


def _universe():
    d = Dictionary()
    tau0 = d.encode_triples(
        [
            ("e:1", A, "c:Athlete"),
            ("e:1", "p:goals", "10"),
            ("e:2", A, "c:Team"),
        ]
    )
    return d, tau0


def _stream(d, n, seed=0):
    rng = np.random.default_rng(seed)

    def rows(k):
        out = set()
        for _ in range(k):
            e = f"e:{rng.integers(0, 9)}"
            kind = rng.integers(0, 4)
            if kind == 0:
                out.add((e, A, f"c:{['Athlete', 'Team'][rng.integers(2)]}"))
            elif kind == 1:
                out.add((e, "p:goals", str(int(rng.integers(0, 30)))))
            elif kind == 2:
                out.add((e, "p:rank", str(int(rng.integers(0, 5)))))
            else:
                out.add((e, "p:noise", f"o{rng.integers(0, 6)}"))
        return d.encode_triples(sorted(out))

    return [
        (rows(int(rng.integers(0, 5))), rows(int(rng.integers(1, 7))))
        for _ in range(n)
    ]


def _twin_brokers(d, tau0, policies):
    """Two brokers over one dictionary: device-resident vs round-trip."""
    dev = Broker(d, deferred_device_resident=True)
    rtt = Broker(d, deferred_device_resident=False)
    exprs = _exprs()
    for i, pol in enumerate(policies):
        expr = exprs[i % len(exprs)]
        dev.subscribe(expr, CAPS, initial_target=tau0, policy=pol)
        rtt.subscribe(expr, CAPS, initial_target=tau0, policy=pol)
    return dev, rtt


def assert_results_identical(got, want, label):
    assert len(got) == len(want), label
    for k, (g, w) in enumerate(zip(got, want)):
        assert (g is None) == (w is None), (label, k)
        if g is None:
            continue
        for field in ("r", "r_i", "r_prime", "a", "a_i"):
            gf, wf = getattr(g, field), getattr(w, field)
            assert np.array_equal(
                np.asarray(gf.spo), np.asarray(wf.spo)
            ), (label, k, field)
            assert int(gf.n) == int(wf.n), (label, k, field)


def assert_states_identical(dev, rtt, label):
    for k, (sd, sr) in enumerate(zip(dev.subs, rtt.subs)):
        assert np.array_equal(
            np.asarray(sd.tau.spo), np.asarray(sr.tau.spo)
        ), (label, k, "tau")
        assert np.array_equal(
            np.asarray(sd.rho.spo), np.asarray(sr.rho.spo)
        ), (label, k, "rho")
        assert sd.since == sr.since, (label, k)


def test_device_resident_matches_round_trip_golden():
    """Mixed cadences (eager / every-2 / every-3) through both paths stay
    bit-identical step by step, and a multi-frontier flush stacks the
    same-shape cohorts into fewer passes than the sequential baseline."""
    d, tau0 = _universe()
    dev, rtt = _twin_brokers(
        d,
        tau0,
        [
            PushPolicy(),  # eager
            PushPolicy.every(2),
            PushPolicy.every(3),
            PushPolicy.every(3),  # same shape as sub 0 family, slow lane
        ],
    )
    for i, cs in enumerate(_stream(d, 5, seed=1)):
        got = dev.process_changeset(*cs)
        want = rtt.process_changeset(*cs)
        assert_results_identical(got, want, ("step", i))
        assert_states_identical(dev, rtt, ("step", i))

    # leave two distinct frontiers pending, then drain both paths at once
    got = dev.flush()
    want = rtt.flush()
    assert_results_identical(got, want, "flush")
    assert_states_identical(dev, rtt, "flush")
    if dev.stats and rtt.stats:
        dev_passes = dev.stats[-1].n_cohort_passes
        rtt_passes = rtt.stats[-1].n_cohort_passes
        assert dev_passes <= rtt_passes

    # nothing pending: both flushes are no-ops
    assert dev.flush() == [None] * len(dev.subs)
    assert rtt.flush() == [None] * len(rtt.subs)


def test_multi_frontier_flush_stacks_same_shape_cohorts():
    """Two same-shape subscribers stuck at different frontiers drain in ONE
    stacked cohort pass on the device-resident path (two sequentially on
    the baseline), with identical outputs."""
    d, tau0 = _universe()
    expr = _exprs()[0]
    # pre-encode the stream so the dictionary (and with it id_capacity,
    # part of the cohort key) is identical for both subscriptions
    stream = _stream(d, 4, seed=2)
    dev = Broker(d, deferred_device_resident=True)
    rtt = Broker(d, deferred_device_resident=False)
    for b in (dev, rtt):
        b.subscribe(
            expr, CAPS, initial_target=tau0, policy=PushPolicy.max_staleness(1e9)
        )
    for b in (dev, rtt):
        b.process_changeset(*stream[0])
        # second subscriber arrives mid-stream: its frontier starts later
        b.subscribe(
            expr, CAPS, initial_target=tau0, policy=PushPolicy.max_staleness(1e9)
        )
        for cs in stream[1:]:
            b.process_changeset(*cs)

    got, want = dev.flush(), rtt.flush()
    assert_results_identical(got, want, "stacked flush")
    assert_states_identical(dev, rtt, "stacked flush")
    # both subscribers share one shape cohort: the stacked path folds the
    # two frontiers into a single executable call
    assert dev.stats[-1].n_cohort_passes == 1
    assert rtt.stats[-1].n_cohort_passes == 2


def test_device_resident_property_random_streams():
    """Hypothesis sweep: random policies + random streams stay bit-identical
    between the device-resident and round-trip paths, including flushes."""
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @given(
        seed=st.integers(0, 2**16),
        ks=st.lists(st.integers(1, 4), min_size=2, max_size=4),
        n_steps=st.integers(2, 6),
    )
    @settings(
        max_examples=10,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    def prop(seed, ks, n_steps):
        d, tau0 = _universe()
        dev, rtt = _twin_brokers(
            d, tau0, [PushPolicy.every(k) for k in ks]
        )
        for i, cs in enumerate(_stream(d, n_steps, seed=seed)):
            got = dev.process_changeset(*cs)
            want = rtt.process_changeset(*cs)
            assert_results_identical(got, want, ("step", i))
        got, want = dev.flush(), rtt.flush()
        assert_results_identical(got, want, "flush")
        assert_states_identical(dev, rtt, "final")

    prop()


# ---------------------------------------------------------------------------
# delta-encoded frontier chains
# ---------------------------------------------------------------------------


def test_delta_chain_matches_stacked_golden():
    """The delta-chain flush (default) stays bit-identical to the PR 3
    stacked pass (delta_frontiers=False) AND the PR 2 round-trip baseline
    across mixed cadences, and its multi-frontier flushes match each
    distinct D row once (rows_matched == rows_distinct) where the stacked
    pass re-matches the shared suffix once per frontier."""
    d, tau0 = _universe()
    policies = [
        PushPolicy.max_staleness(1e9),
        PushPolicy.max_staleness(1e9),
        PushPolicy.every(3),
        PushPolicy.every(2),
    ]
    exprs = _exprs()
    brokers = {
        "delta": Broker(d, deferred_device_resident=True),
        "stacked": Broker(
            d, deferred_device_resident=True, delta_frontiers=False
        ),
        "roundtrip": Broker(d, deferred_device_resident=False),
    }
    assert brokers["delta"].delta_frontiers  # delta is the default
    subs = {}
    for name, b in brokers.items():
        subs[name] = [
            b.subscribe(exprs[i % len(exprs)], CAPS, initial_target=tau0,
                        policy=pol)
            for i, pol in enumerate(policies)
        ]
    stream = _stream(d, 6, seed=7)
    for i, cs in enumerate(stream[:3]):
        got = {n: b.process_changeset(*cs) for n, b in brokers.items()}
        assert_results_identical(got["delta"], got["stacked"], ("step", i))
        assert_results_identical(got["delta"], got["roundtrip"], ("step", i))
    # stagger: drain the first slow subscriber early, then keep feeding so
    # the final flush drains >= 2 overlapping frontiers
    for n, b in brokers.items():
        b.flush(subs=[subs[n][0]])
    for i, cs in enumerate(stream[3:]):
        got = {n: b.process_changeset(*cs) for n, b in brokers.items()}
        assert_results_identical(got["delta"], got["stacked"], ("step2", i))
    flushed = {n: b.flush() for n, b in brokers.items()}
    assert_results_identical(flushed["delta"], flushed["stacked"], "flush")
    assert_results_identical(flushed["delta"], flushed["roundtrip"], "flush")
    assert_states_identical(brokers["delta"], brokers["stacked"], "final")
    assert_states_identical(brokers["delta"], brokers["roundtrip"], "final")

    # dedup efficacy is observable: the delta broker's match volume equals
    # its distinct-row volume, and never exceeds the stacked broker's
    st_d = brokers["delta"].stats[-1]
    st_s = brokers["stacked"].stats[-1]
    assert st_d.rows_matched == st_d.rows_distinct
    assert st_s.rows_matched >= st_d.rows_matched
    assert brokers["delta"].rows_matched == brokers["delta"].rows_distinct
    assert brokers["stacked"].rows_matched >= brokers["stacked"].rows_distinct


def test_delta_chain_nonmonotone_add_remove_readd_golden():
    """A triple added, removed, then re-added across fired frontiers (the
    non-monotone composition case) flushes bit-identically to eager seed
    evaluation of each subscriber's composed batch."""
    from repro.core import IrapEngine
    from repro.core.propagation import ChangesetBatch

    d, tau0 = _universe()
    expr = _exprs()[2]  # ("?a", "p:goals", "?v") — matches T directly
    t_add = d.encode_triples([("e:7", "p:goals", "99")])
    noise = d.encode_triples([("e:8", "p:noise", "o1")])
    z = np.zeros((0, 3), np.int32)
    # cs1 adds T (+ a real D row), cs2 removes T, cs3 re-adds T: frontier
    # [2..3] composes to <{T}, {T}>, frontier [1..3] to <{T, D1}, {T}> —
    # T's A-membership flips between what the two frontiers absorbed
    d1 = d.encode_triples([("e:1", "p:goals", "10")])
    cs = [(d1, t_add), (t_add, noise), (z, t_add)]

    broker = Broker(d)
    pol = PushPolicy.max_staleness(1e9)
    a = broker.subscribe(expr, CAPS, initial_target=tau0, policy=pol)
    b = broker.subscribe(expr, CAPS, initial_target=tau0, policy=pol)

    broker.process_changeset(*cs[0])
    broker.flush(subs=[a])  # a's frontier advances past cs1
    broker.process_changeset(*cs[1])
    broker.process_changeset(*cs[2])
    out = broker.flush()  # drains two overlapping frontiers at once
    assert broker.stats[-1].rows_matched == broker.stats[-1].rows_distinct

    d_ref = Dictionary()
    tau_ref = d_ref.encode_triples(
        [("e:1", A, "c:Athlete"), ("e:1", "p:goals", "10"),
         ("e:2", A, "c:Team")]
    )
    t_ref = d_ref.encode_triples([("e:7", "p:goals", "99")])
    noise_ref = d_ref.encode_triples([("e:8", "p:noise", "o1")])
    d1_ref = d_ref.encode_triples([("e:1", "p:goals", "10")])
    cs_ref = [(d1_ref, t_ref), (t_ref, noise_ref), (z, t_ref)]
    engine = IrapEngine(d_ref)
    ref_a = engine.register_interest(expr, CAPS, initial_target=tau_ref)
    ref_b = engine.register_interest(expr, CAPS, initial_target=tau_ref)
    ref_a.apply(*cs_ref[0])  # a consumed cs1 at the early flush
    comp_a = ChangesetBatch.fresh(*cs_ref[1], 2)
    comp_a.extend(*cs_ref[2], 3)
    comp_b = ChangesetBatch.fresh(*cs_ref[0], 1)
    comp_b.extend(*cs_ref[1], 2)
    comp_b.extend(*cs_ref[2], 3)
    want_a = ref_a.apply(*comp_a.arrays())
    want_b = ref_b.apply(*comp_b.arrays())
    for got, want, label in ((out[0], want_a, "a"), (out[1], want_b, "b")):
        for field in ("r", "r_i", "r_prime", "a", "a_i"):
            assert np.array_equal(
                np.asarray(getattr(got, field).spo),
                np.asarray(getattr(want, field).spo),
            ), (label, field)
    for sub, ref in ((a, ref_a), (b, ref_b)):
        assert np.array_equal(np.asarray(sub.tau.spo), np.asarray(ref.tau.spo))
        assert np.array_equal(np.asarray(sub.rho.spo), np.asarray(ref.rho.spo))


def test_delta_chain_nonmonotone_property():
    """Hypothesis sweep over tiny-pool streams (heavy add/remove/re-add
    churn of the same triples across frontiers): delta-chain flushes stay
    bit-identical to the stacked pass, step by step and at flush."""
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @given(
        seed=st.integers(0, 2**16),
        ks=st.lists(st.integers(1, 4), min_size=2, max_size=4),
        n_steps=st.integers(3, 7),
    )
    @settings(
        max_examples=8,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    def prop(seed, ks, n_steps):
        rng = np.random.default_rng(seed)
        d, tau0 = _universe()
        # 4-triple pool: the same triples keep entering/leaving D and A,
        # flipping membership between overlapping frontiers
        pool = [("e:1", "p:goals", "10"), ("e:2", "p:goals", "11"),
                ("e:1", A, "c:Athlete"), ("e:3", "p:rank", "2")]

        def pick(k):
            if k == 0:
                return np.zeros((0, 3), np.int32)
            idx = sorted(set(rng.integers(0, len(pool), size=k).tolist()))
            return d.encode_triples([pool[i] for i in idx])

        delta = Broker(d, deferred_device_resident=True)
        stacked = Broker(
            d, deferred_device_resident=True, delta_frontiers=False
        )
        exprs = _exprs()
        for i, k in enumerate(ks):
            for b in (delta, stacked):
                b.subscribe(
                    exprs[i % len(exprs)], CAPS, initial_target=tau0,
                    policy=PushPolicy.every(k),
                )
        for i in range(n_steps):
            cs = (pick(int(rng.integers(0, 3))), pick(int(rng.integers(0, 4))))
            got = delta.process_changeset(*cs)
            want = stacked.process_changeset(*cs)
            assert_results_identical(got, want, ("step", i))
        got, want = delta.flush(), stacked.flush()
        assert_results_identical(got, want, "flush")
        assert_states_identical(delta, stacked, "final")

    prop()


# ---------------------------------------------------------------------------
# flush fast paths
# ---------------------------------------------------------------------------


def test_flush_fast_paths_no_fire_and_empty_batches():
    """No pending work, all-deferred policies, and empty composed batches
    all skip statics/executables entirely: zero cohort passes, zero
    compiles."""
    d, tau0 = _universe()
    broker = Broker(d)
    z = np.zeros((0, 3), np.int32)
    slow = broker.subscribe(
        _exprs()[0], CAPS, initial_target=tau0, policy=PushPolicy.every(100)
    )
    eager = broker.subscribe(
        _exprs()[1], CAPS, initial_target=tau0, policy=PushPolicy()
    )

    # nothing pending: flush is a no-op that touches no executables
    assert broker.flush() == [None, None]
    assert broker.rejit_count == 0 and not broker._exec_cache
    assert len(broker.stats) == 0

    # an all-empty changeset: the eager policy fires but the composed
    # batch is empty — canonical empty outputs, no cohort passes
    outs = broker.process_changeset(z, z)
    assert outs[0] is None  # slow subscriber deferred
    assert outs[1] is not None
    for field in ("r", "r_i", "r_prime", "a", "a_i"):
        assert int(getattr(outs[1], field).n) == 0, field
    assert not bool(outs[1].overflow)
    assert broker.stats[-1].n_cohort_passes == 0
    assert broker.rejit_count == 0 and not broker._exec_cache

    # the slow subscriber's pending batch is empty too: flush drains it
    # through the same fast path and the batch is garbage-collected
    outs = broker.flush()
    assert outs[0] is not None and int(outs[0].r.n) == 0
    assert broker.stats[-1].n_cohort_passes == 0
    assert broker.rejit_count == 0 and not broker._exec_cache
    assert not broker._batches
    assert slow.since == eager.since == broker._last_cid + 1

    # a real changeset afterwards still evaluates normally
    cs = (z, d.encode_triples([("e:1", "p:goals", "77")]))
    outs = broker.process_changeset(*cs)
    assert broker.stats[-1].n_cohort_passes >= 1
    assert int(outs[1].a.n) >= 0  # evaluated, not fast-pathed


def test_empty_batch_fast_path_matches_roundtrip():
    """Both residency modes take the same empty-batch fast path, so their
    results and replica states stay bit-identical around empty fires."""
    d, tau0 = _universe()
    dev, rtt = _twin_brokers(
        d, tau0, [PushPolicy(), PushPolicy.every(2)]
    )
    z = np.zeros((0, 3), np.int32)
    stream = [(z, z), (z, d.encode_triples([("e:1", "p:goals", "31")])),
              (z, z), (z, z)]
    for i, cs in enumerate(stream):
        got = dev.process_changeset(*cs)
        want = rtt.process_changeset(*cs)
        assert_results_identical(got, want, ("step", i))
        assert_states_identical(dev, rtt, ("step", i))
    got, want = dev.flush(), rtt.flush()
    assert_results_identical(got, want, "flush")
    assert_states_identical(dev, rtt, "flush")


def _burst_rows(d, n_raw, n_distinct, seed=0):
    """n_raw triples drawn from an n_distinct-triple pool (duplicate-heavy:
    raw rows force capacity growth, composed rows stay small)."""
    rng = np.random.default_rng(seed)
    pool = [
        (f"e:{i % 50}", "p:goals", str(1000 + i)) for i in range(n_distinct)
    ]
    picks = [pool[rng.integers(0, n_distinct)] for _ in range(n_raw)]
    return d.encode_triples(picks)


def test_batch_capacity_decay():
    """A deferred frontier that grew through a duplicate-heavy burst decays
    back to a smaller pow2 bucket after `decay_patience` consecutive drains,
    and BrokerStats exposes the grow/shrink counts."""
    d, tau0 = _universe()
    broker = Broker(d, decay_patience=2)
    expr = _exprs()[0]
    # X is drained explicitly every round; Y defers forever, so its batch
    # survives every drain and is the decay candidate
    x = broker.subscribe(
        expr, CAPS, initial_target=tau0, policy=PushPolicy.max_staleness(1e9)
    )
    y = broker.subscribe(
        _exprs()[1], CAPS, initial_target=tau0,
        policy=PushPolicy.max_staleness(1e9),
    )
    z = np.zeros((0, 3), np.int32)

    # small first changeset: the shared batch starts at the 64-row floor
    broker.process_changeset(z, _burst_rows(d, 8, 8, seed=1))
    # duplicate-heavy burst: 200 raw rows force the pow2 bucket up, but the
    # composed distinct rows stay far below half the new allocation
    broker.process_changeset(z, _burst_rows(d, 200, 24, seed=2))
    batch = next(iter(broker._batches.values()))
    assert batch.capacity >= 256
    assert broker.batch_grows >= 1
    cap_peak = batch.capacity

    # each explicit drain of X is one decay check on Y's surviving batch;
    # patience=2 means the first check only arms the streak
    broker.process_changeset(z, _burst_rows(d, 4, 4, seed=3))
    broker.flush(subs=[x])
    assert batch.capacity == cap_peak and broker.batch_shrinks == 0
    broker.process_changeset(z, _burst_rows(d, 4, 4, seed=4))
    broker.flush(subs=[x])
    assert batch.capacity < cap_peak, "second consecutive drain shrinks"
    assert broker.batch_shrinks == 1
    assert broker.stats[-1].batch_shrinks == 1
    assert broker.stats[-1].batch_grows >= 1

    # the decayed batch still drains correctly: Y's flush output equals
    # eager evaluation of the same composed batch by the seed engine
    from repro.core import IrapEngine
    from repro.core.propagation import ChangesetBatch

    d_ref = Dictionary()
    tau_ref = d_ref.encode_triples(
        [("e:1", A, "c:Athlete"), ("e:1", "p:goals", "10"), ("e:2", A, "c:Team")]
    )
    ref_stream = [
        (z, _burst_rows(d_ref, 8, 8, seed=1)),
        (z, _burst_rows(d_ref, 200, 24, seed=2)),
        (z, _burst_rows(d_ref, 4, 4, seed=3)),
        (z, _burst_rows(d_ref, 4, 4, seed=4)),
    ]
    comp = ChangesetBatch.fresh(*ref_stream[0], 1)
    for i, cs in enumerate(ref_stream[1:], start=2):
        comp.extend(*cs, i)
    engine = IrapEngine(d_ref)
    ref_sub = engine.register_interest(
        _exprs()[1], CAPS, initial_target=tau_ref
    )
    want = ref_sub.apply(*comp.arrays())
    got = broker.flush()[list(broker.subs).index(y)]
    for field in ("r", "r_i", "r_prime", "a", "a_i"):
        assert np.array_equal(
            np.asarray(getattr(got, field).spo),
            np.asarray(getattr(want, field).spo),
        ), field


def test_batch_decay_streak_resets_on_refill():
    """A well-filled check between two under-filled ones resets the streak:
    one burst never thrashes the capacity down."""
    from repro.core.propagation import ChangesetBatch

    d, _ = _universe()
    batch = ChangesetBatch.fresh(
        np.zeros((0, 3), np.int32), _burst_rows(d, 8, 8, seed=1), 1
    )
    batch.extend(np.zeros((0, 3), np.int32), _burst_rows(d, 200, 24, seed=2), 2)
    cap = batch.capacity
    assert cap >= 256
    assert not batch.maybe_decay(patience=2)  # arms the streak
    # refill above half: streak resets
    batch.extend(
        np.zeros((0, 3), np.int32),
        d.encode_triples(
            [(f"e:{i}", "p:fill", str(i)) for i in range(cap // 2 + 8)]
        ),
        3,
    )
    assert not batch.maybe_decay(patience=2)
    assert batch._decay_streak == 0
