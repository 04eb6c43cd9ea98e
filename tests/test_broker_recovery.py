"""Durable broker: WAL journal, crash recovery, delivery robustness.

The recovery contract under test: a broker rebuilt by
``Broker.recover(journal, store)`` is **bit-identical** to the crashed
broker at every journal-record boundary — same τ/ρ rows, same consumption
frontiers, same pending composed batches, same sequence clock
(:func:`repro.testing.faults.broker_state` pins the comparison). Delivery
faults (flaky/poisonous transports) must *degrade* — retry, back off,
quarantine with the frontier pinned and the batch composing — and never
halt ingest or corrupt a healthy subscriber's state.

One subtlety the delivery goldens encode: interest-filtered propagation is
*cadence-dependent* (additions are join-filtered against the evolving τ at
delivery time), so a quarantined subscriber that catches up on a composed
window is NOT compared against an eagerly-fed twin — the correct oracle is
a fault-free twin on the *same effective schedule* (policy-deferred, one
flush at the catch-up point). Redelivery of the same window is what Def-6
composition makes idempotent, and that is what recovery relies on.
"""
import shutil

import numpy as np
import pytest

from repro.checkpoint.store import CheckpointStore
from repro.core import (
    Broker,
    ChangesetJournal,
    DeliveryChannel,
    PushPolicy,
    StepCapacities,
    to_numpy,
)
from repro.testing import (
    CapturingJournal,
    FakeClock,
    ScriptedTransport,
    assert_state_equal,
    broker_state,
    corrupt_tail,
    crash_at_record,
    tear_tail,
    tiny_caps,
)
from test_broker_deferred import (
    CAPS,
    _exprs,
    _stream,
    _universe,
    assert_results_identical,
)

# generous capacities for the boundary goldens: a capacity overflow inside
# a fire grows caps *before* the fire record is appended, so the captured
# boundary state would include growth the crash-side recovery (which never
# sees that record) cannot reproduce — the goldens must stay overflow-free
RCAPS = StepCapacities(n_removed=32, n_added=32, tau=128, rho=128, pulls=64)


# ---------------------------------------------------------------------------
# journal unit tests (no broker)
# ---------------------------------------------------------------------------


def _fill(journal, n, seed=0):
    rng = np.random.default_rng(seed)
    for i in range(1, n + 1):
        journal.append(
            "ingest",
            meta={"i": i},
            arrays={
                "removed": rng.integers(0, 99, (i % 3, 3)).astype(np.int32),
                "added": rng.integers(0, 99, (1 + i % 4, 3)).astype(np.int32),
            },
        )


def _roundtrip_equal(journal, n, seed=0):
    rng = np.random.default_rng(seed)
    recs = list(journal.records())
    assert [r.seq for r in recs] == list(range(1, n + 1))
    for i, r in enumerate(recs, start=1):
        assert r.kind == "ingest" and r.meta == {"i": i}
        np.testing.assert_array_equal(
            r.arrays["removed"],
            rng.integers(0, 99, (i % 3, 3)).astype(np.int32),
        )
        np.testing.assert_array_equal(
            r.arrays["added"],
            rng.integers(0, 99, (1 + i % 4, 3)).astype(np.int32),
        )


def test_journal_append_reopen_roundtrip(tmp_path):
    j = ChangesetJournal(tmp_path / "wal", fsync=False)
    _fill(j, 7)
    assert j.last_seq == 7
    j.close()
    j2 = ChangesetJournal(tmp_path / "wal", fsync=False)
    assert j2.last_seq == 7 and not j2.torn
    _roundtrip_equal(j2, 7)
    # appends continue the sequence across reopen
    assert j2.append("ingest", meta={"i": 8}) == 8
    assert [r.seq for r in j2.records(start_seq=7)] == [7, 8]


def test_journal_rotation_and_compaction(tmp_path):
    j = ChangesetJournal(tmp_path / "wal", fsync=False, segment_bytes=256)
    _fill(j, 20)
    assert len(j.segments) > 3, "tiny segment_bytes must rotate"
    _roundtrip_equal(j, 20)
    # compaction keeps every record >= keep_from_seq readable (it drops
    # whole leading segments only, so earlier records may survive)
    keep = 12
    removed = j.compact(keep_from_seq=keep)
    assert removed > 0
    recs = list(j.records())
    assert recs[0].seq <= keep and recs[-1].seq == 20
    assert {r.seq for r in recs} >= set(range(keep, 21))
    # append after compaction still continues the sequence
    assert j.append("ingest", meta={"i": 21}) == 21


@pytest.mark.parametrize("cut", [1, 5, 17])
def test_journal_torn_tail_truncates(tmp_path, cut):
    j = ChangesetJournal(tmp_path / "wal", fsync=False)
    _fill(j, 5)
    j.close()
    assert tear_tail(tmp_path / "wal", cut) == cut
    j2 = ChangesetJournal(tmp_path / "wal", fsync=False)
    assert j2.torn and j2.last_seq == 4 and j2.dropped_bytes > 0
    assert [r.seq for r in j2.records()] == [1, 2, 3, 4]
    # the torn slot is reused: the journal stays densely sequenced
    assert j2.append("ingest", meta={"i": 5}) == 5


def test_journal_crc_rejects_corruption(tmp_path):
    j = ChangesetJournal(tmp_path / "wal", fsync=False)
    _fill(j, 5)
    j.close()
    assert corrupt_tail(tmp_path / "wal", seed=7) > 0
    j2 = ChangesetJournal(tmp_path / "wal", fsync=False)
    assert j2.torn and j2.last_seq == 4
    assert [r.seq for r in j2.records()] == [1, 2, 3, 4]


# ---------------------------------------------------------------------------
# crash recovery goldens
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def journaled_run(tmp_path_factory):
    """One journaled broker run with a mid-stream snapshot: mixed cadences,
    a pre-append state capture per record, and the final state."""
    tmp = tmp_path_factory.mktemp("durable")
    d, tau0 = _universe()
    captures = {}
    j = CapturingJournal(
        tmp / "wal",
        fsync=False,
        on_append=lambda seq, kind: captures.__setitem__(
            seq, broker_state(b)
        ),
    )
    b = Broker(d, journal=j)
    exprs = _exprs()
    policies = [PushPolicy(), PushPolicy.every(2), PushPolicy.every(3)]
    for i in range(3):
        b.subscribe(exprs[i], RCAPS, initial_target=tau0, policy=policies[i])
    stream = _stream(d, 4, seed=3)
    store = CheckpointStore(tmp / "ckpt")
    for i, (rm, ad) in enumerate(stream):
        b.process_changeset(rm, ad)
        if i == 1:
            b.snapshot(store)  # mid-stream: pending batches straddle it
    b.flush()
    final = broker_state(b)
    j.sync()
    j.close()
    return {
        "tmp": tmp,
        "dictionary": d,
        "jdir": tmp / "wal",
        "store": store,
        "captures": captures,
        "final": final,
        "n": max(captures),
    }


def test_crash_at_every_boundary_recovers_bit_identical(journaled_run):
    """Kill the broker between any two journal appends: recovery from the
    surviving prefix reproduces the captured pre-append state exactly —
    τ/ρ rows, frontiers, pending composed batches, sequence clock."""
    run = journaled_run
    n, captures = run["n"], run["captures"]
    assert n >= 8  # subscribes + ingests + fire commits all journal
    for k in range(n + 1):
        cdst = run["tmp"] / f"crash{k}"
        kept = crash_at_record(run["jdir"], cdst, k)
        assert kept == k, (kept, k)
        j2 = ChangesetJournal(cdst, fsync=False)
        assert j2.last_seq == k
        r = Broker.recover(j2, run["store"], dictionary=run["dictionary"])
        # the capture taken before record k+1 is the state of a broker
        # holding exactly k durable records — except its sequence clock,
        # which had already consumed record k+1's tick
        want = (
            run["final"] if k == n else {**captures[k + 1], "seq": k}
        )
        assert_state_equal(want, broker_state(r))


@pytest.mark.parametrize("cut", [1, 5, 17])
def test_torn_tail_recovers_to_previous_boundary(journaled_run, cut):
    run = journaled_run
    n = run["n"]
    cdst = run["tmp"] / f"torn{cut}"
    shutil.copytree(run["jdir"], cdst)
    tear_tail(cdst, cut)
    j = ChangesetJournal(cdst, fsync=False)
    assert j.torn and j.last_seq == n - 1 and j.dropped_bytes > 0
    r = Broker.recover(j, run["store"], dictionary=run["dictionary"])
    assert_state_equal(
        {**run["captures"][n], "seq": n - 1}, broker_state(r)
    )


def test_corrupt_tail_recovers_to_previous_boundary(journaled_run):
    run = journaled_run
    n = run["n"]
    cdst = run["tmp"] / "corrupt"
    shutil.copytree(run["jdir"], cdst)
    assert corrupt_tail(cdst, seed=7) > 0
    j = ChangesetJournal(cdst, fsync=False)
    assert j.torn and j.last_seq == n - 1
    r = Broker.recover(j, run["store"], dictionary=run["dictionary"])
    assert_state_equal(
        {**run["captures"][n], "seq": n - 1}, broker_state(r)
    )


def test_recovery_from_journal_alone(tmp_path):
    """No snapshot at all: full-journal replay rebuilds the broker."""
    d, tau0 = _universe()
    j = ChangesetJournal(tmp_path / "wal", fsync=False)
    b = Broker(d, journal=j)
    exprs = _exprs()
    b.subscribe(exprs[0], RCAPS, initial_target=tau0)
    b.subscribe(exprs[2], RCAPS, initial_target=tau0,
                policy=PushPolicy.every(2))
    for rm, ad in _stream(d, 3, seed=9):
        b.process_changeset(rm, ad)
    b.flush()
    j.sync()
    j2 = ChangesetJournal(tmp_path / "wal", fsync=False)
    r = Broker.recover(j2, dictionary=d)
    assert_state_equal(broker_state(b), broker_state(r))


def test_snapshot_compaction_preserves_recovery(tmp_path):
    """Snapshot, drop the journal segments replay can no longer need, keep
    streaming: recovery over the compacted journal stays bit-identical."""
    d, tau0 = _universe()
    j = ChangesetJournal(tmp_path / "wal", fsync=False, segment_bytes=256)
    b = Broker(d, journal=j)
    exprs = _exprs()
    for i in range(3):
        b.subscribe(exprs[i], CAPS, initial_target=tau0,
                    policy=PushPolicy.every(2))
    store = CheckpointStore(tmp_path / "ckpt")
    removed = 0
    for i, (rm, ad) in enumerate(_stream(d, 10, seed=5)):
        b.process_changeset(rm, ad)
        if i == 6:
            b.snapshot(store)
            removed = b.compact_journal()
    b.flush()
    j.sync()
    assert removed > 0, "segment rotation + snapshot must free segments"
    j2 = ChangesetJournal(tmp_path / "wal", fsync=False)
    r = Broker.recover(j2, store, dictionary=d)
    assert_state_equal(broker_state(b), broker_state(r))


def test_recovery_refuses_overcompacted_journal(tmp_path):
    """A journal whose surviving records start past what replay needs (a
    compacted-away or lost segment) must fail loudly, not rebuild silently
    wrong state."""
    d, tau0 = _universe()
    j = ChangesetJournal(tmp_path / "wal", fsync=False, segment_bytes=128)
    b = Broker(d, journal=j)
    b.subscribe(_exprs()[0], CAPS, initial_target=tau0)
    for rm, ad in _stream(d, 6, seed=4):
        b.process_changeset(rm, ad)
    j.sync()
    # no snapshot exists, so replay needs seq 1 — force-drop the head
    assert j.compact(keep_from_seq=j.last_seq) > 0
    j2 = ChangesetJournal(tmp_path / "wal", fsync=False)
    with pytest.raises(RuntimeError, match="compacted away or lost"):
        Broker.recover(j2, dictionary=d)


def test_crash_boundary_property_random_schedules():
    """Hypothesis sweep: random cadences, random streams, crash at a random
    boundary — recovery always lands on the captured state."""
    import tempfile
    from pathlib import Path

    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @given(
        seed=st.integers(0, 2**16),
        ks=st.lists(st.integers(1, 3), min_size=1, max_size=2),
        n_steps=st.integers(2, 3),
        crash_frac=st.floats(0.0, 1.0),
    )
    @settings(
        max_examples=5,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    def prop(seed, ks, n_steps, crash_frac):
        tmp = Path(tempfile.mkdtemp())
        try:
            d, tau0 = _universe()
            captures = {}
            j = CapturingJournal(
                tmp / "wal",
                fsync=False,
                on_append=lambda seq, kind: captures.__setitem__(
                    seq, broker_state(b)
                ),
            )
            b = Broker(d, journal=j)
            exprs = _exprs()
            for i, kk in enumerate(ks):
                b.subscribe(
                    exprs[i % len(exprs)], RCAPS, initial_target=tau0,
                    policy=PushPolicy.every(kk),
                )
            for rm, ad in _stream(d, n_steps, seed=seed):
                b.process_changeset(rm, ad)
            b.flush()
            final = broker_state(b)
            j.sync()
            j.close()
            n = max(captures)
            k = min(n, int(round(crash_frac * n)))
            kept = crash_at_record(tmp / "wal", tmp / "crash", k)
            assert kept == k
            j2 = ChangesetJournal(tmp / "crash", fsync=False)
            r = Broker.recover(j2, dictionary=d)
            want = final if k == n else {**captures[k + 1], "seq": k}
            assert_state_equal(want, broker_state(r))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    prop()


# ---------------------------------------------------------------------------
# delivery robustness: retry / backoff / quarantine / backpressure
# ---------------------------------------------------------------------------


def test_quarantine_pins_frontier_and_composed_catchup():
    """A poisonous subscriber quarantines after ``quarantine_after``
    consecutive failed deliveries; its frontier pins while its batch keeps
    composing, the healthy subscriber is unaffected, and readmission
    delivers the composed window exactly once. The catch-up oracle is a
    fault-free twin on the same effective schedule (policy-deferred, one
    flush) — NOT an eager twin: interest filtering is cadence-dependent."""
    clk = FakeClock()
    tr = ScriptedTransport(scripts={0: ["fail"] * 10}, clock=clk)
    ch = DeliveryChannel(
        tr, max_attempts=1, base_backoff_s=1.0, backoff_factor=2.0,
        jitter=0.0, quarantine_after=3, clock=clk, sleep=clk.sleep,
    )
    d, tau0 = _universe()
    exprs = _exprs()
    b = Broker(d, channel=ch)
    s0 = b.subscribe(exprs[0], CAPS, initial_target=tau0)  # poisoned
    s1 = b.subscribe(exprs[2], CAPS, initial_target=tau0)  # healthy

    d2, tau0b = _universe()
    twin = Broker(d2)
    t0 = twin.subscribe(
        exprs[0], CAPS, initial_target=tau0b, policy=PushPolicy(every_k=None)
    )
    t1 = twin.subscribe(exprs[2], CAPS, initial_target=tau0b)

    stream = _stream(d, 6, seed=11)
    stream_t = _stream(d2, 6, seed=11)
    for i, ((rm, ad), (rm2, ad2)) in enumerate(zip(stream, stream_t)):
        outs = b.process_changeset(rm, ad)
        outs_t = twin.process_changeset(rm2, ad2)
        # the healthy subscriber never notices the poisoned one
        assert_results_identical([outs[1]], [outs_t[1]], ("healthy", i))
        clk.advance(10.0)  # let each backoff elapse between changesets

    assert ch.is_quarantined(s0) and ch.stats.quarantines == 1
    assert not ch.eligible(s0) and ch.eligible(s1)
    assert s0.since < s1.since  # pinned frontier, healthy one advanced
    batch = b._batches[s0.since]
    assert batch.n_changesets > 1  # the pinned window kept composing

    # readmit: the whole composed window delivers in ONE transport call
    ch.readmit(s0)
    tr.scripts[0] = []
    b.flush([s0])
    assert s0.since > b._last_cid
    assert len(tr.delivered.get(0, [])) == 1

    twin.flush([t0])
    np.testing.assert_array_equal(to_numpy(s0.tau), to_numpy(t0.tau))
    np.testing.assert_array_equal(to_numpy(s0.rho), to_numpy(t0.rho))
    np.testing.assert_array_equal(to_numpy(s1.tau), to_numpy(t1.tau))
    np.testing.assert_array_equal(to_numpy(s1.rho), to_numpy(t1.rho))


def test_backoff_schedule_golden():
    """Exact exponential backoff against a fake clock (jitter=0): a failed
    delivery at t=0 retries at 1.0, a second failure at t=1 retries at
    3.0, the third attempt delivers and clears the failure state."""
    clk = FakeClock()
    tr = ScriptedTransport(scripts={0: ["fail"] * 2}, clock=clk)
    ch = DeliveryChannel(
        tr, max_attempts=1, base_backoff_s=1.0, backoff_factor=2.0,
        jitter=0.0, quarantine_after=5, clock=clk, sleep=clk.sleep,
    )
    d, tau0 = _universe()
    b = Broker(d, channel=ch)
    u0 = b.subscribe(_exprs()[0], CAPS, initial_target=tau0)
    rm, ad = _stream(d, 1, seed=2)[0]
    b.process_changeset(rm, ad)  # attempt 1 fails at t=0
    assert ch.failures(u0) == 1 and ch.next_retry_at(u0) == 1.0
    assert not ch.retry_due(u0)  # backoff not yet elapsed
    clk.advance(1.0)
    assert ch.retry_due(u0)
    b.flush([u0])  # attempt 2 fails at t=1
    assert ch.failures(u0) == 2 and ch.next_retry_at(u0) == 3.0
    clk.advance(2.0)
    b.flush([u0])  # attempt 3 succeeds
    assert ch.failures(u0) == 0 and u0.since > b._last_cid
    assert tr.log == [(0, "fail"), (0, "fail"), (0, "ok")]


def test_backpressure_pump_terminates_into_quarantine():
    """With a full in-flight retry queue the ingest path blocks on the
    injected clock and pumps retries; every pump either acks or moves a
    subscriber toward quarantine, so ingest always makes progress — a
    poisonous consumer degrades to quarantine, never a deadlock."""
    clk = FakeClock()
    tr = ScriptedTransport(scripts={0: ["fail"] * 10}, clock=clk)
    ch = DeliveryChannel(
        tr, max_attempts=1, base_backoff_s=1.0, jitter=0.0,
        quarantine_after=2, max_in_flight=1, clock=clk, sleep=clk.sleep,
    )
    d, tau0 = _universe()
    exprs = _exprs()
    b = Broker(d, channel=ch)
    s0 = b.subscribe(exprs[0], CAPS, initial_target=tau0)
    s1 = b.subscribe(exprs[2], CAPS, initial_target=tau0)
    for rm, ad in _stream(d, 4, seed=13):
        b.process_changeset(rm, ad)  # never deadlocks on the fake clock
    assert b._last_cid > 0 and ch.is_quarantined(s0)
    assert ch.in_flight() == 0  # quarantine emptied the retry queue
    assert s1.since > s0.since  # healthy subscriber kept advancing
    assert len(tr.delivered.get(1, [])) >= 1


def test_timeout_counts_as_failed_delivery():
    """A transport that 'succeeds' slower than ``timeout_s`` on the
    injected clock is a failed delivery: the subscriber stays pinned."""
    clk = FakeClock()
    tr = ScriptedTransport(
        scripts={0: ["timeout"]}, clock=clk, timeout_advance=5.0
    )
    ch = DeliveryChannel(
        tr, max_attempts=1, timeout_s=1.0, jitter=0.0,
        base_backoff_s=1.0, clock=clk, sleep=clk.sleep,
    )
    d, tau0 = _universe()
    b = Broker(d, channel=ch)
    u0 = b.subscribe(_exprs()[0], CAPS, initial_target=tau0)
    rm, ad = _stream(d, 1, seed=2)[0]
    b.process_changeset(rm, ad)
    assert ch.stats.timeouts == 1 and ch.failures(u0) == 1
    assert u0.since <= b._last_cid  # not committed


def _goal_stream(d, n, per=4):
    """τ-growing stream: every changeset adds ``per`` fresh matching rows,
    each small enough to dodge the host-side input-capacity pre-growth —
    so with tiny τ capacity the *output* side must overflow mid-run."""
    z = np.zeros((0, 3), np.int32)
    return [
        (
            z,
            d.encode_triples(
                [(f"e:{i}-{j}", "p:goals", str(i * per + j))
                 for j in range(per)]
            ),
        )
        for i in range(n)
    ]


def test_degraded_fire_ceiling_falls_back_bit_identical():
    """With ``max_fire_retries=0`` an overflowing fire falls back to the
    per-subscriber seed path instead of recompile-retrying the cohort —
    same outputs, same τ, with the degradation surfaced in
    ``Broker.degraded_fires``."""
    d, tau0 = _universe()
    exprs = _exprs()
    b_deg = Broker(d, max_fire_retries=0)
    g0 = b_deg.subscribe(exprs[2], tiny_caps(), initial_target=tau0)
    d2, tau0b = _universe()
    b_ret = Broker(d2)  # default ceiling: whole-fire recompile-retry path
    g1 = b_ret.subscribe(exprs[2], tiny_caps(), initial_target=tau0b)
    for (rm, ad), (rm2, ad2) in zip(
        _goal_stream(d, 6), _goal_stream(d2, 6)
    ):
        o1 = b_deg.process_changeset(rm, ad)
        o2 = b_ret.process_changeset(rm2, ad2)
        assert_results_identical(o1, o2, "degraded vs retry")
    np.testing.assert_array_equal(to_numpy(g0.tau), to_numpy(g1.tau))
    assert b_deg.degraded_fires > 0 and b_ret.degraded_fires == 0
    assert any(st.degraded_fires > 0 for st in b_deg.stats)  # surfaced


# ---------------------------------------------------------------------------
# unified sequence clock
# ---------------------------------------------------------------------------


def test_unified_clock_journal_on_off_identical():
    """subscribe/ingest/committed-fire each consume one sequence tick with
    or without a journal, so journal-on and journal-off brokers assign
    identical changeset ids, frontiers, and stats sequence points."""
    d, tau0 = _universe()
    exprs = _exprs()
    import tempfile
    from pathlib import Path

    tmp = Path(tempfile.mkdtemp())
    try:
        j = ChangesetJournal(tmp / "wal", fsync=False)
        bj = Broker(d, journal=j)
        bn = Broker(d)
        for b in (bj, bn):
            b.subscribe(exprs[0], CAPS, initial_target=tau0)
            b.subscribe(
                exprs[1], CAPS, initial_target=tau0, policy=PushPolicy.every(2)
            )
        stream = _stream(d, 4, seed=17)
        for i, (rm, ad) in enumerate(stream):
            got = bj.process_changeset(rm, ad)
            want = bn.process_changeset(rm, ad)
            assert_results_identical(got, want, ("step", i))
            assert bj._seq == bn._seq and bj._last_cid == bn._last_cid
            assert [s.since for s in bj.subs] == [s.since for s in bn.subs]
        got, want = bj.flush(), bn.flush()
        assert_results_identical(got, want, "flush")
        assert bj._seq == bn._seq
        assert bj.stats[-1].seq == bn.stats[-1].seq == bj._seq
        # the flush's committed fire is itself a journal record
        kinds = [r.kind for r in j.records()]
        assert kinds.count("subscribe") == 2
        assert kinds.count("ingest") == len(stream)
        assert kinds.count("fire") >= 1 and kinds[-1] == "fire"
        assert j.last_seq == bj._seq
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
