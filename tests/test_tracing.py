"""The broker's spans, device scopes and compile count (core/tracing.py).

One small broker, on the CPU: an eager subscriber fires the cohort step on
every changeset, and two deferred subscribers on different frontiers fire
the delta-chain step at the closing flush.
"""
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import (
    Broker,
    ChangesetJournal,
    Dictionary,
    InterestExpr,
    PushPolicy,
    StepCapacities,
)
from repro.core import tracing

A = "rdf:type"
CAPS = StepCapacities(n_removed=16, n_added=16, tau=64, rho=64, pulls=32)
# an object-subject join: its probes read τ's OPS index (build_index)
EAGER = InterestExpr.parse(
    "g", "t0",
    bgp=[("?a", A, "c:Athlete"), ("?a", "p:team", "?t"), ("?t", A, "c:Team")],
)
DEFERRED = InterestExpr.parse(
    "g", "t1", bgp=[("?a", A, "c:Team"), ("?a", "p:rank", "?v")]
)


def _changeset(d, k):
    """The k-th changeset; every one has the same row counts."""
    removed = [
        (f"e:{k % 3}", "p:goals", str(k)),
        (f"e:{k % 4}", "p:rank", "1"),
    ]
    added = [
        (f"e:{k}", A, "c:Athlete"),
        (f"e:{k}", "p:team", f"e:{k + 1}"),
        (f"e:{k + 1}", A, "c:Team"),
        (f"e:{k + 1}", "p:rank", str(k % 5)),
    ]
    return d.encode_triples(removed), d.encode_triples(added)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """Four changesets and a flush; the third repeats the second's shapes."""
    d = Dictionary()
    tau0 = d.encode_triples([
        ("e:1", A, "c:Athlete"),
        ("e:1", "p:team", "e:2"),
        ("e:2", A, "c:Team"),
    ])
    journal = ChangesetJournal(tmp_path_factory.mktemp("journal"))
    broker = Broker(d, journal=journal)
    broker.subscribe(EAGER, CAPS, initial_target=tau0,
                     policy=PushPolicy.every(1))
    broker.subscribe(DEFERRED, CAPS, initial_target=tau0,
                     policy=PushPolicy.every(100))
    changesets = [_changeset(d, k) for k in range(5)]
    for k in range(3):
        broker.process_changeset(*changesets[k])
    # a second deferred frontier, so the flush fires the delta chain
    broker.subscribe(DEFERRED, CAPS, initial_target=tau0[:1],
                     policy=PushPolicy.every(100))
    broker.process_changeset(*changesets[3])
    broker.flush()
    return broker, changesets[4]


def _entry_instructions(hlo: str):
    """(name, opcode) of the entry computation's while, sort, custom-call
    and fusion instructions."""
    lines = hlo.split("\n")
    start = next(i for i, ln in enumerate(lines) if ln.startswith("ENTRY"))
    out = []
    for ln in lines[start + 1:]:
        if ln.startswith("}"):
            break
        m = re.match(r"\s*(?:ROOT )?%?([^\s=]+) = .*? "
                     r"(while|sort|custom-call|fusion)\(", ln)
        if m:
            out.append(m.groups())
    return out


def test_scope_table_maps_both_cohort_steps(run):
    broker, _ = run
    steps = {}
    for key, fn in broker._exec_cache.items():
        if key[0] in ("cohort", "cohort-delta"):
            steps[key[0]] = fn
    assert set(steps) == {"cohort", "cohort-delta"}
    table = tracing.scope_table()
    for kind, fn in steps.items():
        text = fn.as_text()
        module = re.search(r"^HloModule ([^\s,]+)", text, re.M).group(1)
        assert module == {"cohort": "jit_step",
                          "cohort-delta": "jit_step_delta"}[kind]
        scopes = tracing.instruction_scopes(text)
        # other live executables of the same name merge into the table;
        # every instruction kept there has this executable's scope
        assert table[module]
        assert all(scopes.get(i, s) == s for i, s in table[module].items())
        entry = _entry_instructions(text)
        assert any(op == "while" for _, op in entry), kind
        for name, op in entry:
            if op != "fusion":
                assert scopes.get(name) in tracing.SCOPES, (kind, name, op)
        fusions = [name for name, op in entry if op == "fusion"]
        mapped = sum(name in scopes for name in fusions)
        assert mapped >= 0.9 * len(fusions), (kind, mapped, len(fusions))
        want = {"cohort.gather", "cohort.lanes", "cohort.eval_removed",
                "cohort.eval_added", "cohort.combine"}
        if kind == "cohort":
            want.add("cohort.build_index")
        assert want <= set(scopes.values()), kind


def _host_events(trace_dir: Path):
    from jax.profiler import ProfileData

    path = next(Path(trace_dir).glob("**/*.xplane.pb"))
    profile = ProfileData.from_file(str(path))
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     dict(ev.stats)))
    return out


def test_process_changeset_spans_nest_with_one_seq(run, tmp_path):
    broker, changeset = run
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.outer"):
            broker.process_changeset(*changeset)
    seq = broker.stats[-1].seq - 1  # the fire's tick follows the changeset's
    ev = _host_events(tmp_path)
    (outer,) = ev["test.outer"]
    (call,) = ev["broker.process_changeset"]
    assert outer[0] <= call[0] and call[1] <= outer[1]
    assert call[2]["seq"] == seq
    for name in ("journal.append", "journal.fsync", "broker.compose",
                 "broker.evaluate", "broker.statics", "broker.bank_pass",
                 "broker.cohort_dispatch", "broker.await_device",
                 "broker.commit", "broker.fanout", "broker.record_stats"):
        assert name in tracing.SPANS
        assert ev.get(name), name
        for start, end, stats in ev[name]:
            assert call[0] <= start and end <= call[1], name
            assert stats["seq"] == seq, name
    # the ingest record is the changeset's own; the fire record follows
    records = sorted(s["record"] for _, _, s in ev["journal.append"])
    assert records == [seq, seq + 1]


def test_compiles_counted_on_a_new_shape_and_not_on_a_repeat(run):
    broker, _ = run
    first, second, repeat = broker.stats[:3]
    assert first.compiles >= 1
    assert second.compiles >= 1  # the deferred batch composes for the 1st time
    assert repeat.compiles == 0
    assert not hasattr(broker, "words_compiles")


def test_span_is_cheap_without_a_trace():
    import time

    n = 20000
    t = time.perf_counter()
    for _ in range(n):
        with tracing.span("broker.evaluate"):
            pass
    assert (time.perf_counter() - t) / n < 50e-6


HLO = """HloModule jit_step, is_scheduled=true

%body (p: s32[4]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  ROOT %add.1 = s32[4]{0} add(%p, %p)
}

ENTRY %main (x: s32[4]) -> s32[4] {
  %x = s32[4]{0} parameter(0), metadata={op_name="x"}
  %copy.2 = s32[4]{0} copy(%x)
  %fusion.3 = s32[4]{0} fusion(%copy.2), kind=kLoop, calls=%f, \
metadata={op_name="jit(step)/cohort.lanes/and" stack_frame_id=2}
  %while.4 = s32[4]{0} while(%fusion.3), condition=%c, body=%body, \
metadata={op_name="jit(step)/cohort.eval_added/while/cohort.combine"}
  ROOT %copy.5 = s32[4]{0} copy(%while.4)
}
"""


def test_outermost_scope_wins_and_the_rest_is_inferred():
    assert tracing.outermost_scope(
        "jit(step)/cohort.eval_added/while/body/cohort.combine/add"
    ) == "cohort.eval_added"
    assert tracing.outermost_scope("jit(step)/while/body/add") is None
    assert tracing.instruction_scopes(HLO) == {
        "x": "cohort.lanes",  # its user's
        "copy.2": "cohort.lanes",  # its user's
        "fusion.3": "cohort.lanes",
        "while.4": "cohort.eval_added",
        "copy.5": "cohort.eval_added",  # no user: its operand's
        "p": "cohort.eval_added",  # the loop body: its caller's
        "add.1": "cohort.eval_added",
    }


def test_seq_defaults_to_the_running_call():
    with tracing.call_span("broker.flush", 41):
        assert tracing._seq.get() == 41
    assert tracing._seq.get() == 0
    assert np.all([s in tracing.SPANS for s in
                   ("broker.flush", "broker.process_changeset")])


class _Compiled:
    """Stands in for a ``jax.stages.Compiled``: only its HLO text."""

    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


def test_executables_that_share_a_name_merge_and_drop_clashes():
    one = HLO.replace("HloModule jit_step", "HloModule jit_fake")
    other = one.replace('op_name="jit(step)/cohort.lanes/and',
                        'op_name="jit(step)/cohort.gather/and')
    held = [_Compiled(one), _Compiled(other)]
    for c in held:
        tracing.register(c)
    table = tracing.scope_table()["jit_fake"]
    assert table["while.4"] == "cohort.eval_added"
    # the fusion's own scope, and the two it gives its operands, clash
    for name in ("fusion.3", "copy.2", "x"):
        assert name not in table
