"""The readers of the program's spans and device scopes, on summaries and
scope tables made by hand.

    python -m pytest bench/tests/test_program_trace.py
"""
import importlib.util
import types
from pathlib import Path

import pytest

import program_trace as pt
import trace_reduce as tr

METRICS = Path(__file__).resolve().parents[1] / "metrics"
PLANE = "/device:TPU:0"
TABLE = {
    "jit_step": {
        "while.1": "cohort.eval_removed",
        "fusion.2": "cohort.eval_removed",  # the loop's body
        "sort.3": "cohort.build_index",
        "fusion.4": "cohort.combine",
        "fusion.5": "cohort.eval_added",
    },
}


def _summary():
    """Two changesets in a 10-s window. Each runs one ``jit_step``: an
    index sort, a while loop with two body fusions inside it, an added-side
    fusion, an unscoped copy and the combine. The chip idles inside the
    journal and evaluate spans between them; a words executable runs
    outside the cohort step."""
    ops, mods = [], []
    spans = {n: [] for n in ("broker.process_changeset", "journal.append",
                             "broker.evaluate")}
    for t in (0.0, 5.0):
        spans["broker.process_changeset"].append((t, t + 4.0))
        spans["journal.append"].append((t, t + 0.5))
        spans["broker.evaluate"].append((t + 0.5, t + 1.5))
        mods.append(tr.Event("jit_step(7)", t + 1.0, t + 3.0))
        mods.append(tr.Event("jit__lambda_(9)", t + 0.7, t + 0.9))
        ops += [
            tr.Event("%fusion.2 = words", t + 0.7, t + 0.9),  # not cohort
            tr.Event("%sort.3 = s32[] sort(...)", t + 1.0, t + 1.4),
            tr.Event("%while.1 = (s32[]) while(...)", t + 1.4, t + 2.2),
            tr.Event("%fusion.2 = s32[] fusion(...)", t + 1.5, t + 1.8),
            tr.Event("%fusion.2 = s32[] fusion(...)", t + 1.9, t + 2.1),
            tr.Event("%fusion.5 = s32[] fusion(...)", t + 2.2, t + 2.5),
            tr.Event("%copy.6 = s32[] copy(...)", t + 2.5, t + 2.6),
            tr.Event("%fusion.4 = s32[] fusion(...)", t + 2.6, t + 3.0),
        ]
    return tr.Summary((0.0, 10.0), {PLANE: ops}, {PLANE: mods}, spans)


def test_phases_take_the_union_of_nested_events():
    loaded = (_summary(), TABLE)
    # the loop's 0.8 s holds its body's 0.5 s: counted once
    assert pt.phase_ms(loaded, ("cohort.eval_removed",)) == \
        pytest.approx(800.0)
    assert pt.phase_ms(loaded, ("cohort.eval_removed",
                                "cohort.eval_added")) == pytest.approx(1100.0)
    assert pt.phase_ms(loaded, ("cohort.build_index",)) == \
        pytest.approx(400.0)
    assert pt.phase_ms(loaded, ("cohort.combine",)) == pytest.approx(400.0)
    # the copy (0.1 s of each 2-s step) is the unscoped remainder
    assert pt.scoped_share(loaded) == pytest.approx(95.0)


def test_only_the_cohort_modules_are_read():
    assert pt.phase_ms((_summary(), {"jit_other": TABLE["jit_step"]}),
                       ("cohort.combine",)) == 0.0
    table = {"jit__lambda_": TABLE["jit_step"]}  # the words pass's fusion.2
    assert pt.phase_ms((_summary(), table), ("cohort.eval_removed",)) == 0.0


def test_idle_inside_program_spans_per_changeset():
    loaded = (_summary(), TABLE)
    # journal.append: 0.5 s of idle each time
    assert pt.idle_in_ms(loaded, "journal.append") == pytest.approx(500.0)
    # broker.evaluate (0.5-1.5): busy 0.7-0.9 (words) and from 1.0
    assert pt.idle_in_ms(loaded, "broker.evaluate") == pytest.approx(300.0)


def test_nothing_to_read_gives_none():
    assert pt.phase_ms(None, ("cohort.combine",)) is None
    assert pt.scoped_share(None) is None
    assert pt.idle_in_ms(None, "journal.append") is None
    empty = tr.Summary((0.0, 1.0), {PLANE: []}, {PLANE: []}, {})
    assert pt.phase_ms((empty, TABLE), ("cohort.combine",)) is None
    assert pt.scoped_share((empty, TABLE)) is None
    assert pt.idle_in_ms((empty, TABLE), "journal.append") is None
    assert pt.scoped_share((_summary(), {})) is None
    assert pt.load(types.SimpleNamespace(trace=None, workload="w")) is None


def _read(name, run):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


@pytest.mark.parametrize("name, want", [
    ("index_build_ms", 400.0),
    ("probe_join_ms", 1100.0),
    ("replica_commit_ms", 400.0),
    ("cohort_scoped_share", 95.0),
    ("host_gap_journal_ms.steady", 500.0),
    ("host_gap_journal_ms.catchup", 500.0),
    ("host_gap_evaluate_ms.steady", 300.0),
    ("host_gap_evaluate_ms.catchup", 300.0),
])
def test_metric_files_read_the_loaded_trace(name, want, monkeypatch):
    run = types.SimpleNamespace(trace=object(), workload="hand-made")
    monkeypatch.setitem(pt._loaded, pt.WORK / "hand-made" / "trace",
                        (_summary(), TABLE))
    assert _read(name, run) == pytest.approx(want)
