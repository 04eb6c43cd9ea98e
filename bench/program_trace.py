"""The program's own spans and device scopes in a traced window.

The harness reduces its trace with its own span names only. This module
reloads the same trace (``bench/.work/<workload>/trace``) with the span
names the program declares (``repro.core.tracing.SPANS``), once per
process, and names each device operation of the cohort-step executables by
the ``cohort.*`` scope its HLO instruction came from
(``repro.core.tracing.scope_table()``, built from the executables the
broker holds, so it is read while the run's broker is alive).

Against a program without that module, or a run without a trace, every
function here returns None, and the metric is left out of the line.
"""
from __future__ import annotations

import bisect
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce
from trace_reduce import Interval, Summary, union

WORK = Path(__file__).resolve().parent / ".work"
COHORT_MODULES = ("jit_step", "jit_step_delta")
CALL_SPAN = "broker.process_changeset"

_loaded: Dict[Path, Tuple[Summary, Dict[str, Dict[str, str]]]] = {}


def _tracing():
    try:
        from repro.core import tracing
    except ImportError:
        return None
    return tracing


def load(run) -> Optional[Tuple[Summary, Dict[str, Dict[str, str]]]]:
    """(window summary with the program's spans, scope table), or None."""
    tracing = _tracing()
    if run.trace is None or tracing is None:
        return None
    trace_dir = WORK / run.workload / "trace"
    if trace_dir not in _loaded:
        _loaded[trace_dir] = (trace_reduce.load(trace_dir, tracing.SPANS),
                              tracing.scope_table())
    return _loaded[trace_dir]


def changesets(s: Summary) -> int:
    return s.count(CALL_SPAN)


def _module_names(ops, modules) -> List[str]:
    """Name (``jit_step``, the program id dropped) of the executable each
    operation ran in, "" where none holds its start."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        inside = i >= 0 and op.start < mods[i].end
        out.append(mods[i].name.split("(", 1)[0] if inside else "")
    return out


def scope_covers(s: Summary, table: Dict[str, Dict[str, str]], plane: str
                 ) -> Tuple[Dict[str, List[Interval]], List[Interval]]:
    """On one chip: per scope, the union of the intervals of the cohort
    executables' operations under it (a ``while`` contains its body's
    operations, so intervals nest); and the union of the cohort
    executables' own intervals."""
    mods = [m for m in s.modules.get(plane, ())
            if m.name.split("(", 1)[0] in COHORT_MODULES]
    ops = s.ops.get(plane, [])
    ivs: Dict[str, List[Interval]] = {}
    for op, module in zip(ops, _module_names(ops, mods)):
        scope = table.get(module, {}).get(
            op.name.split(" = ", 1)[0].lstrip("%"))
        if scope is not None:
            ivs.setdefault(scope, []).append((op.start, op.end))
    return ({sc: union(iv) for sc, iv in ivs.items()},
            union((m.start, m.end) for m in mods))


def _length(ivs: Sequence[Interval]) -> float:
    return sum(e - s for s, e in ivs)


def phase_ms(loaded, scopes: Sequence[str]) -> Optional[float]:
    """Device time under any of ``scopes``, per changeset, in ms (summed
    over chips)."""
    if loaded is None:
        return None
    s, table = loaded
    n = changesets(s)
    if not n or not table:
        return None
    total, seen = 0.0, False
    for plane in s.ops:
        covers, modules = scope_covers(s, table, plane)
        seen = seen or bool(modules)
        total += _length(union(iv for sc in scopes
                               for iv in covers.get(sc, ())))
    return 1e3 * total / n if seen else None


def scoped_share(loaded) -> Optional[float]:
    """% of the cohort executables' device time under some scope."""
    if loaded is None:
        return None
    s, table = loaded
    if not table:
        return None
    scoped = total = 0.0
    for plane in s.ops:
        covers, modules = scope_covers(s, table, plane)
        total += _length(modules)
        scoped += trace_reduce.overlap(
            union(iv for ivs in covers.values() for iv in ivs), modules)
    return 100.0 * scoped / total if total > 0 else None


def idle_in_ms(loaded, span: str) -> Optional[float]:
    """Device-idle time inside the program's ``span``, per changeset, in
    ms."""
    if loaded is None:
        return None
    s, _ = loaded
    n = changesets(s)
    if not n:
        return None
    return 1e3 * s.idle_inside((span,)) / n
