"""The broker's own spans, device scopes and compile count.

Three things, each read by a per-layer metric of the benchmark
(``bench/program_trace.py``) or by an operator looking at a trace:

* **Host spans** (:func:`span`): thin ``jax.profiler.TraceAnnotation``
  wrappers. Their events land on the host plane of the same ``.xplane.pb``
  as the device operations, on the profiler's clock, so every device-idle
  gap can be put down to a program phase. Every span carries the sequence id
  of its changeset (``seq=``), so the spans of one changeset share it. With
  no trace active a span costs about a microsecond.
* **Device scopes** (:data:`SCOPES`): ``jax.named_scope`` around each
  phase of the cohort step. They change only the HLO's ``op_name``
  metadata. The TPU trace names an operation by its HLO instruction
  alone, so :func:`scope_table` maps instruction names back to scopes from
  the optimized HLO of the executables the broker compiled or loaded.
* **Compiles** (:func:`compile_count`): backend compiles in this process,
  persistent-cache loads and eager operations included, counted by one
  ``jax.monitoring`` listener registered at import.
"""
from __future__ import annotations

import re
import weakref
from contextvars import ContextVar
from typing import Dict, List, Optional

import jax

# every host span the program opens, in the order a changeset meets them
SPANS = (
    "broker.process_changeset",  # the whole call
    "broker.flush",  # the whole call
    "journal.append",  # ChangesetJournal.append
    "journal.fsync",  # inside journal.append, and in sync
    "broker.compose",  # _apply_ingest: Def-6 composition into batches
    "broker.evaluate",  # _evaluate_frontiers
    "broker.statics",  # in evaluate: capacity guards, bank, statics
    "broker.bank_pass",  # in evaluate: the deleted-side words executable
    "broker.cohort_dispatch",  # in evaluate: the cohort executable call
    "broker.await_device",  # the overflow-flag readback: host waits on chip
    "broker.commit",  # fire journal record and _commit_staged
    "broker.fanout",  # result hand-out, epochs, _sweep_batches
    "broker.record_stats",  # the call's BrokerStats record
)

# the cohort step's phases (make_cohort_step), outermost scope wins
SCOPES = (
    "cohort.gather",  # stack / gather of stores and maps, I = A ∪ ρ
    "cohort.lanes",  # both lane-bit passes
    "cohort.build_index",  # OPS index of each unique τ
    "cohort.eval_removed",  # Defs 13, 11-12 over D: probes and joins
    "cohort.eval_added",  # Def 14 over I: probes and joins
    "cohort.combine",  # τ', ρ', Υ (Defs 16-18)
    "cohort.unstack",  # per-member outputs
)

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# sequence id of the changeset whose call is running (0: none)
_seq: ContextVar[int] = ContextVar("changeset_seq", default=0)


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` (one of :data:`SPANS`), with ``ids`` as
    its arguments in the trace; ``seq`` is that of the running
    :class:`call_span` unless given."""
    ids.setdefault("seq", _seq.get())
    return jax.profiler.TraceAnnotation(name, **ids)


class call_span:
    """The span of one whole broker call (``broker.process_changeset``,
    ``broker.flush``): every :func:`span` opened inside it carries its
    ``seq``, the journal's included."""

    __slots__ = ("_ann", "_seq", "_token")

    def __init__(self, name: str, seq: int):
        self._ann = jax.profiler.TraceAnnotation(name, seq=seq)
        self._seq = seq

    def __enter__(self) -> "call_span":
        self._token = _seq.set(self._seq)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        _seq.reset(self._token)


# -- compiles ---------------------------------------------------------------

_compiles = 0


def _on_duration(event: str, duration: float, **kw) -> None:
    global _compiles
    if event == BACKEND_COMPILE_EVENT:
        _compiles += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_count() -> int:
    """Backend compiles (cache loads included) since the process started."""
    return _compiles


# -- scope table ------------------------------------------------------------

# executables registered by the broker, held weakly: one evicted from the
# broker's cache drops out here too
_executables: "weakref.WeakSet" = weakref.WeakSet()

_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) .*\{$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")


def register(compiled) -> None:
    """Keeps ``compiled`` (a ``jax.stages.Compiled``) for
    :func:`scope_table`; nothing is read from it until then."""
    _executables.add(compiled)


def outermost_scope(op_name: str) -> Optional[str]:
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return None


def _parse(hlo_text: str):
    """``[(computation, [(instruction, op_name, refs)])]`` of an HLO
    module's text; ``refs`` are the names the instruction line mentions
    (operands, called computations)."""
    comps = []
    body = None
    for line in hlo_text.split("\n"):
        if body is None:
            m = _COMPUTATION.match(line)
            if m:
                body = []
                comps.append((m.group(1), body))
        elif line.startswith("}"):
            body = None
        else:
            m = _INSTR.match(line)
            if m:
                rest = m.group(2)
                op = _OP_NAME.search(rest)
                body.append((m.group(1), op.group(1) if op else "",
                             _REF.findall(rest)))
    return comps


def instruction_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: outermost scope}`` of one optimized HLO module.

    An instruction's own ``op_name`` decides. One that has no scope of its
    own (XLA-inserted copies, broadcasts of constants, the
    pieces a pass splits an operation into) takes the scope of its first
    user in the same computation, else of its first operand, else of the
    instruction that calls its computation (a loop body, a fusion)."""
    comps = _parse(hlo_text)
    out: Dict[str, str] = {}
    for _, body in comps:
        for name, op_name, _ in body:
            s = outermost_scope(op_name)
            if s is not None:
                out[name] = s
    callers: Dict[str, List[str]] = {}
    for _, body in comps:
        users: Dict[str, List[str]] = {}
        for name, _, refs in body:
            for r in refs:
                users.setdefault(r, []).append(name)
                callers.setdefault(r, []).append(name)
        for name, _, refs in reversed(body):
            if name not in out:
                s = next((out[u] for u in users.get(name, ()) if u in out),
                         None)
                if s is not None:
                    out[name] = s
        for name, _, refs in body:
            if name not in out:
                s = next((out[r] for r in refs if r in out), None)
                if s is not None:
                    out[name] = s
    for comp, body in comps:
        s = next((out[c] for c in callers.get(comp, ()) if c in out), None)
        if s is None:
            continue
        for name, _, _ in body:
            out.setdefault(name, s)
    return out


def scope_table() -> Dict[str, Dict[str, str]]:
    """``{module: {instruction name: outermost scope}}`` over every
    registered executable that holds a scope.

    ``module`` is the name the trace prints before the program id
    (``jit_step``). The TPU trace's program id is not the runtime's
    fingerprint of the executable, so executables that share a name merge,
    leaving out any instruction whose scope they disagree on. Built on
    call, from the optimized HLO (``compiled.as_text()``), which an
    executable loaded from the persistent cache holds as well."""
    tables: Dict[str, Dict[str, str]] = {}
    clash: Dict[str, set] = {}
    for c in list(_executables):
        text = c.as_text() or ""
        m = _MODULE.search(text)
        if m is None:
            continue
        merged = tables.setdefault(m.group(1), {})
        for instr, s in instruction_scopes(text).items():
            if merged.setdefault(instr, s) != s:
                clash.setdefault(m.group(1), set()).add(instr)
    for name, instrs in clash.items():
        for instr in instrs:
            del tables[name][instr]
    return {name: t for name, t in tables.items() if t}
