"""Reduces a profiler trace of the measured window to the numbers the
per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. Device operations are the events of the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane, executables those of its
``XLA Modules`` line; host spans are the harness's own ``TraceAnnotation``
events (``bench.*``) on the host plane. All of them share the host's clock.
The window is the harness's ``bench.window`` span, and every interval is
clipped to it.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]  # seconds, host clock of the trace

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float
    module: str = ""

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def short(self) -> str:
        """An operation's HLO instruction name (``%fusion.12``), the text
        before its signature, after its executable's name where known; an
        executable's name as it is."""
        op = self.name.split(" = ", 1)[0][:120]
        return f"{self.module}/{op}" if self.module else op


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the given intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Total length of the intersection of two disjoint sorted covers."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(cover: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of the window the cover leaves out."""
    out, t = [], window[0]
    for s, e in cover:
        if s > t:
            out.append((t, min(s, window[1])))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return [g for g in out if g[1] > g[0]]


@dataclasses.dataclass
class Summary:
    window: Interval
    ops: Dict[str, List[Event]]  # device plane -> its operations
    modules: Dict[str, List[Event]]  # device plane -> its executables
    spans: Dict[str, List[Interval]]  # harness span name -> intervals

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy(self, plane: str) -> List[Interval]:
        return union((e.start, e.end) for e in self.ops[plane])

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips that
        ran any."""
        planes = [p for p in self.ops if self.ops[p]]
        if not planes:
            return 0.0
        return sum(sum(e - s for s, e in self.busy(p))
                   for p in planes) / len(planes)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def idle_inside(self, span_names: Sequence[str]) -> float:
        """Device-idle seconds inside the named host spans, averaged over
        the chips that ran any operation."""
        spans = union(iv for n in span_names for iv in self.spans.get(n, ()))
        planes = [p for p in self.ops if self.ops[p]] or list(self.ops)
        if not planes:
            return overlap(spans, [self.window])
        return sum(overlap(spans, gaps(self.busy(p), self.window))
                   for p in planes) / len(planes)

    def count(self, span_name: str) -> int:
        return len(self.spans.get(span_name, ()))

    def op_seconds(self, keep: Callable[[Event], bool]) -> float:
        return sum(e.dur for evs in self.ops.values() for e in evs if keep(e))

    def module_seconds(self, keep: Callable[[Event], bool]) -> float:
        return sum(e.dur for evs in self.modules.values() for e in evs
                   if keep(e))

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, and the longest idle
        gaps named by the harness span the host was in for most of each."""
        by_op: Dict[str, float] = {}
        for evs in self.ops.values():
            for e in evs:
                by_op[e.short] = by_op.get(e.short, 0.0) + e.dur
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        planes = [p for p in self.ops if self.ops[p]]
        idle: List[Tuple[str, float]] = []
        for p in planes[:1]:
            for g in gaps(self.busy(p), self.window):
                best, best_t = "outside spans", 0.0
                for name, ivs in self.spans.items():
                    t = overlap([g], union(ivs))
                    if t > best_t:
                        best, best_t = name, t
                idle.append((best, g[1] - g[0]))
        idle.sort(key=lambda kv: -kv[1])
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in idle[:top]]}


def xplane_path(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce(profile, span_names: Sequence[str]) -> Summary:
    """Summary of one ``ProfileData``: the window, device operations and
    executables clipped to it, and the harness's spans inside it."""
    spans: Dict[str, List[Interval]] = {n: [] for n in span_names}
    window = None
    devices = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                elif ev.name in spans:
                    spans[ev.name].append((ev.start_ns * 1e-9,
                                           ev.end_ns * 1e-9))
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    lo, hi = window

    def clip(ev) -> Event | None:
        s, e = max(ev.start_ns * 1e-9, lo), min(ev.end_ns * 1e-9, hi)
        if e <= s:
            return None
        return Event(ev.name, s, e)

    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    for plane in devices:
        ops[plane.name], modules[plane.name] = [], []
        for line in plane.lines:
            dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
            if dest is None:
                continue
            for ev in line.events:
                c = clip(ev)
                if c is not None:
                    dest[plane.name].append(c)
    for plane in ops:
        ops[plane] = _in_modules(ops[plane], modules[plane])
    spans = {n: [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]
             for n, ivs in spans.items()}
    return Summary(window, ops, modules, spans)


def _in_modules(ops: List[Event], modules: List[Event]) -> List[Event]:
    """Each operation tagged with the executable it ran in (the module
    whose interval holds its start), its hash dropped: ``jit_step``."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        name = ""
        if i >= 0 and op.start < mods[i].end:
            name = mods[i].name.split("(", 1)[0]
        out.append(dataclasses.replace(op, module=name))
    return out


def load(trace_dir: Path, span_names: Sequence[str]) -> Summary:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(str(xplane_path(trace_dir))),
                  span_names)


def roofline_share(bytes_moved: float, seconds: float,
                   peaks: dict) -> float | None:
    """Least time the chip could take to move the bytes, at its peak HBM
    bandwidth, over the time taken, in %. None where nothing was
    measured."""
    if seconds <= 0 or bytes_moved <= 0:
        return None
    return 100.0 * bytes_moved / peaks["hbm_bytes_per_s"] / seconds
