"""Benchmark of the broker's served path on one TPU chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell of ``BENCHMARK.json``. In order: turn on the
persistent compile cache, build the cell's source from the seed (dump,
replica slices and every changeset the run can use), subscribe, warm up one
cycle of the cell's cadences, run the measured window, finish every
changeset that fell due inside it, compare what the window delivered with
the plain reference (``reference.py``), and print one JSON line.

A configuration is ``bench/configs/<config>.json``, a traffic mix
``bench/traffic/<traffic>.json`` and a metric ``bench/metrics/<name>.py``:
the harness finds each by the name ``BENCHMARK.json`` gives it.

Off a TPU it exits 2 and prints no result. ``--rehearse`` runs the same
path on the CPU at the configuration's small rehearsal size and prints no
metric; ``--traffic`` swaps the cell's traffic mix (rate sweeps);
``--control`` runs the configuration's control, which has to read not
correct (the benchmark's own runs never pass it).
"""
from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

import numpy as np  # noqa: E402

# the TPU runtime's own log files would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

import reference  # noqa: E402
import wal  # noqa: E402
from source import DBpediaLive, SourceSize  # noqa: E402

WORK = BENCH / ".work"  # journal and trace of the current run (gitignored)
SPANS = ("bench.wait_due", "bench.ingest", "bench.flush", "bench.block_ready")


def log(*parts) -> None:
    print(*parts, flush=True)


# --------------------------------------------------------------------------
# the cell: configuration + traffic, found by name
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(workload: str, traffic: Optional[str] = None) -> Cell:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    mix = traffic or w["traffic"]
    traffic_d = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())

    def mine(m):
        if "workloads" in m:
            return workload in m["workloads"]
        return True

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if mine(m) and m["moves"] in names]
    return Cell(workload, int(w["chips"]), config, traffic_d, e2e, per_layer)


# --------------------------------------------------------------------------
# source, subscribers, capacities
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Subscriber:
    name: str
    expr: dict  # target, bgp, ogp
    caps: dict
    policy: dict
    slice: Optional[dict] = None


def window_cap(rows: int, n_changesets: int) -> int:
    """A changeset capacity that holds ``n_changesets`` composed."""
    return 1 << (int(1.2 * rows * n_changesets) - 1).bit_length()


def subscribers_of(config: dict) -> List[Subscriber]:
    """The configuration's subscribers, one by one."""
    return [Subscriber(s["name"],
                       {"target": s["target"], "bgp": s["bgp"],
                        "ogp": s.get("ogp", [])},
                       config["caps"][s["caps"]], s["policy"], s.get("slice"))
            for s in config["subscribers"]]


@dataclasses.dataclass
class Source:
    terms: object
    dump: np.ndarray
    slices: Dict[str, np.ndarray]  # subscriber -> initial replica
    plans: Dict[str, reference.Plan]  # subscriber -> query tree
    stream: List[Tuple[np.ndarray, np.ndarray]]


def build_source(config: dict, subs: List[Subscriber], seed: int,
                 n_changesets: int, rehearse: bool) -> Source:
    """Dump, slices and every changeset of the run, all encoded before any
    subscriber compiles, with every interest constant in the term table, so
    the program's dictionary never grows mid-run."""
    size = config["rehearsal"]["source_size"] if rehearse \
        else config["source_size"]
    gen = DBpediaLive(SourceSize(**size), seed)
    dump = gen.initial_dump()
    plans, slices = {}, {}
    for s in subs:
        plans[s.name] = reference.compile_plan(
            s.expr["bgp"], s.expr["ogp"], gen.terms.id)
        if s.slice and "subject_prefix" in s.slice:
            prefixes = tuple(s.slice["subject_prefix"])
            slices[s.name] = gen.slice_where(
                lambda t, pre=prefixes: t[0].startswith(pre))
        else:
            slices[s.name] = reference.star_slice(plans[s.name], dump)
    stream = gen.stream(n_changesets)
    return Source(gen.terms, dump, slices, plans, stream)


def caps_of(caps: dict, config: dict, rehearse: bool):
    from repro.core import StepCapacities

    size = config["rehearsal"]["source_size"] if rehearse \
        else config["source_size"]
    n = config["window_changesets"]
    shift = config["rehearsal"]["tau_shift"] if rehearse else 0

    def sized(v):
        return max(1024, v >> shift)

    return StepCapacities(
        n_removed=window_cap(size["removes"], n),
        n_added=window_cap(size["adds"], n),
        tau=sized(caps["tau"]), rho=caps["rho"], pulls=caps["pulls"],
        fanout=caps["fanout"],
        dedup_candidates=caps["dedup_candidates"],
    )


def policy_of(p: dict):
    from repro.core import PushPolicy

    if p.get("priority"):
        return PushPolicy.priority_lane()
    if "max_staleness_s" in p:
        return PushPolicy.max_staleness(float(p["max_staleness_s"]))
    return PushPolicy.every(int(p["every_k"]))


# --------------------------------------------------------------------------
# generator: releases changesets on a schedule of its own
# --------------------------------------------------------------------------

class Generator(threading.Thread):
    """Releases changeset indices at fixed due times, whether or not the
    broker keeps up. A changeset's creation stamp is its due time; how late
    the thread released it is recorded beside."""

    def __init__(self, first: int, due: List[float]):
        super().__init__(daemon=True)
        self.first, self.due = first, due
        self.q: "queue.Queue[Tuple[int, float]]" = queue.Queue()
        self.late: List[float] = []
        self._stop_ev = threading.Event()

    def run(self) -> None:
        for k, t in enumerate(self.due):
            wait = t - time.perf_counter()
            if wait > 0 and self._stop_ev.wait(wait):
                return
            self.late.append(max(0.0, time.perf_counter() - t))
            self.q.put((self.first + k, t))

    def stop(self) -> None:
        self._stop_ev.set()
        self.join(timeout=60)


class SyncWatch:
    """Watches ``os.fsync`` on the journal's segment files, so that the
    comparison can tell an acknowledged changeset whose journal bytes were
    never made durable."""

    def __init__(self, directory: Path):
        self.dir = Path(directory).resolve()
        self.synced: Dict[str, int] = {}  # segment path -> bytes synced
        self._fsync = os.fsync
        os.fsync = self._watch

    def _watch(self, fd: int) -> None:
        self._fsync(fd)
        try:
            path = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            return
        if Path(path).parent == self.dir:
            self.synced[path] = os.fstat(fd).st_size

    def close(self) -> None:
        os.fsync = self._fsync

    def unsynced_bytes(self) -> int:
        return sum(max(0, p.stat().st_size - self.synced.get(str(p), 0))
                   for p in self.dir.glob("wal_*.seg"))


class CompileCounter:
    """Backend compiles (and persistent-cache loads) seen by JAX."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **kw) -> None:
        if event == self.EVENT:
            self.n += 1

    def _on_event(self, event, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    workload: str
    traffic: dict
    setup_s: float
    window_s: float  # window start to the last delivery
    latencies_s: List[float]  # one per delivery
    n_delivered: int  # changesets fully delivered
    compiles_in_window: int
    stats: list  # BrokerStats of the window's calls
    bank_words: int
    device_kind: str
    peaks: Optional[dict]
    trace: Optional[object] = None  # trace_reduce.Summary


class Harness:
    """Drives one broker through warm-up and window; keeps what the
    correctness comparison needs."""

    def __init__(self, cell: Cell, seed: int, seconds: float,
                 rehearse: bool, control: bool = False):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.rehearse = rehearse
        tr = cell.traffic
        self.saturated = tr["arrivals"] == "saturated"
        self.warm = int(tr["warmup_changesets"])
        if self.saturated:
            self.n_window = int(tr["backlog_changesets"])
        else:
            self.interval = 1.0 / float(tr["rate_per_s"])
            self.n_window = max(1, math.ceil(seconds / self.interval))
        self.subs = subscribers_of(cell.config)
        # the control: the journal without fsync
        self.fsync = not control or \
            cell.config["control"].get("journal_fsync", True)

    # -- set-up --------------------------------------------------------
    def build(self) -> None:
        t = time.perf_counter()
        self.src = build_source(self.cell.config, self.subs, self.seed,
                                self.warm + self.n_window, self.rehearse)
        self.rows = [(reference.rows(d), reference.rows(a))
                     for d, a in self.src.stream]
        log(f"phase=source wall_s={time.perf_counter() - t:.3f} "
            f"triples={len(self.src.dump)} terms={len(self.src.terms.names)} "
            f"changesets={len(self.src.stream)} "
            f"removed={sum(len(d) for d, _ in self.src.stream)} "
            f"added={sum(len(a) for _, a in self.src.stream)}")

    def subscribe(self, journal_dir: Path) -> None:
        from repro.core import Broker, ChangesetJournal, Dictionary

        t = time.perf_counter()
        dictionary = Dictionary()
        for term in self.src.terms.names:
            dictionary.encode_term(term)
        self.journal_dir = journal_dir
        self.broker = Broker(
            dictionary,
            journal=ChangesetJournal(journal_dir, fsync=self.fsync))
        self.syncs = SyncWatch(journal_dir)
        self.unsynced = 0  # changesets acknowledged with journal bytes unsynced
        self.handles = []
        for s in self.subs:
            self.handles.append(self.broker.subscribe(
                _expr(s.expr),
                caps_of(s.caps, self.cell.config, self.rehearse),
                initial_target=self.src.slices[s.name],
                policy=policy_of(s.policy)))
        self.compared = list(range(len(self.subs)))
        self.fires: Dict[int, list] = {k: [] for k in self.compared}
        self.since = [0] * len(self.subs)
        self.processed = 0
        self.flushed = False
        log(f"phase=subscribe wall_s={time.perf_counter() - t:.3f} "
            f"subscribers={len(self.subs)} bank_words={self.broker.bank.n_words}")

    # -- one changeset ---------------------------------------------------
    def step(self, i: int):
        import jax

        d, a = self.src.stream[i]
        t, c = time.perf_counter(), self.counter.n
        with jax.profiler.TraceAnnotation("bench.ingest"):
            outs = self.broker.process_changeset(d, a)
        self.processed = i + 1
        fired, t_done = self._deliver(outs)
        log(f"  changeset {i} wall_s={t_done - t:.3f} fired={len(fired)} "
            f"compiles={self.counter.n - c}")
        return fired, t_done

    def pending(self) -> bool:
        return any(s < self.processed for s in self.since)

    def flush(self):
        """Delivers every changeset still pending under a cadence."""
        import jax

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.flush"):
            outs = self.broker.flush()
        self.flushed = True
        fired, t_done = self._deliver(outs)
        log(f"  flush wall_s={t_done - t:.3f} fired={len(fired)}")
        return fired, t_done

    def _deliver(self, outs):
        """Waits until the fired outputs and committed replicas are ready;
        keeps each compared fire with the changesets it covers."""
        import jax

        fired = [k for k, o in enumerate(outs) if o is not None]
        with jax.profiler.TraceAnnotation("bench.block_ready"):
            jax.block_until_ready([
                (outs[k], self.handles[k].tau, self.handles[k].rho)
                for k in fired])
        t_done = time.perf_counter()
        for k in fired:
            if k in self.fires:
                self.fires[k].append(((self.since[k], self.processed - 1),
                                      outs[k]))
            self.since[k] = self.processed
        if self.syncs.unsynced_bytes():
            self.unsynced += 1
        return fired, t_done

    def warmup(self) -> None:
        """One cycle of the cadences, on the cell's own schedule."""
        t = time.perf_counter()
        c0 = self.counter.n
        start = time.perf_counter()
        for i in range(self.warm):
            if not self.saturated:
                wait = start + i * self.interval - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
            self.step(i)
        self.next_due = start + self.warm * (
            0.0 if self.saturated else self.interval)
        log(f"phase=warmup wall_s={time.perf_counter() - t:.3f} "
            f"changesets={self.warm} compiles={self.counter.n - c0} "
            f"cache_hits={self.counter.hits}")

    # -- the window --------------------------------------------------------
    def window(self, trace_dir: Optional[Path]) -> dict:
        import jax

        if trace_dir is not None:
            # host spans and device events; the Python tracer would add an
            # event per Python call, slowing the host it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        n_stats = len(self.broker.stats)
        c0 = self.counter.n
        latencies: List[float] = []
        done: List[float] = []
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = max(time.perf_counter(), self.next_due)
            if self.saturated:
                i = self.warm
                while i < self.warm + self.n_window and \
                        time.perf_counter() - t0 < self.seconds:
                    fired, t_done = self.step(i)
                    latencies += [t_done - t0] * len(fired)
                    done.append(t_done)
                    i += 1
                late = []
            else:
                due = [t0 + k * self.interval for k in range(self.n_window)]
                gen = Generator(self.warm, due)
                gen.start()
                for _ in range(self.n_window):
                    with jax.profiler.TraceAnnotation("bench.wait_due"):
                        i, stamp = gen.q.get()
                    fired, t_done = self.step(i)
                    latencies += [t_done - stamp] * len(fired)
                    done.append(t_done)
                gen.stop()
                late = gen.late
            if self.pending():  # finish what a cadence still holds
                fired, t_done = self.flush()
                latencies += [t_done - (t0 if self.saturated else stamp)] \
                    * len(fired)
                done[-1] = t_done
        compiles = self.counter.n - c0
        if trace_dir is not None:
            jax.profiler.stop_trace()
        t_end = done[-1] if done else time.perf_counter()
        return {
            "t0": t0, "t_end": t_end, "latencies": latencies,
            "n_delivered": len(done), "compiles": compiles,
            "stats": self.broker.stats[n_stats:],
            "attempted": len(done) if self.saturated else self.n_window,
            "late": late,
        }

    # -- correctness -------------------------------------------------------
    def compare(self) -> Dict[str, Tuple[int, int]]:
        """Each number compared, with its limit: every fire of every compared
        subscriber and its final replica against the reference, its fire
        windows against its cadence, and the deployment's guarantees."""
        from repro.core import to_numpy

        out_diff = rep_diff = cadence = overflowed = 0
        self.rows_compared = {f: 0 for f in reference.OUT_FIELDS}
        got_fires = {}
        for k in self.compared:
            got_fires[k] = [
                (w, {f: _rowset(to_numpy(getattr(o, f)))
                     for f in reference.OUT_FIELDS}, bool(o.overflow))
                for w, o in self.fires[k]]
        got_state = {k: (_rowset(to_numpy(self.handles[k].tau)),
                         _rowset(to_numpy(self.handles[k].rho)))
                     for k in self.compared}
        degraded = int(self.broker.degraded_fires)
        grown = [
            (s.name, h.caps) for h, s in zip(self.handles, self.subs)
            if h.caps != caps_of(s.caps, self.cell.config, self.rehearse)]
        if grown:
            print(f"capacities grew: {grown[:8]}", file=sys.stderr)
        journal_unsynced = self.unsynced
        journal_missing = wal.missing_ingests(
            self.journal_dir, self.src.stream[:self.processed])
        for k in self.compared:
            s = self.subs[k]
            rep = reference.Replica(self.src.plans[s.name],
                                    reference.rows(self.src.slices[s.name]))
            for (first, last), got, ovf in got_fires[k]:
                want = rep.fire(self.rows[first:last + 1])
                for f in reference.OUT_FIELDS:
                    self.rows_compared[f] += len(want[f])
                out_diff += sum(len(got[f] ^ want[f])
                                for f in reference.OUT_FIELDS)
                overflowed += ovf
            tau, rho = got_state[k]
            rep_diff += len(tau ^ rep.tau) + len(rho ^ rep.rho)
            cadence += _cadence_misses(s.policy, [w for w, _, _ in
                                                  got_fires[k]],
                                       self.processed, self.flushed)
        return {
            "output_rows_differ": (out_diff, 0),
            "replica_rows_differ": (rep_diff, 0),
            "cadence_misses": (cadence, 0),
            "overflowed_fires": (overflowed, 0),
            "degraded_fires": (degraded, 0),
            "caps_grown": (len(grown), 0),
            "journal_missing": (journal_missing, 0),
            "journal_unsynced": (journal_unsynced, 0),
        }


def _expr(e: dict):
    from repro.core import InterestExpr

    return InterestExpr.parse(source="synthetic://dbpedia-live",
                              target=e["target"], bgp=e["bgp"],
                              ogp=e.get("ogp", []))


def _rowset(arr: np.ndarray) -> set:
    return {(int(s), int(p), int(o)) for s, p, o in arr}


def _cadence_misses(policy: dict, windows: List[Tuple[int, int]],
                    processed: int, flushed: bool) -> int:
    """Fires off a count cadence: an every-k subscriber fires exactly on
    each k-th changeset it has pending (priority: every changeset), and a
    flush at the window's close delivers the rest."""
    if "max_staleness_s" in policy:
        return 0
    k = 1 if policy.get("priority") else int(policy["every_k"])
    want = [(f, f + k - 1) for f in range(0, processed - k + 1, k)]
    if flushed and processed % k:
        want.append((processed - processed % k, processed - 1))
    return len(set(want) ^ set(windows))


# --------------------------------------------------------------------------
# metrics: each in bench/metrics/<name>.py, read(run) -> number or None
# --------------------------------------------------------------------------

def read_metric(name: str, run: Run) -> Optional[float]:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"bench: device kind {kind!r} is not in peaks.json")
    return table[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the configuration's small size; "
                         "prints no metric")
    ap.add_argument("--control", action="store_true",
                    help="run the configuration's control (the journal "
                         "without fsync), which has to read not correct")
    ap.add_argument("--traffic", default=None,
                    help="run the cell's configuration under another "
                         "traffic mix (for rate sweeps)")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload, args.traffic)
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and platform != "tpu":
        print(f"bench: no TPU found (JAX platform {platform!r}); the CPU "
              "rehearsal is --rehearse", file=sys.stderr)
        return 2
    if args.rehearse and platform == "tpu":
        print("bench: --rehearse is the CPU rehearsal", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    kind = devices[0].device_kind
    peaks = None if args.rehearse else peaks_for(kind)
    log(f"device platform={platform} kind={kind!r} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={cache_dir}")

    work = WORK / args.workload
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    h = Harness(cell, args.seed, args.seconds, args.rehearse, args.control)
    h.counter = CompileCounter()
    h.build()
    h.subscribe(work / "journal")
    h.warmup()
    trace_dir = work / "trace" if args.trace else None
    w = h.window(trace_dir)
    setup_s = w["t0"] - PROCESS_T0
    late = w["late"]
    log(f"phase=window wall_s={w['t_end'] - w['t0']:.3f} "
        f"changesets={w['n_delivered']} deliveries={len(w['latencies'])} "
        f"compiles={w['compiles']} generator_late_max_s="
        f"{max(late) if late else 0.0:.6f} generator_late_mean_s="
        f"{(sum(late) / len(late)) if late else 0.0:.6f}")
    mem = devices[0].memory_stats() or {}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    run = Run(args.workload, cell.traffic, setup_s, w["t_end"] - w["t0"],
              w["latencies"], w["n_delivered"], w["compiles"], w["stats"],
              h.broker.bank.n_words, kind, peaks)
    breakdown = None
    if trace_dir is not None:
        import trace_reduce

        t = time.perf_counter()
        run.trace = trace_reduce.load(trace_dir, SPANS)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()
        log(f"phase=trace wall_s={time.perf_counter() - t:.3f} "
            f"busy_s={run.trace.busy_s} window_s={run.trace.window_s}")

    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    if not args.rehearse:
        for m in wanted:
            v = read_metric(m["name"], run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    t = time.perf_counter()
    h.syncs.close()
    checks = h.compare()
    log(f"phase=reference wall_s={time.perf_counter() - t:.3f} "
        f"subscribers_compared={len(h.compared)} "
        f"fires_compared={sum(len(f) for f in h.fires.values())} "
        f"reference_rows={json.dumps(h.rows_compared, separators=(',', ':'))}")
    correct = all(v <= lim for v, lim in checks.values())
    failed = w["attempted"] - w["n_delivered"]
    for name, (v, lim) in checks.items():
        print(f"check {name}={v} limit={lim}", file=sys.stderr)
    out = {"correct": correct, "attempted": w["attempted"], "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
