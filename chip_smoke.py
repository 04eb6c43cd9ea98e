"""Bring-up smoke run: the broker's served path on a TPU v5e, end to end.

Drives ``Broker.subscribe`` -> ``process_changeset`` -> ``flush`` through the
public ``repro.core`` API, in one process, at the state a replica deployment
holds on one chip: a DBpedia-Live-like source of ~765k triples (the
``benchmarks/common.py`` class structure at 50x its entity counts), the
paper's Football and Location interests with 2^20-row replicas seeded from
their slices of the dump, and 62 class-star interests with 2^16-row
replicas, on mixed push cadences. It then checks, and fails on any miss:

* parity: every fire of the two paper interests equals the seed
  per-interest engine (``IrapEngine``) run with the XLA reference matcher
  (``kernels.ref.pattern_bitmask_ref``, no Pallas) on the same composed
  changesets, and so do their final replicas;
* no fallback: no fire degraded to the seed path and no subscriber's
  capacities grew (no capacity overflow);
* device: JAX runs on a TPU and a cached cohort executable carries the
  Pallas kernels (``tpu_custom_call``), so the fused kernels ran.

    python chip_smoke.py                # one TPU chip
    python chip_smoke.py --chips 4      # four chips: single == placed == sharded
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # CPU rehearsal, small size

Without a TPU, and without ``--tiny``, it exits non-zero and prints no
result. Each phase prints one line (wall time, compile time, rows
delivered); the last line of a passing run is one JSON object naming the
device. Timings it prints are bring-up observations, not benchmarks.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from benchmarks.common import FOOTBALL, LOCATION  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    Broker,
    ChangesetBatch,
    CohortPlacement,
    InterestExpr,
    IrapEngine,
    PushPolicy,
    StepCapacities,
    to_numpy,
)
from repro.data import DBpediaLikeGenerator, GeneratorConfig  # noqa: E402
from repro.kernels import ref  # noqa: E402

OUT_FIELDS = ("r", "r_i", "r_prime", "a", "a_i")
CLASSES = ("foaf:Person", "dbo:Work", "dbo:Place", "dbo:SoccerPlayer")


@dataclasses.dataclass(frozen=True)
class Size:
    scale: int  # multiple of benchmarks/common.py's entity counts
    n_changesets: int
    removes: int  # rows removed / added per changeset
    adds: int
    paper_tau: int
    class_tau: int
    n_class: int

    def window(self, rows: int) -> int:
        """Changeset capacity that holds the whole stream composed (the
        flush window), so no capacity ever grows."""
        return 1 << (int(1.2 * rows * self.n_changesets) - 1).bit_length()


FULL = Size(scale=50, n_changesets=6, removes=250, adds=500,
            paper_tau=1 << 20, class_tau=1 << 16, n_class=62)
TINY = Size(scale=1, n_changesets=6, removes=250, adds=500,
            paper_tau=1 << 14, class_tau=1 << 10, n_class=6)
# the four-chip comparison proves the mesh paths on real chips, so it is
# sized for compile time, not state: every cohort program there compiles
# three times (single, placed, sharded)
FOUR = Size(scale=1, n_changesets=4, removes=50, adds=100,
            paper_tau=1 << 12, class_tau=1 << 12, n_class=6)


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def phase(name: str, t0: float, **fields) -> None:
    extra = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"phase={name} wall_s={time.perf_counter() - t0:.3f} {extra}",
          flush=True)


def class_interest(i: int) -> InterestExpr:
    """A two-pattern class star: one entity class plus one constant-valued
    property. The class pattern refines Location's ``?x rdf:type ?t`` row
    (a virtual lattice lane); the property constants are distinct, so the
    real bank spans several 32-lane words."""
    return InterestExpr.parse(
        source="synthetic://dbpedia-live",
        target=f"local://class{i}",
        bgp=[
            ("?e", "rdf:type", CLASSES[i % len(CLASSES)]),
            ("?e", f"dbp:prop{i % 4}", str(i)),
        ],
    )


def name_interest(i: int) -> InterestExpr:
    """A class star with a variable object: a second cohort shape."""
    return InterestExpr.parse(
        source="synthetic://dbpedia-live",
        target=f"local://names{i}",
        bgp=[
            ("?e", "rdf:type", CLASSES[i % len(CLASSES)]),
            ("?e", "foaf:name", "?n"),
        ],
    )


def paper_caps(size: Size, dedup: int) -> StepCapacities:
    return StepCapacities(
        n_removed=size.window(size.removes), n_added=size.window(size.adds),
        tau=size.paper_tau, rho=min(1 << 16, size.paper_tau),
        pulls=min(1 << 14, size.paper_tau), fanout=8, dedup_candidates=dedup,
    )


def class_caps(size: Size, dedup: int) -> StepCapacities:
    return StepCapacities(
        n_removed=size.window(size.removes), n_added=size.window(size.adds),
        tau=size.class_tau, rho=min(1 << 14, size.class_tau),
        pulls=min(1 << 12, size.class_tau), fanout=8, dedup_candidates=dedup,
    )


def build_source(size: Size, seed: int):
    """The dump, the two paper slices and the changesets, all encoded into
    one dictionary before any subscriber compiles (so no dictionary growth
    recompiles mid-stream)."""
    t0 = time.perf_counter()
    gen = DBpediaLikeGenerator(GeneratorConfig(
        n_athletes=300 * size.scale, n_places=500 * size.scale,
        n_other=2500 * size.scale, n_teams=50, seed=seed,
        adds_per_changeset=size.adds, removes_per_changeset=size.removes,
    ))
    dump = gen.initial_dump()
    slices = {
        "football": gen.slice_for(
            lambda t: t[0].startswith(("dbr:Athlete", "dbr:Team"))),
        "location": gen.slice_for(lambda t: t[0].startswith("dbr:Place")),
    }
    stream = list(gen.stream(size.n_changesets))
    phase(
        "source", t0, compile_s=0.0, rows_delivered=0,
        triples=dump.shape[0], terms=len(gen.dict),
        changesets=len(stream),
        removed=sum(d.shape[0] for d, _ in stream),
        added=sum(a.shape[0] for _, a in stream),
    )
    return gen.dict, slices, stream


def subscribe_all(broker: Broker, size: Size, slices, dedup: int):
    """Football every 2 changesets, Location bounded by staleness (drained
    by the final flush), the class stars on the priority lane, eager or
    every 2; returns {name: subscription}. Every fire then evaluates one
    or two frontiers, which keeps the compiled cohort programs to four."""
    subs = {
        "football": broker.subscribe(
            FOOTBALL, paper_caps(size, dedup),
            initial_target=slices["football"],
            policy=PushPolicy.every(2)),
        "location": broker.subscribe(
            LOCATION, paper_caps(size, dedup),
            initial_target=slices["location"],
            policy=PushPolicy.max_staleness(3600.0)),
    }
    cadences = (PushPolicy.priority_lane(), PushPolicy(), PushPolicy.every(2))
    for i in range(size.n_class):
        subs[f"class{i}"] = broker.subscribe(
            class_interest(i), class_caps(size, dedup),
            policy=cadences[i % len(cadences)])
    return subs


def rows_of(outs) -> int:
    return sum(int(o.r.n) + int(o.a.n) for o in outs if o is not None)


def drive(broker: Broker, subs, stream):
    """The stream then a final flush. Returns, per subscriber name, the
    list of (first, last) changeset windows it fired on with its output."""
    names = list(subs)
    fires = {name: [] for name in names}
    since = {name: 0 for name in names}
    n_stats = len(broker.stats)
    t0 = time.perf_counter()
    delivered = 0
    for t, cs in enumerate(stream):
        t1 = time.perf_counter()
        outs = broker.process_changeset(*cs)
        rows = rows_of(outs)
        delivered += rows
        for name, out in zip(names, outs):
            if out is not None:
                fires[name].append(((since[name], t), out))
                since[name] = t + 1
        st = broker.stats[-1]
        print(f"  changeset {t + 1}: wall_s={time.perf_counter() - t1:.3f} "
              f"compile_s={st.rejit_s:.3f} fired={st.n_evaluated} "
              f"rows_delivered={rows}", flush=True)
    compile_s = sum(st.rejit_s for st in broker.stats[n_stats:])
    phase("stream", t0, compile_s=f"{compile_s:.3f}",
          rows_delivered=delivered, changesets=len(stream),
          cohort_passes=sum(
              st.n_cohort_passes for st in broker.stats[n_stats:]))
    n_stats = len(broker.stats)
    t0 = time.perf_counter()
    outs = broker.flush()
    for name, out in zip(names, outs):
        if out is not None:
            fires[name].append(((since[name], len(stream) - 1), out))
    compile_s = sum(st.rejit_s for st in broker.stats[n_stats:])
    phase("flush", t0, compile_s=f"{compile_s:.3f}",
          rows_delivered=rows_of(outs),
          drained=sum(o is not None for o in outs))
    return fires


def composed(stream, first: int, last: int):
    batch = ChangesetBatch.fresh(*stream[first], first + 1)
    for t in range(first + 1, last + 1):
        batch.extend(*stream[t], t + 1)
    return batch.arrays()


def same_outputs(a, b) -> bool:
    return all(
        (to_numpy(getattr(a, f)) == to_numpy(getattr(b, f))).all()
        and to_numpy(getattr(a, f)).shape == to_numpy(getattr(b, f)).shape
        for f in OUT_FIELDS
    )


def same_state(a, b) -> bool:
    return all(
        to_numpy(x).shape == to_numpy(y).shape
        and (to_numpy(x) == to_numpy(y)).all()
        for x, y in ((a.tau, b.tau), (a.rho, b.rho))
    )


def check_parity(dictionary, size, slices, stream, subs, fires) -> None:
    """Both paper interests against the seed engine on the XLA reference
    matcher, window by window, then their final τ/ρ. The engine runs on
    the host's CPU backend: a reference that shares neither the Pallas
    kernels nor the TPU compiler with the broker."""
    t0 = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        engine = IrapEngine(dictionary)
        ref_subs = {
            name: engine.register_interest(
                expr, subs[name].caps, initial_target=slices[name],
                matcher=ref.pattern_bitmask_ref)
            for name, expr in (("football", FOOTBALL), ("location", LOCATION))
        }
        n_fires = 0
        for name, ref_sub in ref_subs.items():
            check(fires[name], f"{name} never fired")
            for (first, last), out in fires[name]:
                want = ref_sub.apply(*composed(stream, first, last))
                check(same_outputs(out, want),
                      f"{name}: broker != IrapEngine on changesets "
                      f"{first + 1}..{last + 1}")
                n_fires += 1
            check(same_state(subs[name], ref_sub),
                  f"{name}: final replica differs from IrapEngine's")
    phase("parity", t0, compile_s="n/a", rows_delivered=0,
          fires_checked=n_fires, broker_eq_engine=True)


def check_no_fallback(broker: Broker, subs, caps0, fires) -> None:
    t0 = time.perf_counter()
    grown = [name for name, s in subs.items() if s.caps != caps0[name]]
    overflowed = [
        name for name, fl in fires.items()
        if any(bool(out.overflow) for _, out in fl)
    ]
    check(broker.degraded_fires == 0,
          f"{broker.degraded_fires} fire(s) degraded to the seed path")
    check(not grown, f"capacities grew (overflow) for {grown}")
    check(not overflowed, f"overflowed outputs for {overflowed}")
    phase("no_fallback", t0, compile_s="n/a", rows_delivered=0,
          degraded_fires=broker.degraded_fires, caps_grown=0)


def check_device(broker: Broker, on_tpu: bool) -> None:
    t0 = time.perf_counter()
    # the broker's cached executables are the compiled cohort steps
    cohort_exes = [
        fn for key, fn in broker._exec_cache.items()
        if str(key[0]).startswith("cohort")
    ]
    check(cohort_exes, "no cohort executable was compiled")
    with_kernel = sum("tpu_custom_call" in fn.as_text() for fn in cohort_exes)
    if on_tpu:
        check(jax.default_backend() == "tpu", "JAX backend is not tpu")
        check(with_kernel > 0, "no cohort executable carries a Pallas kernel")
    phase("device", t0, compile_s="n/a", rows_delivered=0,
          backend=jax.default_backend(),
          cohort_executables=len(cohort_exes),
          with_tpu_custom_call=with_kernel,
          bank_words=broker.bank.n_words, real_lanes=broker.bank.n_real,
          virtual_lanes=broker.bank.n_virtual)


def one_chip(size: Size, seed: int, on_tpu: bool) -> None:
    dictionary, slices, stream = build_source(size, seed)
    t0 = time.perf_counter()
    broker = Broker(dictionary)
    subs = subscribe_all(broker, size, slices, dedup=4096)
    caps0 = {name: s.caps for name, s in subs.items()}
    check(broker.bank.n_virtual > 0, "the lattice has no virtual lanes")
    check(broker.bank.n_words > 1, "the bank fits one 32-lane word")
    phase("subscribe", t0, compile_s="0.000", rows_delivered=0,
          subscribers=len(subs), bank_words=broker.bank.n_words,
          virtual_lanes=broker.bank.n_virtual)
    fires = drive(broker, subs, stream)
    check_parity(dictionary, size, slices, stream, subs, fires)
    check_no_fallback(broker, subs, caps0, fires)
    check_device(broker, on_tpu)


def four_chip(seed: int) -> None:
    """Single-device, placed and sharded brokers on one stream over a
    four-device mesh, each bit-identical to the single-device run: two
    cohort shapes (six constant-valued class stars, four name stars), all
    eager. The sharded path takes no candidate dedup, so no broker here
    does, and the paper interests stay out: without dedup, Football's
    edge pattern needs probe pools beyond a chip's HBM."""
    size = FOUR
    dictionary, _, stream = build_source(size, seed)
    caps = StepCapacities(
        n_removed=size.window(size.removes), n_added=size.window(size.adds),
        tau=size.class_tau, rho=1 << 10, pulls=1 << 10, fanout=4,
    )
    exprs = {f"class{i}": class_interest(i) for i in range(size.n_class)}
    exprs.update({f"names{i}": name_interest(i) for i in range(4)})
    mesh = jax.make_mesh((4,), ("shard",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:4])
    runs = {}
    for mode in ("single", "placed", "sharded"):
        t0 = time.perf_counter()
        if mode == "single":
            broker = Broker(dictionary)
        elif mode == "placed":
            broker = Broker(dictionary, mesh=mesh,
                            placement=CohortPlacement(mode="load_balanced"))
        else:
            broker = Broker(dictionary, mesh=mesh, shard_cohorts=True)
        subs = {name: broker.subscribe(expr, caps)
                for name, expr in exprs.items()}
        print(f"mode={mode}", flush=True)
        fires = drive(broker, subs, stream)
        check(broker.degraded_fires == 0, f"{mode}: degraded fires")
        runs[mode] = (subs, fires)
        phase(f"four_chip_{mode}", t0, compile_s="see stream/flush",
              rows_delivered=sum(
                  int(o.r.n) + int(o.a.n)
                  for fl in fires.values() for _, o in fl),
              device_passes=dict(sorted(broker.device_passes.items())))
    t0 = time.perf_counter()
    base_subs, base_fires = runs["single"]
    for mode in ("placed", "sharded"):
        subs, fires = runs[mode]
        for name in base_subs:
            check(len(fires[name]) == len(base_fires[name]),
                  f"{mode}/{name}: fire count differs")
            for (w0, o0), (w1, o1) in zip(base_fires[name], fires[name]):
                check(w0 == w1 and same_outputs(o0, o1),
                      f"{mode}/{name}: outputs differ on window {w0}")
            check(same_state(base_subs[name], subs[name]),
                  f"{mode}/{name}: replica differs")
    phase("four_chip_parity", t0, compile_s="n/a", rows_delivered=0,
          single_eq_placed_eq_sharded=True, subscribers=len(base_subs))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal of every phase at a small size")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if args.tiny:
        if platform == "tpu":
            print("chip_smoke: --tiny is the CPU rehearsal; run without it "
                  "on a TPU", file=sys.stderr)
            return 2
    elif platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {platform!r}); the "
              "CPU rehearsal is JAX_PLATFORMS=cpu python chip_smoke.py "
              "--tiny", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    kind = devices[0].device_kind
    print(f"device platform={platform} kind={kind!r} count={len(devices)} "
          f"jax={jax.__version__} compile_cache={cache_dir}", flush=True)

    if args.chips == 4:
        four_chip(args.seed)
    else:
        one_chip(TINY if args.tiny else FULL, args.seed, on_tpu=not args.tiny)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
