"""Multi-subscriber broker demo: many interests, cohort-cached fused passes.

Registers several subscribers (the paper-shaped Football interest plus a
family of class-star interests) against one synthetic DBpedia-Live stream —
contrast with examples/subscribe_replica.py, which drives the per-interest
engine. Subscribers carry different PushPolicy cadences (an eager priority
lane, every-k batchers, a staleness-bounded replica), mid-stream churn
(unsubscribe + re-subscribe) shows the cohort executable cache absorbing
membership changes without global re-jits, and a final flush() drains every
deferred batch.

    PYTHONPATH=src python examples/multi_subscriber.py --days 3 --subscribers 6
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from repro.compile_cache import enable_compile_cache
from repro.core import Broker, InterestExpr, PushPolicy, StepCapacities

from benchmarks.common import FOOTBALL, default_generator, football_caps


def class_interest(i: int) -> InterestExpr:
    """Subscriber i mirrors one entity class + its names (same plan shape
    for every i, so the broker evaluates all of them as one vmapped cohort)."""
    cls = ["dbo:SoccerPlayer", "dbo:Place", "dbo:Person"][i % 3]
    return InterestExpr.parse(
        source="synthetic://dbpedia-live",
        target=f"local://class{i}",
        bgp=[("?e", "rdf:type", cls), ("?e", "foaf:name", "?name")],
    )


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--days", type=int, default=3)
    ap.add_argument("--per-day", type=int, default=3)
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--subscribers", type=int, default=6)
    args = ap.parse_args()

    gen = default_generator(seed=7, scale=args.scale)
    gen.initial_dump()
    broker = Broker(gen.dict)

    # the paper interest rides a priority lane: evaluated at every changeset,
    # ahead of the batched class subscribers
    broker.subscribe(
        FOOTBALL, football_caps(),
        initial_target=gen.slice_for(
            lambda t: t[0].startswith(("dbr:Athlete", "dbr:Team"))),
        policy=PushPolicy.priority_lane(),
    )
    caps = StepCapacities(
        n_removed=1024, n_added=2048, tau=1 << 14, rho=1 << 13, pulls=1 << 12,
        fanout=8, dedup_candidates=1024,
    )
    policies = [
        PushPolicy(),  # eager default
        PushPolicy.every(2),  # slow consumer: batch 2 changesets per push
        PushPolicy.max_staleness(3600.0),  # mirror: drained by flush() below
    ]
    for i in range(args.subscribers - 1):
        broker.subscribe(class_interest(i), caps, policy=policies[i % 3])

    print(f"source: {len(gen.current)} triples | subscribers: "
          f"{len(broker.subs)}")

    cs_id = 0
    churned = False
    for day in range(args.days):
        for _ in range(args.per_day):
            cs_id += 1
            d_np, a_np = gen.changeset()
            outs = broker.process_changeset(d_np, a_np)
            st = broker.stats[-1]
            per_sub = " ".join(
                f"s{k}:r={int(o.r.n)},a={int(o.a.n)}" if o is not None
                else f"s{k}:…"  # policy deferred: batch keeps accumulating
                for k, o in enumerate(outs)
            )
            print(
                f"[day {day+1} cs {cs_id}] Δ=({d_np.shape[0]}-,{a_np.shape[0]}+) "
                f"bank={st.n_lanes}/{st.n_lanes_raw} lanes "
                f"eval={st.n_evaluated}/{len(broker.subs)} "
                f"({st.elapsed_s*1e3:.0f} ms, {st.rejit_s*1e3:.0f} ms re-jit) "
                f"| {per_sub}"
            )
        if not churned and len(broker.subs) > 2:
            # mid-stream churn: drop one class subscriber, add a fresh one —
            # only the touched cohort can recompile, everyone else reuses
            # cached executables
            compiles_before = broker.rejit_count
            broker.unsubscribe(broker.subs[-1])
            broker.subscribe(
                class_interest(args.subscribers), caps, policy=PushPolicy()
            )
            churned = True
            print(f"  ~ churn: -1/+1 subscriber (compiles so far: "
                  f"{compiles_before}, bank {broker.bank.n_live} live / "
                  f"{broker.bank.n_lanes} lanes)")

    flushed = broker.flush()
    n_drained = sum(1 for o in flushed if o is not None)
    print(f"\nflush(): drained {n_drained} deferred subscriber(s)")
    print("final τ sizes:",
          " ".join(f"s{k}={int(s.tau.n)}" for k, s in enumerate(broker.subs)),
          f"| executable compiles: {broker.rejit_count} "
          f"(cohorts: {sum(broker.cohort_compiles.values())})")


if __name__ == "__main__":
    main()
