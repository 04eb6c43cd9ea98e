"""Streaming subscription driver: two interests over a synthetic DBpedia-Live.

Maintains the Football and Location replicas against a live changeset stream
and prints per-changeset propagation stats (the iRap architecture of paper
§3: Interest Manager + Changeset Manager + Interest Evaluator loop).

    PYTHONPATH=src python examples/subscribe_replica.py --days 3
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from repro.compile_cache import enable_compile_cache
from repro.core import IrapEngine

from benchmarks.common import (
    FOOTBALL,
    LOCATION,
    default_generator,
    football_caps,
    location_caps,
)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--days", type=int, default=3)
    ap.add_argument("--per-day", type=int, default=3)
    ap.add_argument("--scale", type=float, default=0.5)
    args = ap.parse_args()

    gen = default_generator(seed=7, scale=args.scale)
    gen.initial_dump()
    engine = IrapEngine(gen.dict)
    fb = engine.register_interest(
        FOOTBALL, football_caps(),
        initial_target=gen.slice_for(
            lambda t: t[0].startswith(("dbr:Athlete", "dbr:Team"))),
    )
    loc = engine.register_interest(
        LOCATION, location_caps(), initial_target=gen.slice_for(lambda t: True)
    )
    print(f"source: {len(gen.current)} triples | football τ0={int(fb.tau.n)} "
          f"| location τ0={int(loc.tau.n)}")

    cs_id = 0
    for day in range(args.days):
        for _ in range(args.per_day):
            cs_id += 1
            d_np, a_np = gen.changeset()
            stats = engine.process_changeset(d_np, a_np)
            f, l = stats
            print(
                f"[day {day+1} cs {cs_id}] Δ=({d_np.shape[0]}-,{a_np.shape[0]}+) | "
                f"football: r={f.interesting_removed} a={f.interesting_added} "
                f"ρ={f.potential_size} τ={f.target_size} ({f.elapsed_s*1e3:.0f} ms) | "
                f"location: r={l.interesting_removed} a={l.interesting_added} "
                f"ρ={l.potential_size} τ={l.target_size} ({l.elapsed_s*1e3:.0f} ms)"
            )
    print("\nfinal sizes:",
          f"source={len(gen.current)} football_tau={int(fb.tau.n)}",
          f"location_tau={int(loc.tau.n)}")


if __name__ == "__main__":
    main()
