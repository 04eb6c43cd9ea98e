# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one entry per paper table/figure (+ kernel micro).

    PYTHONPATH=src python -m benchmarks.run [--scale 1.0] [--days 5]

Outputs ``name,us_per_call,derived`` CSV rows on stdout and one JSON per
benchmark under experiments/bench/.
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--days", type=int, default=5)
    ap.add_argument("--per-day", type=int, default=3)
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    from . import broker_churn, broker_fanout, broker_flush
    from . import broker_journal, broker_scaling, broker_shard
    from . import fig4_growth, kernels_micro
    from . import table1_changesets
    from . import table23_interest_eval as t23

    benches = {
        "table1": lambda: table1_changesets.run(args.days, args.per_day, args.scale),
        "table2_football": lambda: t23.run_football(args.days, args.per_day, args.scale),
        "table3_location": lambda: t23.run_location(args.days, args.per_day, args.scale),
        "fig4_growth": lambda: fig4_growth.run(args.days, args.per_day, args.scale),
        "kernel_triple_match": kernels_micro.run_triple_match,
        "broker_scaling": lambda: broker_scaling.run(args.scale),
        "broker_churn": lambda: broker_churn.run(args.scale),
        "broker_flush": lambda: broker_flush.run(args.scale),
        "broker_fanout": lambda: broker_fanout.run(args.scale),
        "broker_shard": lambda: broker_shard.run(args.scale),
        "broker_journal": lambda: broker_journal.run(args.scale),
    }
    print("name,us_per_call,derived")
    failures = []
    for name, fn in benches.items():
        if args.only and args.only not in name:
            continue
        try:
            print(fn(), flush=True)
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            print(f"{name},NaN,ERROR:{e!r}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
