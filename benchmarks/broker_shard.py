"""Sharded vs single-device broker flush throughput on a 4-device mesh.

Drives identical deferred workloads — ``n_subs`` subscribers over several
shape cohorts, half flushed early so every full flush drains TWO distinct
consumption frontiers — through three brokers:

  * single  — no mesh (the PR 3 device-resident broker),
  * placed  — ``Broker(mesh=...)``: cohorts placed on mesh devices
              (``CohortPlacement`` round-robin), frontier passes dispatched
              grouped by device so cohorts run concurrently,
  * sharded — ``Broker(mesh=..., shard_cohorts=True)``: every cohort pass
              inside shard_map (hash-partitioned τ shards, all_to_all-routed
              probes, block-gather-stitched bank words).

Before timing, one warm round asserts all three paths' flush outputs
bit-identical to each other AND to eager evaluation of the same composed
batches by the seed per-interest engine. Reported: flush seconds per round
(compile time excluded via ``BrokerStats.rejit_s``), cohort passes per
device (``Broker.device_passes``), and sharded/placed vs single speedups.
Emits ``experiments/bench/BENCH_shard.json``.

The brokers run in this process over the first ``N_DEVICES`` of
``jax.devices()``: one process holds every chip it drives. On the CPU the
caller supplies virtual devices (``XLA_FLAGS=
--xla_force_host_platform_device_count=4`` before the process starts); the
collectives are then emulated and the recorded ratios measure routing
overhead, not speed.

    PYTHONPATH=src python -m benchmarks.run --only shard
"""
from __future__ import annotations

N_DEVICES = 4


def _mesh():
    import jax

    devices = jax.devices()
    if len(devices) < N_DEVICES:
        raise RuntimeError(
            f"broker_shard needs {N_DEVICES} devices for its mesh, found "
            f"{len(devices)} {devices[0].platform} device(s); on the CPU start "
            f"the process with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={N_DEVICES}"
        )
    return jax.make_mesh(
        (N_DEVICES,), ("shard",),
        axis_types=(jax.sharding.AxisType.Auto,),
        devices=devices[:N_DEVICES],
    )


def run(scale: float = 1.0, n_subs: int = 12, n_rounds: int = 4,
        per_round: int = 3) -> str:
    from repro.core import (
        Broker,
        CohortPlacement,
        Dictionary,
        IrapEngine,
        PushPolicy,
    )
    from .broker_flush import (
        _assert_outputs_equal,
        _caps,
        _composed,
        _interest,
        _stream,
    )

    from .common import csv_row, save_json

    mesh = _mesh()

    def build(name: str):
        d = Dictionary()
        stream = _stream(d, 2 * per_round * (n_rounds + 1), seed=0)
        if name == "single":
            broker = Broker(d)
        elif name == "placed":
            broker = Broker(
                d, mesh=mesh, placement=CohortPlacement(mode="round_robin")
            )
        else:
            broker = Broker(d, mesh=mesh, shard_cohorts=True)
        policy = PushPolicy.max_staleness(1e9)  # only explicit flush fires
        subs = [
            broker.subscribe(_interest(i), _caps(), policy=policy)
            for i in range(n_subs)
        ]
        return broker, subs, stream

    brokers = {name: build(name) for name in ("single", "placed", "sharded")}

    # -- warm + parity round: all paths vs eager composed-batch evaluation
    flushed = {}
    for name, (broker, subs, stream) in brokers.items():
        for cs in stream[: 2 * per_round]:
            broker.process_changeset(*cs)
        flushed[name] = broker.flush()
    d_ref = Dictionary()
    ref_stream = _stream(d_ref, 2 * per_round, seed=0)
    engine = IrapEngine(d_ref)
    refs = [
        engine.register_interest(_interest(i), _caps())
        for i in range(n_subs)
    ]
    d_np, a_np = _composed(ref_stream)
    for k, ref in enumerate(refs):
        want = ref.apply(d_np, a_np)
        for name in brokers:
            _assert_outputs_equal(flushed[name][k], want, f"{name}/{k}")

    # -- timed rounds (steady state: executables, statics, τ shards cached)
    results = {}
    for name, (broker, subs, stream) in brokers.items():
        half = subs[: len(subs) // 2]
        it = iter(stream[2 * per_round :])
        warm_stats = len(broker.stats)
        passes_before = dict(broker.device_passes)
        for _ in range(n_rounds):
            for _ in range(per_round):
                broker.process_changeset(*next(it))
            broker.flush(subs=half)
            for _ in range(per_round):
                broker.process_changeset(*next(it))
            broker.flush()
        flush_stats = [
            st for st in broker.stats[warm_stats:] if st.total_added == 0
        ]
        eval_s = sum(st.elapsed_s - st.rejit_s for st in flush_stats)
        results[name] = {
            "n_flushes": len(flush_stats),
            "flush_eval_s": eval_s,
            "flush_eval_s_per_round": eval_s / max(1, n_rounds),
            "cohort_passes": sum(st.n_cohort_passes for st in flush_stats),
            "rejit_s": sum(st.rejit_s for st in broker.stats[warm_stats:]),
            "device_passes": {
                str(dev): n - passes_before.get(dev, 0)
                for dev, n in sorted(broker.device_passes.items())
            },
            "n_subscribers": n_subs,
            "changesets_per_round": 2 * per_round,
        }

    single_s = results["single"]["flush_eval_s"]
    payload = {
        "n_devices": N_DEVICES,
        "single_device": results["single"],
        "placed": results["placed"],
        "sharded": results["sharded"],
        "sharded_vs_single_speedup": single_s
        / max(1e-9, results["sharded"]["flush_eval_s"]),
        "placed_vs_single_speedup": single_s
        / max(1e-9, results["placed"]["flush_eval_s"]),
        "parity": {
            "bit_identical_to_single_device": True,
            "checked_against_eager_composed_batches": True,
            "subscribers_checked": n_subs,
        },
        "scale": scale,
    }
    save_json("BENCH_shard", payload)
    us = payload["sharded"]["flush_eval_s_per_round"] * 1e6
    return csv_row(
        "broker_shard",
        us,
        f"shard_x={payload['sharded_vs_single_speedup']:.2f};"
        f"placed_x={payload['placed_vs_single_speedup']:.2f};"
        f"devs={N_DEVICES};subs={payload['sharded']['n_subscribers']}",
    )


if __name__ == "__main__":
    print(run())
