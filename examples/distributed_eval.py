"""Distributed interest evaluation demo: shard_map semijoin over 8 devices.

Forces 8 host devices (must run as its own process) and evaluates the
Football interest over hash-partitioned changeset/target shards, with
all_to_all-routed candidate-assertion probes (DESIGN.md §3).

    PYTHONPATH=src python examples/distributed_eval.py
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import FOOTBALL, default_generator
from repro.compile_cache import enable_compile_cache
from repro.core.distributed import (
    gather_result_sets,
    make_distributed_evaluator,
    partition_rows,
    prepare_target_shards,
)
from repro.core.interest import compile_interest


def main():
    enable_compile_cache()
    n_shards = 8
    mesh = jax.make_mesh(
        (n_shards,), ("data",), axis_types=(jax.sharding.AxisType.Auto,)
    )
    gen = default_generator(seed=5, scale=0.5)
    gen.initial_dump()
    tau_rows = gen.slice_for(
        lambda t: t[0].startswith(("dbr:Athlete", "dbr:Team")))
    plan = compile_interest(FOOTBALL, gen.dict)

    m_cap, t_cap = 1024, 4096
    ev = make_distributed_evaluator(
        plan, mesh, id_capacity=gen.dict.id_capacity, fanout=8,
        out_capacity=2048, pull_capacity=8192,
    )
    spo_sh, ops_sh, tau_ovf = prepare_target_shards(tau_rows, n_shards, t_cap)

    for i in range(3):
        d_np, a_np = gen.changeset()
        m_sh, m_ovf = partition_rows(a_np, n_shards, key_col=0, cap=m_cap)
        t0 = time.perf_counter()
        res = ev(jnp.asarray(m_sh), jnp.asarray(spo_sh), jnp.asarray(ops_sh))
        jax.block_until_ready(res.interesting.spo)
        dt = time.perf_counter() - t0
        inter, pot, pulls, overflow = gather_result_sets(
            res, partition_overflow=m_ovf | tau_ovf
        )
        per_shard = [int(x) for x in np.asarray(res.interesting.n)]
        print(
            f"[changeset {i+1}] adds={a_np.shape[0]} -> interesting={len(inter)} "
            f"potential={len(pot)} pulls={len(pulls)} overflow={overflow} "
            f"in {dt*1e3:.0f} ms (per-shard interesting: {per_shard})"
        )
    print("\n8-way shard_map evaluation with all_to_all-routed probes: OK")


if __name__ == "__main__":
    main()
