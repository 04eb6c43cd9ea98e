"""Distributed (shard_map) interest evaluation == single-device evaluation.

Runs in a subprocess with 8 forced host devices so the main test process
keeps its single-device jax config (the dry-run owns the 512-device setup).
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import numpy as np

    from repro.core import Dictionary, InterestExpr, from_numpy
    from repro.core.distributed import (
        gather_result_sets,
        make_distributed_evaluator,
        partition_rows,
        prepare_target_shards,
    )
    from repro.core.evaluation import build_index, make_side_evaluator
    from repro.core.interest import compile_interest
    from repro.core.triples import PAD

    N_SHARDS = 4
    mesh = jax.make_mesh(
        (N_SHARDS,), ("data",), axis_types=(jax.sharding.AxisType.Auto,)
    )

    d = Dictionary()
    for t in ([f"s{i}" for i in range(12)] + ["type", "p0", "p1", "goals",
              "label", "Athlete"] + [f"o{i}" for i in range(8)]):
        d.encode_term(t)
    R = d.id_capacity

    plans = {
        "star": InterestExpr.parse("g", "t",
            bgp=[("?a", "type", "Athlete"), ("?a", "goals", "?g")],
            ogp=[("?a", "p0", "?h")]),
        "football": InterestExpr.parse("g", "t",
            bgp=[("?f", "type", "Athlete"), ("?f", "p1", "?t"),
                 ("?t", "label", "?n")]),
    }

    SUBJ = [d.lookup(f"s{i}") for i in range(12)]
    PRED = [d.lookup(x) for x in ("type", "p0", "p1", "goals", "label")]
    OBJ = [d.lookup(x) for x in ("Athlete", "o0", "o1")] + SUBJ[:6]

    rng = np.random.default_rng(0)
    M_CAP, T_CAP, K = 32, 64, 8

    def rand_rows(n):
        return np.stack([
            rng.choice(SUBJ, n), rng.choice(PRED, n), rng.choice(OBJ, n)
        ], axis=1).astype(np.int32)

    n_cases = 0
    for name, expr in plans.items():
        plan = compile_interest(expr, d)
        local_ev = make_side_evaluator(
            plan, id_capacity=R, fanout=K, out_capacity=4 * M_CAP,
            pull_capacity=4096)
        dist_ev = make_distributed_evaluator(
            plan, mesh, id_capacity=R, fanout=K,
            out_capacity=4 * M_CAP, pull_capacity=4096)
        for trial in range(6):
            m_rows = np.unique(rand_rows(rng.integers(1, 24)), axis=0)
            tau_rows = np.unique(rand_rows(rng.integers(1, 40)), axis=0)

            m_store = from_numpy(m_rows, M_CAP * N_SHARDS)
            tau_store = from_numpy(tau_rows, T_CAP)
            ref = local_ev(m_store, build_index(tau_store))
            from repro.core import to_set
            want = (to_set(ref.interesting), to_set(ref.potential),
                    to_set(ref.pulls))

            m_sh, m_ovf = partition_rows(m_rows, N_SHARDS, key_col=0, cap=M_CAP)
            spo_sh, ops_sh, t_ovf = prepare_target_shards(
                tau_rows, N_SHARDS, T_CAP)
            assert not m_ovf.any() and not t_ovf.any()
            res = dist_ev(jax.numpy.asarray(m_sh), jax.numpy.asarray(spo_sh),
                          jax.numpy.asarray(ops_sh))
            got = gather_result_sets(res, partition_overflow=m_ovf | t_ovf)
            assert got[0] == want[0], (name, trial, "interesting", got[0], want[0])
            assert got[1] == want[1], (name, trial, "potential")
            assert got[2] == want[2], (name, trial, "pulls")
            assert got[3] == bool(ref.overflow), (name, trial, "overflow")
            n_cases += 1
    print(f"DISTRIBUTED_EQUIVALENCE_OK cases={n_cases}")
    """
)


def test_partition_rows_overflow_flags():
    """Per-shard overflow comes back as flags, never as an exception."""
    np_mod = pytest.importorskip("numpy")
    from repro.core.distributed import partition_rows, prepare_target_shards
    from repro.core.triples import PAD

    rows = np_mod.stack(
        [
            np_mod.arange(8, dtype=np_mod.int32) * 2,  # all even subjects
            np_mod.ones(8, np_mod.int32),
            np_mod.arange(8, dtype=np_mod.int32),
        ],
        axis=1,
    )
    shards, overflow = partition_rows(rows, n_shards=2, key_col=0, cap=4)
    assert overflow.tolist() == [True, False]  # shard 0 got all 8 rows
    assert (shards[0, :, 0] != PAD).sum() == 4  # excess rows dropped, not raised
    assert (shards[1, :, 0] == PAD).all()

    spo, ops, t_ovf = prepare_target_shards(rows, n_shards=2, cap=4)
    assert t_ovf.tolist() == [True, False]
    ok_sh, ok_ovf = partition_rows(rows, n_shards=2, key_col=0, cap=8)
    assert not ok_ovf.any()


@pytest.mark.slow
def test_distributed_equals_local():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=1200,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "DISTRIBUTED_EQUIVALENCE_OK" in proc.stdout
