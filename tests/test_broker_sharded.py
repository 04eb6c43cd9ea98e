"""Device-sharded cohort routing == the single-device broker, bit for bit.

Three brokers consume identical streams (same dictionary insertion order,
same churn schedule):

  * single  — no mesh (the PR 3 broker),
  * placed  — cohorts placed on mesh devices per ``CohortPlacement``; the
              frontier pass dispatches cohort calls grouped by device,
  * sharded — every cohort pass runs inside shard_map over the mesh
              (hash-partitioned τ shards, all_to_all-routed probes,
              block-gather-stitched bank words).

All per-subscriber outputs and all replica state (τ, ρ) must be
bit-identical across the three, and the eager subscribers additionally
match the seed per-interest engine (``InterestSubscription.apply``) on
every changeset.  The golden test runs in a subprocess with 8 forced host
devices; the hypothesis property randomizes the placement policy and the
churn order and runs in-process on a >= 4-device host mesh (CI provides it
via ``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def test_cohort_placement_policies():
    """Host-side placement logic: sticky, balanced, pinned."""
    from repro.core import CohortPlacement

    rr = CohortPlacement()
    assert [rr.assign(f"c{i}", 4, 3) for i in range(5)] == [0, 1, 2, 0, 1]
    assert rr.assign("c0", 4, 3) == 0  # sticky across calls

    lb = CohortPlacement(mode="load_balanced")
    assert lb.assign("big", 16, 2) == 0
    assert lb.assign("s1", 2, 2) == 1  # least-loaded device
    assert lb.assign("s2", 2, 2) == 1  # 2 < 16: still device 1
    assert lb.assign("s3", 16, 2) == 1  # 4 < 16
    assert lb.assign("s4", 2, 2) == 0  # now 16 < 20
    assert lb.assign("s1", 8, 2) == 1  # sticky even after growth

    pin = CohortPlacement(mode="pinned", pins={"a": 7}, default=1)
    assert pin.assign("a", 4, 4) == 3  # 7 % 4
    assert pin.assign("b", 4, 4) == 1  # default fallback

    with pytest.raises(ValueError):
        CohortPlacement(mode="nope")


GOLDEN_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import numpy as np

    from repro.core import (
        Broker, CohortPlacement, Dictionary, InterestExpr, IrapEngine,
        PushPolicy, StepCapacities,
    )

    A = "rdf:type"
    CAPS = StepCapacities(n_removed=16, n_added=16, tau=64, rho=64, pulls=32)
    mesh = jax.make_mesh(
        (8,), ("shard",), axis_types=(jax.sharding.AxisType.Auto,))

    EXPRS = [
        InterestExpr.parse("g", "t0",
            bgp=[("?a", A, "c:Athlete"), ("?a", "p:goals", "?v")]),
        InterestExpr.parse("g", "t1",
            bgp=[("?a", A, "c:Team"), ("?a", "p:rank", "?v")]),
        InterestExpr.parse("g", "t2", bgp=[("?a", "p:goals", "?v")]),
        InterestExpr.parse("g", "t3",
            bgp=[("?a", A, "c:Athlete"), ("?a", "p:plays", "?t"),
                 ("?t", "p:rank", "?r")],
            ogp=[("?a", "p:page", "?w")]),
    ]

    def stream(d, n, seed=3):
        rng = np.random.default_rng(seed)
        def rows(k):
            out = set()
            for _ in range(k):
                e = f"e:{rng.integers(0, 12)}"
                kind = rng.integers(0, 6)
                if kind == 0:
                    out.add((e, A, f"c:{['Athlete','Team'][rng.integers(2)]}"))
                elif kind == 1:
                    out.add((e, "p:goals", str(int(rng.integers(0, 30)))))
                elif kind == 2:
                    out.add((e, "p:rank", str(int(rng.integers(0, 5)))))
                elif kind == 3:
                    out.add((e, "p:plays", f"e:{rng.integers(0, 12)}"))
                elif kind == 4:
                    out.add((e, "p:page", f"w{rng.integers(0, 4)}"))
                else:
                    out.add((e, "p:noise", f"o{rng.integers(0, 6)}"))
            return d.encode_triples(sorted(out))
        return [(rows(int(rng.integers(0, 5))), rows(int(rng.integers(1, 8))))
                for _ in range(n)]

    def tau0_of(d):
        return d.encode_triples([
            ("e:1", A, "c:Athlete"), ("e:1", "p:goals", "10"),
            ("e:2", A, "c:Team"), ("e:2", "p:rank", "1"),
            ("e:3", "p:plays", "e:2"),
        ])

    def drive(make_broker):
        # identical dictionary insertion order per run -> identical ids
        d = Dictionary()
        tau0 = tau0_of(d)
        st = stream(d, 8)
        broker = make_broker(d)
        subs = {}
        subs["A"] = broker.subscribe(EXPRS[0], CAPS, initial_target=tau0)
        subs["B"] = broker.subscribe(
            EXPRS[1], CAPS, initial_target=tau0, policy=PushPolicy.every(2))
        subs["C"] = broker.subscribe(
            EXPRS[0], CAPS, initial_target=tau0, share_target=True)
        outs = []
        for i, cs in enumerate(st):
            if i == 3:  # churn mid-stream: one new cohort, one departure
                subs["D"] = broker.subscribe(
                    EXPRS[3], CAPS, initial_target=tau0)
                broker.unsubscribe(subs.pop("B"))
            outs.append([
                None if o is None else o for o in broker.process_changeset(*cs)
            ])
        outs.append(broker.flush())
        state = {
            name: (np.asarray(s.tau.spo), np.asarray(s.rho.spo))
            for name, s in subs.items()
        }
        return outs, state, broker, d, st, tau0

    def flat(outs):
        res = []
        for per_cs in outs:
            for o in per_cs:
                if o is None:
                    res.append(None)
                else:
                    res.append(tuple(
                        np.asarray(getattr(o, f).spo)
                        for f in ("r", "r_i", "r_prime", "a", "a_i")))
        return res

    runs = {
        "single": drive(lambda d: Broker(d)),
        "placed": drive(lambda d: Broker(
            d, mesh=mesh, placement=CohortPlacement(mode="load_balanced"))),
        "sharded": drive(lambda d: Broker(d, mesh=mesh, shard_cohorts=True)),
    }

    base_outs = flat(runs["single"][0])
    base_state = runs["single"][1]
    for name in ("placed", "sharded"):
        got = flat(runs[name][0])
        assert len(got) == len(base_outs), name
        for i, (a, b) in enumerate(zip(base_outs, got)):
            assert (a is None) == (b is None), (name, i)
            if a is None:
                continue
            for fa, fb in zip(a, b):
                assert np.array_equal(fa, fb), (name, i)
        for sub_name, (tau, rho) in runs[name][1].items():
            assert np.array_equal(tau, base_state[sub_name][0]), (name, sub_name)
            assert np.array_equal(rho, base_state[sub_name][1]), (name, sub_name)

    # seed per-interest oracle over the eager subscriber A on every changeset
    d = Dictionary()
    tau0 = tau0_of(d)
    st = stream(d, 8)
    engine = IrapEngine(d)
    ref = engine.register_interest(EXPRS[0], CAPS, initial_target=tau0)
    a_outs = [per_cs[0] for per_cs in runs["sharded"][0][:-1]]
    for i, cs in enumerate(st):
        want = ref.apply(*cs)
        got = a_outs[i]
        for f in ("r", "r_i", "r_prime", "a", "a_i"):
            assert np.array_equal(
                np.asarray(getattr(got, f).spo),
                np.asarray(getattr(want, f).spo)), ("oracle", i, f)

    # placement actually spread the cohorts; sharding spanned the mesh
    placed_devs = {k for k, v in runs["placed"][2].device_passes.items() if v}
    assert len(placed_devs) > 1, runs["placed"][2].device_passes
    assert len(runs["sharded"][2].device_passes) == 8
    print("SHARDED_GOLDEN_OK")
    """
)


@pytest.mark.slow
def test_sharded_equals_single_device_golden():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", GOLDEN_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=1800,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "SHARDED_GOLDEN_OK" in proc.stdout


def _mesh_or_skip(n: int):
    import jax

    if len(jax.devices()) < n:
        pytest.skip(
            f"needs a >= {n}-device host mesh "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=4)"
        )
    return jax.make_mesh(
        (n,), ("shard",), axis_types=(jax.sharding.AxisType.Auto,)
    )


@pytest.mark.slow
def test_placement_and_churn_property():
    """Random placement policy + churn order == single-device, bit for bit."""
    from hypothesis import given, settings, strategies as st_mod

    mesh = _mesh_or_skip(4)

    from repro.core import (
        Broker,
        CohortPlacement,
        Dictionary,
        InterestExpr,
        PushPolicy,
        StepCapacities,
    )

    A = "rdf:type"
    caps = StepCapacities(n_removed=16, n_added=16, tau=64, rho=64, pulls=32)
    exprs = [
        InterestExpr.parse(
            "g", "t0", bgp=[("?a", A, "c:Athlete"), ("?a", "p:goals", "?v")]
        ),
        InterestExpr.parse(
            "g", "t1", bgp=[("?a", A, "c:Team"), ("?a", "p:rank", "?v")]
        ),
        InterestExpr.parse("g", "t2", bgp=[("?a", "p:goals", "?v")]),
    ]

    def rows_of(rng, d, k):
        out = set()
        for _ in range(k):
            e = f"e:{rng.integers(0, 9)}"
            kind = rng.integers(0, 4)
            if kind == 0:
                out.add((e, A, f"c:{['Athlete', 'Team'][rng.integers(2)]}"))
            elif kind == 1:
                out.add((e, "p:goals", str(int(rng.integers(0, 20)))))
            elif kind == 2:
                out.add((e, "p:rank", str(int(rng.integers(0, 4)))))
            else:
                out.add((e, "p:noise", f"o{rng.integers(0, 4)}"))
        return d.encode_triples(sorted(out))

    def drive(mode, churn_order, seed, shard: bool, use_mesh: bool):
        d = Dictionary()
        tau0 = d.encode_triples(
            [("e:1", A, "c:Athlete"), ("e:1", "p:goals", "3")]
        )
        rng = np.random.default_rng(seed)
        if use_mesh:
            broker = Broker(
                d,
                mesh=mesh,
                shard_cohorts=shard,
                placement=CohortPlacement(mode=mode),
            )
        else:
            broker = Broker(d)
        live = []
        collected = []
        for step_no, action in enumerate(churn_order):
            if action == 0 or not live:  # subscribe
                expr = exprs[step_no % len(exprs)]
                live.append(
                    broker.subscribe(
                        expr,
                        caps,
                        initial_target=tau0,
                        policy=PushPolicy.every(1 + step_no % 2),
                    )
                )
            else:  # unsubscribe the oldest
                broker.unsubscribe(live.pop(0))
            outs = broker.process_changeset(
                rows_of(rng, d, int(rng.integers(0, 4))),
                rows_of(rng, d, int(rng.integers(1, 6))),
            )
            collected.append(outs)
        collected.append(broker.flush())
        state = [
            (np.asarray(s.tau.spo), np.asarray(s.rho.spo)) for s in live
        ]
        return collected, state

    @settings(max_examples=4, deadline=None)
    @given(
        mode=st_mod.sampled_from(["round_robin", "load_balanced", "pinned"]),
        churn_order=st_mod.lists(
            st_mod.integers(min_value=0, max_value=1), min_size=3, max_size=6
        ),
        seed=st_mod.integers(min_value=0, max_value=2**16),
        shard=st_mod.booleans(),
    )
    def check(mode, churn_order, seed, shard):
        base_outs, base_state = drive(mode, churn_order, seed, shard, False)
        mesh_outs, mesh_state = drive(mode, churn_order, seed, shard, True)
        assert len(base_outs) == len(mesh_outs)
        for per_a, per_b in zip(base_outs, mesh_outs):
            assert len(per_a) == len(per_b)
            for a, b in zip(per_a, per_b):
                assert (a is None) == (b is None)
                if a is None:
                    continue
                for f in ("r", "r_i", "r_prime", "a", "a_i"):
                    assert np.array_equal(
                        np.asarray(getattr(a, f).spo),
                        np.asarray(getattr(b, f).spo),
                    )
        for (t_a, r_a), (t_b, r_b) in zip(base_state, mesh_state):
            assert np.array_equal(t_a, t_b)
            assert np.array_equal(r_a, r_b)

    check()


def test_or_reduce_words_reassembly():
    """The uint32 branch of make_or_reduce: shards holding masked (and here
    deliberately OVERLAPPING) subsets of a lane-bit words tensor reassemble
    the full tensor exactly — the OR fold is idempotent where subsets
    overlap, which the broker's disjoint block-stitching cannot cover."""
    mesh = _mesh_or_skip(4)
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.distributed import make_or_reduce
    from repro.core.triples import PAD
    from repro.kernels import ops as kops

    n = 4
    rng = np.random.default_rng(0)
    spo = jnp.asarray(rng.integers(0, 40, (32, 3)).astype(np.int32))
    bank = jnp.asarray(
        np.array(
            [[-1, 7, -1], [5, -1, -1], [-1, -1, 3], [2, 9, -1]], np.int32
        )
    )
    or_reduce = make_or_reduce("shard")

    def body(spo_in, bank_in):
        my = jax.lax.axis_index("shard")
        idx = jnp.arange(spo_in.shape[0])
        # each row is owned by TWO shards: overlap that OR absorbs exactly
        mine = (idx % n == my) | (idx % n == (my + 1) % n)
        masked = jnp.where(mine[:, None], spo_in, PAD)
        words = or_reduce(
            kops.pattern_bitmask_words(masked, bank_in).astype(jnp.uint32)
        )
        covered = or_reduce(mine)  # bool branch: union of coverage
        return words[None], covered[None]

    fn = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(P(), P()),
            out_specs=(P("shard"), P("shard")), check_vma=False,
        )
    )
    words_sh, covered_sh = fn(spo, bank)
    want = np.asarray(kops.pattern_bitmask_words(spo, bank))
    for i in range(n):  # every shard reconstructed the full words tensor
        assert np.array_equal(np.asarray(words_sh[i]), want), i
    assert np.asarray(covered_sh).all()
