"""Distributed interest evaluation: shard_map dataflow + cohort placement.

The paper's §6 names a distributed pub/sub architecture as future work; this
module builds both halves of it on jax-native collectives:

**Within one evaluation pass** (the shard_map semijoin dataflow, DESIGN.md
§3, used by :func:`make_distributed_evaluator` and the broker's sharded
cohort step in :mod:`repro.core.broker`):

  * the target dataset is hash-partitioned TWICE: the SPO index by subject
    id, the OPS index by object id — so every bound-slot probe has exactly
    one owner shard (the classic distributed-index layout);
  * changeset rows evaluate locally on their owner shard; candidate-
    assertion probes whose binding lives on another shard are ROUTED via
    ``jax.lax.all_to_all`` (MoE-style bucketed dispatch) and answered by the
    owner.  :func:`make_routed_probe` answers one flat query vector (the
    per-interest evaluator);  :func:`make_routed_probe_batched` is the
    member-axis-aware variant for the broker's vmapped cohort steps: it
    speaks the traced-pattern (``probe_dyn``) hook contract and is written
    so that under ``jax.vmap`` over the cohort member axis every hop still
    lowers to ONE ``all_to_all`` over the flattened (member, binding)
    bucket tensor (jax's collective batching rules fold the member axis
    into the bucket payload);
  * signature tables / edge vectors / bank lane-bit words are OR-reduced
    across shards by :func:`make_or_reduce` — boolean bitsets through
    ``pmax``, uint32 lane-bit *words* through an ``all_gather`` + bitwise-OR
    fold (they are binding- or row-indexed bitsets, so the collective volume
    is independent of target size);
  * per-triple classification and output compaction stay fully local.

**Across cohorts** (the broker's placement layer): :class:`CohortPlacement`
maps whole cohorts — the independently compiled, independently schedulable
units PR 2/3 produced — onto mesh devices (round-robin, load-balanced by
padded member count, or pinned).  ``Broker(mesh=...)`` groups its
frontier-stacked cohort calls by assigned device so the per-cohort
executables run concurrently across the mesh, and
``Broker(mesh=..., shard_cohorts=True)`` instead runs every cohort pass
*inside* shard_map over the whole mesh with the hooks above.

Host-side partitioning (:func:`partition_rows`, :func:`prepare_target_shards`)
reports per-shard overflow through flags — matching the device-side
``SideResult.overflow`` discipline — instead of raising mid-pipeline; the
flags are surfaced by :func:`gather_result_sets`.

The evaluator body is *shared* with the single-device path
(``make_side_evaluator`` distribution hooks), so the semantics are identical
by construction and asserted by the equivalence tests.
"""
from __future__ import annotations

import dataclasses
import itertools
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .evaluation import TripleIndex, SideResult, make_side_evaluator, probe, probe_dyn
from .interest import CompiledInterest
from .triples import PAD, TripleStore, from_array, lex_sort


# ---------------------------------------------------------------------------
# host-side partitioning
# ---------------------------------------------------------------------------

def partition_rows(
    rows: np.ndarray, n_shards: int, key_col: int, cap: int
) -> Tuple[np.ndarray, np.ndarray]:
    """(N, 3) -> (n_shards, cap, 3) hash-partitioned by ``rows[:, key_col]``.

    Returns ``(shards, overflow)`` where ``overflow`` is ``bool[n_shards]``:
    True where a shard received more than ``cap`` rows (the excess rows are
    dropped).  Overflow is a *flag*, not an exception, matching the
    device-side ``SideResult.overflow`` discipline so a pipeline can grow
    capacities between steps instead of dying mid-flight.
    """
    out = np.full((n_shards, cap, 3), PAD, np.int32)
    overflow = np.zeros((n_shards,), bool)
    if rows.size:
        dest = rows[:, key_col] % n_shards
        for s in range(n_shards):
            mine = rows[dest == s]
            if mine.shape[0] > cap:
                overflow[s] = True
                mine = mine[:cap]
            out[s, : mine.shape[0]] = mine
    return out, overflow


def prepare_target_shards(
    tau: np.ndarray, n_shards: int, cap: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(SPO shards by subject, OPS shards by object, overflow) — lex-sorted.

    OPS shards store rows permuted to (o, p, s) so the shared prefix-range
    probe machinery works unchanged.  ``overflow`` is ``bool[n_shards]``,
    the OR of the two partition passes' per-shard flags.
    """
    spo, ovf_s = partition_rows(tau, n_shards, key_col=0, cap=cap)
    ops_rows = tau[:, [2, 1, 0]] if tau.size else tau
    ops, ovf_o = partition_rows(ops_rows, n_shards, key_col=0, cap=cap)
    for s in range(n_shards):
        spo[s] = spo[s][np.lexsort((spo[s][:, 2], spo[s][:, 1], spo[s][:, 0]))]
        ops[s] = ops[s][np.lexsort((ops[s][:, 2], ops[s][:, 1], ops[s][:, 0]))]
    return spo, ops, ovf_s | ovf_o


# ---------------------------------------------------------------------------
# cohort -> device placement policy
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CohortPlacement:
    """cohort id -> mesh device assignment for the broker's placed mode.

    Cohorts are the broker's unit of compilation and scheduling; placement
    makes them its unit of *distribution*: each cohort's executable (inputs
    included) is committed to one mesh device, and the broker dispatches the
    frontier pass grouped by device so same-fire cohorts on different
    devices run concurrently.

    ``mode``:
      ``"round_robin"``    new cohorts cycle through the mesh devices;
      ``"load_balanced"``  a new cohort lands on the device with the least
                           accumulated padded member count (padded size is
                           what the executable actually evaluates, dummy
                           lanes included, so it is the honest load proxy);
      ``"pinned"``         explicit ``pins`` lookup (cohort signature ->
                           device index, modulo the mesh size) with
                           ``default`` as the fallback.

    Assignments are sticky: a cohort signature keeps its device across
    fires, so its τ/ρ state stays resident and steady-state fires move no
    replica data.  Load accounting is additive — a cohort whose padded size
    grows updates its device's load, but departed cohorts are not refunded
    (signatures are stable, churn within a cohort does not change its
    signature, and the estimate only seeds *new* assignments).
    """

    mode: str = "round_robin"
    pins: Dict[object, int] = dataclasses.field(default_factory=dict)
    default: int = 0

    def __post_init__(self):
        if self.mode not in ("round_robin", "load_balanced", "pinned"):
            raise ValueError(f"unknown placement mode {self.mode!r}")
        self._assigned: Dict[object, int] = {}
        self._sizes: Dict[object, int] = {}
        self._load: Dict[int, int] = {}
        self._rr = itertools.count()

    def assign(self, sig: object, padded_members: int, n_devices: int) -> int:
        """Device index for one cohort signature (sticky across calls).

        Always in ``range(n_devices)`` — a sticky assignment made against a
        larger mesh (the instance is mutable state and may be handed to a
        second broker) folds back into the current mesh instead of indexing
        past it.
        """
        dev = self._assigned.get(sig)
        if dev is not None:
            dev %= n_devices
        if dev is None:
            if self.mode == "pinned":
                dev = self.pins.get(sig, self.default) % n_devices
            elif self.mode == "load_balanced":
                dev = min(
                    range(n_devices), key=lambda i: self._load.get(i, 0)
                )
            else:
                dev = next(self._rr) % n_devices
            self._assigned[sig] = dev
            self._sizes[sig] = 0
        grown = padded_members - self._sizes[sig]
        if grown > 0:
            self._sizes[sig] = padded_members
            self._load[dev] = self._load.get(dev, 0) + grown
        return dev


# ---------------------------------------------------------------------------
# in-graph primitives (inside shard_map)
# ---------------------------------------------------------------------------

def _bucketize(vals: jax.Array, n: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Group vals (B,) by dest = val % n into (n, B) buckets (PAD-padded).

    Returns (buckets, dest, pos) so responses can be scattered back.
    """
    b = vals.shape[0]
    live = vals != PAD
    dest = jnp.where(live, vals % n, n)  # PAD -> dropped
    onehot = jax.nn.one_hot(dest, n, dtype=jnp.int32)  # (B, n)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos_of = jnp.sum(pos * onehot, axis=1)  # (B,)
    buckets = jnp.full((n, b), PAD, jnp.int32)
    buckets = buckets.at[dest, pos_of].set(vals, mode="drop")
    return buckets, dest, pos_of


def make_routed_probe(axis: str, n_shards: int) -> Callable:
    """all_to_all probe: queries travel to the owner shard, answers return.

    Static-pattern hook contract (``make_side_evaluator(probe_impl=...)``
    without ``dynamic_patterns``):
    ``(index, pattern, bound_slot, bound_vals, fanout)``.
    """

    def routed(index: TripleIndex, pattern, bound_slot, bound_vals, fanout):
        return _routed_exchange(
            axis,
            n_shards,
            bound_vals,
            lambda recv: probe(index, pattern, bound_slot, recv, fanout),
            fanout,
        )

    return routed


def make_routed_probe_batched(axis: str, n_shards: int) -> Callable:
    """Member-axis-aware routed probe with traced pattern values.

    Speaks the *dynamic* hook contract of ``make_side_evaluator(
    dynamic_patterns=True, probe_impl=...)``:
    ``(index, pattern_host, pattern_dev, bound_slot, bound_vals, fanout)``
    — ``pattern_host`` carries the static const/var structure, ``pattern_dev``
    the traced comparison values (they differ per cohort member).

    The broker's cohort steps call this under ``jax.vmap`` over the member
    axis.  Every operation here is pointwise in the member dimension and the
    collectives carry jax's batching rules, so one *logical* probe hop per
    member lowers to ONE physical ``all_to_all`` over the flattened
    (member, binding) bucket tensor — the member axis rides inside the
    bucket payload, exactly like bucketized MoE dispatch.  The owner shard
    answers from its local hash partition: partition key == bound slot
    (subject for SPO probes, object for OPS probes), so the owner holds the
    *complete* prefix range for every query it receives and the answers —
    including the ``fanout`` truncation order — are bit-identical to a probe
    of the unpartitioned index.
    """

    def routed(
        index: TripleIndex,
        pattern_host,
        pattern_dev,
        bound_slot,
        bound_vals,
        fanout,
    ):
        return _routed_exchange(
            axis,
            n_shards,
            bound_vals,
            lambda recv: probe_dyn(
                index, pattern_host, pattern_dev, bound_slot, recv, fanout
            ),
            fanout,
        )

    return routed


def _routed_exchange(
    axis: str,
    n_shards: int,
    bound_vals: jax.Array,
    local_probe: Callable[[jax.Array], Tuple[jax.Array, jax.Array]],
    fanout: int,
) -> Tuple[jax.Array, jax.Array]:
    """Shared send/answer/return machinery of the routed probes."""
    b = bound_vals.shape[0]
    buckets, dest, pos = _bucketize(bound_vals, n_shards)
    # send: each shard receives one (B,) bucket from every peer
    recv = jax.lax.all_to_all(buckets, axis, 0, 0)  # (n, B) queries for me
    rows, valid = local_probe(recv.reshape(-1))
    rows = rows.reshape(n_shards, b, fanout, 3)
    valid = valid.reshape(n_shards, b, fanout)
    # return: answers go back to the asking shard
    rows_back = jax.lax.all_to_all(rows, axis, 0, 0)  # (n, B, K, 3)
    valid_back = jax.lax.all_to_all(
        valid.astype(jnp.int8), axis, 0, 0
    ).astype(bool)
    # un-bucketize: my query i was sent to shard dest[i] at slot pos[i]
    my_rows = rows_back[dest.clip(0, n_shards - 1), pos]
    my_valid = valid_back[dest.clip(0, n_shards - 1), pos] & (
        bound_vals != PAD
    )[:, None]
    return my_rows, my_valid


def make_or_reduce(axis: str) -> Callable:
    """Cross-shard OR: boolean bitsets via ``pmax``, lane-bit words via
    ``all_gather`` + bitwise-OR fold.

    The evaluator's signature tables / edge vectors are boolean and reduce
    through ``pmax``.  The uint32 path generalizes the hook to *lane-bit
    words*: shards that each computed a masked subset of a words tensor
    (zeros elsewhere) reassemble the full tensor by OR — exact and
    order-independent even when the subsets overlap.  (For the broker's
    disjoint block splits, gathering just the blocks and stitching them at
    static offsets is cheaper — ``make_sharded_cohort_step`` does that —
    but masked/overlapping decompositions, e.g. under custom matcher hooks,
    need the OR fold.)  Both forms batch correctly under ``jax.vmap``.
    """

    def or_reduce(t: jax.Array) -> jax.Array:
        if t.dtype == jnp.bool_:
            return jax.lax.pmax(t.astype(jnp.uint8), axis).astype(bool)
        gathered = jax.lax.all_gather(t, axis)  # (n_shards, ...)
        acc = gathered[0]
        for i in range(1, gathered.shape[0]):
            acc = acc | gathered[i]
        return acc

    return or_reduce


def route_rows_by_key(rows: jax.Array, axis: str, n_shards: int, key_col: int = 0):
    """Send each row to the shard owning ``row[key_col]`` (for Υ set algebra).

    rows: (N, 3) local, PAD-padded. Returns (n * N, 3) rows now resident on
    the owner shard (PAD-padded, unsorted).
    """
    n_rows = rows.shape[0]
    key = rows[:, key_col]
    buckets, dest, pos = _bucketize(key, n_shards)
    full_buckets = jnp.full((n_shards, n_rows, 3), PAD, jnp.int32)
    full_buckets = full_buckets.at[dest, pos].set(rows, mode="drop")
    recv = jax.lax.all_to_all(full_buckets, axis, 0, 0)
    return recv.reshape(-1, 3)


# ---------------------------------------------------------------------------
# the distributed side evaluator
# ---------------------------------------------------------------------------

def make_distributed_evaluator(
    plan: CompiledInterest,
    mesh,
    *,
    axis: str = "data",
    id_capacity: int,
    fanout: int = 4,
    out_capacity: int,
    pull_capacity: int,
):
    """shard_map side evaluator over hash-partitioned (M, τ) shards.

    Inputs (global views):
      m_shards:   int32[n, m_cap, 3]      changeset rows (any partitioning)
      spo_shards: int32[n, t_cap, 3]      τ partitioned by subject, sorted
      ops_shards: int32[n, t_cap, 3]      τ (o,p,s) partitioned by object
    Returns per-shard SideResult stacked on the leading axis.
    """
    n_shards = int(mesh.shape[axis])
    evaluator = make_side_evaluator(
        plan,
        id_capacity=id_capacity,
        fanout=fanout,
        out_capacity=out_capacity,
        pull_capacity=pull_capacity,
        probe_impl=make_routed_probe(axis, n_shards),
        table_reduce=make_or_reduce(axis),
    )

    def shard_fn(m_rows, spo_rows, ops_rows):
        m_store = TripleStore(
            spo=lex_sort(m_rows[0]),
            n=jnp.sum(m_rows[0, :, 0] != PAD, dtype=jnp.int32),
        )
        tgt = TripleIndex(
            spo=TripleStore(
                spo=spo_rows[0],
                n=jnp.sum(spo_rows[0, :, 0] != PAD, dtype=jnp.int32),
            ),
            ops=TripleStore(
                spo=ops_rows[0],
                n=jnp.sum(ops_rows[0, :, 0] != PAD, dtype=jnp.int32),
            ),
        )
        res = evaluator(m_store, tgt)
        return jax.tree.map(lambda t: t[None], res)

    spec = P(axis, None, None)
    out_specs = SideResult(
        interesting=TripleStore(spo=P(axis, None, None), n=P(axis)),
        potential=TripleStore(spo=P(axis, None, None), n=P(axis)),
        pulls=TripleStore(spo=P(axis, None, None), n=P(axis)),
        overflow=P(axis),
    )
    mapped = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=out_specs, check_vma=False,
    )
    return jax.jit(mapped)


def gather_result_sets(res: SideResult, partition_overflow=None):
    """Union the per-shard outputs into host-side sets (for tests/stats).

    Returns ``(interesting, potential, pulls, overflow)``; ``overflow`` ORs
    the per-shard device flags with any host-side partition flags passed in
    (one or more ``bool[n_shards]`` arrays from :func:`partition_rows` /
    :func:`prepare_target_shards`), so a pipeline sees every capacity
    violation — host or device — through one value.
    """
    def rows_of(store_stacked):
        arr = np.asarray(store_stacked.spo).reshape(-1, 3)
        return {tuple(int(x) for x in r) for r in arr if r[0] != PAD}

    overflow = bool(np.any(np.asarray(res.overflow)))
    if partition_overflow is not None:
        overflow = overflow or bool(np.any(np.asarray(partition_overflow)))
    return (
        rows_of(res.interesting),
        rows_of(res.potential),
        rows_of(res.pulls),
        overflow,
    )
