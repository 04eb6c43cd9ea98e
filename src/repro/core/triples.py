"""Columnar, fixed-capacity RDF triple-set algebra.

The TPU-native replacement for Jena's B-tree triple indexes: a triple store is
a lexicographically sorted ``int32[C, 3]`` array (subject, predicate, object
ids) padded at the tail with ``PAD`` sentinel rows plus a valid-count scalar.
Every operation is fixed-shape and jit-friendly; overflow is reported through
flags so the host runtime can grow a store between steps.

Triple ids produced by :mod:`repro.core.dictionary` are dense and >= 0, so
``PAD = 2**31 - 1`` sorts strictly after every valid row.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PAD = np.int32(np.iinfo(np.int32).max)
WILDCARD = np.int32(-1)


@partial(jax.tree_util.register_dataclass, data_fields=["spo", "n"], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class TripleStore:
    """A sorted, deduplicated, fixed-capacity set of RDF triples."""

    spo: jax.Array  # int32[C, 3], lex-sorted, PAD rows at the tail
    n: jax.Array  # int32[] number of valid rows

    @property
    def capacity(self) -> int:
        return self.spo.shape[0]

    def valid_mask(self) -> jax.Array:
        return self.spo[:, 0] != PAD


def empty(capacity: int) -> TripleStore:
    return TripleStore(
        spo=jnp.full((capacity, 3), PAD, dtype=jnp.int32),
        n=jnp.zeros((), dtype=jnp.int32),
    )


# ---------------------------------------------------------------------------
# lexicographic helpers (columnar int32 — avoids a global x64 flip)
# ---------------------------------------------------------------------------

def lex_less(a: jax.Array, b: jax.Array) -> jax.Array:
    """Row-wise (s, p, o) < comparison; broadcasts over leading dims."""
    s_lt = a[..., 0] < b[..., 0]
    s_eq = a[..., 0] == b[..., 0]
    p_lt = a[..., 1] < b[..., 1]
    p_eq = a[..., 1] == b[..., 1]
    o_lt = a[..., 2] < b[..., 2]
    return s_lt | (s_eq & (p_lt | (p_eq & o_lt)))


def rows_equal(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.all(a == b, axis=-1)


def lex_sort(spo: jax.Array) -> jax.Array:
    """Return ``spo`` sorted lexicographically by (s, p, o)."""
    perm = jnp.lexsort((spo[:, 2], spo[:, 1], spo[:, 0]))
    return spo[perm]


def _dedup_sorted_mask(spo: jax.Array) -> jax.Array:
    """Keep-mask for the first occurrence of each row in a sorted array."""
    prev = jnp.roll(spo, 1, axis=0)
    first = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), ~rows_equal(spo[1:], prev[1:])]
    )
    return first & (spo[:, 0] != PAD)


def compact(spo: jax.Array, keep: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Stable-partition kept rows to the front; pad the rest. Returns (rows, count).

    Works on the leading axis of any array. Output slot ``i`` takes the
    ``(i + 1)``-th kept row, found by binary search over the running count
    of kept rows: no sort, because on the TPU a sort this size takes about
    ten times longer to compile than the search (and the broker compiles
    one program per cohort shape).
    """
    n = spo.shape[0]
    if n == 0:
        return spo, jnp.zeros((), jnp.int32)
    kept = jnp.cumsum(keep, dtype=jnp.int32)
    count = kept[-1]
    idx = jnp.arange(n, dtype=jnp.int32)
    src = jnp.searchsorted(kept, idx + 1, side="left").astype(jnp.int32)
    rows = jnp.take(spo, jnp.minimum(src, n - 1), axis=0)
    live = (idx < count).reshape((n,) + (1,) * (spo.ndim - 1))
    return jnp.where(live, rows, PAD), count


def from_array(spo: jax.Array, capacity: int) -> Tuple[TripleStore, jax.Array]:
    """Build a store from an unsorted (possibly duplicated) triple array.

    Returns (store, overflowed) — ``overflowed`` is True when the distinct
    triples exceed ``capacity`` (the store then holds the first ``capacity``).
    """
    spo = jnp.asarray(spo, dtype=jnp.int32)
    if spo.ndim != 2 or spo.shape[1] != 3:
        raise ValueError(f"expected (N, 3) triples, got {spo.shape}")
    srt = lex_sort(spo)
    rows, count = compact(srt, _dedup_sorted_mask(srt))
    return _fit(rows, count, capacity)


def select(
    store: TripleStore, keep: jax.Array, capacity: int
) -> Tuple[TripleStore, jax.Array]:
    """The kept rows of a store as a store of ``capacity``: (store, overflowed).

    Equal to :func:`from_array` of the kept rows, without its sort: a
    store's rows are already sorted and distinct.
    """
    rows, count = compact(store.spo, keep & store.valid_mask())
    return _fit(rows, count, capacity)


def _fit(
    rows: jax.Array, count: jax.Array, capacity: int
) -> Tuple[TripleStore, jax.Array]:
    """Compacted sorted rows padded or cut to ``capacity`` rows."""
    c = rows.shape[0]
    if c < capacity:
        rows = jnp.concatenate(
            [rows, jnp.full((capacity - c, 3), PAD, dtype=jnp.int32)], axis=0
        )
    elif c > capacity:
        rows = rows[:capacity]
    overflow = count > capacity
    return TripleStore(spo=rows, n=jnp.minimum(count, capacity)), overflow


def store_from_np(
    triples: np.ndarray, capacity: int
) -> Tuple[TripleStore, bool]:
    """:func:`from_array` for rows held on the host: (store, overflowed).

    The rows are sorted and deduplicated on the host, so nothing is sorted
    on the device: on the TPU a sort compiles for seconds to minutes per
    shape, while replicas and incoming changesets arrive as host arrays.
    """
    rows = np.asarray(triples, np.int32).reshape(-1, 3)
    rows = rows[np.lexsort((rows[:, 2], rows[:, 1], rows[:, 0]))]
    first = np.ones(rows.shape[0], bool)
    first[1:] = (rows[1:] != rows[:-1]).any(axis=1)
    rows = rows[first & (rows[:, 0] != PAD)]
    count = min(rows.shape[0], capacity)
    out = np.full((capacity, 3), PAD, np.int32)
    out[:count] = rows[:count]
    store = TripleStore(spo=jnp.asarray(out), n=jnp.asarray(count, jnp.int32))
    return store, rows.shape[0] > capacity


def from_numpy(triples: np.ndarray, capacity: int) -> TripleStore:
    store, overflow = store_from_np(triples, capacity)
    if overflow:
        raise ValueError(
            f"{np.asarray(triples).shape[0]} distinct triples exceed "
            f"capacity {capacity}"
        )
    return store


# ---------------------------------------------------------------------------
# binary search over sorted rows
# ---------------------------------------------------------------------------

def searchsorted_rows(sorted_spo: jax.Array, queries: jax.Array, side: str = "left") -> jax.Array:
    """Vectorized lexicographic searchsorted. ``queries``: int32[Q, 3]."""
    c = sorted_spo.shape[0]
    q = queries.shape[0]
    lo = jnp.zeros((q,), dtype=jnp.int32)
    hi = jnp.full((q,), c, dtype=jnp.int32)
    iters = max(1, int(np.ceil(np.log2(c + 1))) + 1)

    def body(_, state):
        lo, hi = state
        mid = (lo + hi) // 2
        row = jnp.take(sorted_spo, jnp.minimum(mid, c - 1), axis=0)
        if side == "left":
            go_right = lex_less(row, queries)
        else:
            go_right = ~lex_less(queries, row)
        active = lo < hi
        new_lo = jnp.where(active & go_right, mid + 1, lo)
        new_hi = jnp.where(active & ~go_right, mid, hi)
        return new_lo, new_hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return lo


def _locate(sorted_spo: jax.Array, queries: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Each query's rank among the sorted rows, and whether it is one of them."""
    c = sorted_spo.shape[0]
    idx = searchsorted_rows(sorted_spo, queries, side="left")
    rows = jnp.take(sorted_spo, jnp.minimum(idx, c - 1), axis=0)
    return idx, (idx < c) & rows_equal(rows, queries)


def member(store: TripleStore, queries: jax.Array) -> jax.Array:
    """Boolean membership of each query row in the store."""
    return _locate(store.spo, queries)[1]


def prefix_range(store: TripleStore, prefix: jax.Array, depth: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[start, end) of rows matching the first ``depth`` columns of ``prefix``.

    ``prefix``: int32[Q, 3] (columns past ``depth`` ignored); ``depth``:
    int32[Q] in {1, 2, 3}. Works on any store sorted in the column order the
    prefix refers to.
    """
    neg = jnp.int32(np.iinfo(np.int32).min)
    col = jnp.arange(3, dtype=jnp.int32)[None, :]
    lo_q = jnp.where(col < depth[:, None], prefix, neg)
    hi_q = jnp.where(col < depth[:, None], prefix, PAD)
    start = searchsorted_rows(store.spo, lo_q, side="left")
    end = searchsorted_rows(store.spo, hi_q, side="right")
    return start, end


# ---------------------------------------------------------------------------
# set algebra
# ---------------------------------------------------------------------------

def difference(a: TripleStore, b: TripleStore) -> TripleStore:
    """a \\ b, keeping a's capacity."""
    in_b = member(b, a.spo)
    keep = a.valid_mask() & ~in_b
    rows, count = compact(a.spo, keep)
    return TripleStore(spo=rows, n=count)


def intersection(a: TripleStore, b: TripleStore) -> TripleStore:
    in_b = member(b, a.spo)
    keep = a.valid_mask() & in_b
    rows, count = compact(a.spo, keep)
    return TripleStore(spo=rows, n=count)


def merge_sorted(a: jax.Array, b: jax.Array) -> jax.Array:
    """Two lex-sorted row arrays merged into one lex-sorted array.

    Output row ``k`` is found by a co-rank binary search (merge path): the
    number ``i`` of ``a`` rows among the first ``k`` outputs is the least
    ``i`` with ``b[k - i - 1] < a[i]``. Gathers and compares only: equal to
    ``lex_sort(concatenate([a, b]))``, with no sort to compile (on the TPU
    a sort of a replica's size takes minutes to compile).
    """
    na, nb = a.shape[0], b.shape[0]
    if na == 0 or nb == 0:
        return b if na == 0 else a
    k = jnp.arange(na + nb, dtype=jnp.int32)
    lo = jnp.maximum(0, k - nb)
    hi = jnp.minimum(k, na)
    iters = max(1, int(np.ceil(np.log2(min(na, nb) + 1))) + 1)

    def body(_, state):
        lo, hi = state
        mid = (lo + hi) // 2
        j = k - mid
        a_mid = jnp.take(a, jnp.minimum(mid, na - 1), axis=0)
        b_prev = jnp.take(b, jnp.clip(j - 1, 0, nb - 1), axis=0)
        ok = (mid >= na) | (j <= 0) | lex_less(b_prev, a_mid)
        active = lo < hi
        return (
            jnp.where(active & ~ok, mid + 1, lo),
            jnp.where(active & ok, mid, hi),
        )

    i, _ = jax.lax.fori_loop(0, iters, body, (lo, hi))
    j = k - i
    a_i = jnp.take(a, jnp.minimum(i, na - 1), axis=0)
    b_j = jnp.take(b, jnp.minimum(j, nb - 1), axis=0)
    take_a = (j >= nb) | ((i < na) & ~lex_less(b_j, a_i))
    return jnp.where(take_a[:, None], a_i, b_j)


def union(a: TripleStore, b: TripleStore, capacity: int | None = None) -> Tuple[TripleStore, jax.Array]:
    """a ∪ b with the given output capacity (defaults to a's). Returns (store, overflowed)."""
    capacity = a.capacity if capacity is None else capacity
    srt = merge_sorted(a.spo, b.spo)
    rows, count = compact(srt, _dedup_sorted_mask(srt))
    return _fit(rows, count, capacity)


@partial(jax.jit, static_argnames=("capacity",))
def apply_changeset(
    store: TripleStore,
    removed: TripleStore | Sequence[TripleStore],
    added: TripleStore,
    capacity: int | None = None,
) -> Tuple[TripleStore, jax.Array]:
    """υ(V, Δ) = (V \\ D) ∪ A  — Definition 6 (delete-first ordering).

    ``removed`` is one store or several, whose union is D. Returns what
    ``union(difference(V, D), A, capacity)`` returns, bit for bit: the
    sorted rows of the result, cut to ``capacity`` (default V's) with a PAD
    tail, their count and the overflow flag.

    The work follows the delta, not V: each valid row of D and A is binary
    searched into V (|D| + |A| queries of log C steps, for C = V's
    capacity). A removed row takes effect only if V holds it and A does
    not re-add it; an added row that V already holds stays in place; every
    other added row is inserted. Output slot ``k`` then takes V's row
    ``k + off(k)``, where ``off`` is a step function with one step per
    effective removal (+1) and insertion (−1), built by one scatter of the
    steps and one prefix sum over ``capacity``; the inserted rows are
    scattered in over it. So a commit costs one O(capacity) prefix sum and
    row gather, where :func:`difference` and :func:`union` each make log C
    rounds of C-wide gathers. Several removed stores are merged first, at
    their own small size.

    Every replica commit τ′ = (τ \\ (r ∪ r′)) ∪ a uses it
    (:func:`repro.core.propagation.combine_side_results`, hence the
    broker's cohort steps, single-device and sharded, and the seed
    per-interest step).
    """
    capacity = store.capacity if capacity is None else capacity
    if not isinstance(removed, TripleStore):
        parts = list(removed)
        removed = parts[0]
        for part in parts[1:]:
            removed, _ = union(
                removed, part, removed.capacity + part.capacity
            )
    d_rows, a_rows = removed.spo, added.spo
    d_pos, d_in_v = _locate(store.spo, d_rows)
    a_pos, a_in_v = _locate(store.spo, a_rows)
    d_in_a, d_readded = _locate(a_rows, d_rows)
    drop = removed.valid_mask() & d_in_v & ~d_readded
    insert = added.valid_mask() & ~a_in_v

    def running_count(flags):
        """How many flags are set before each index, and in all (the last)."""
        return jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(flags, dtype=jnp.int32)]
        )

    # D and A are each sorted, so the counts run in V's order too
    drops_before = running_count(drop)
    inserts_before = running_count(insert)
    count = store.n - drops_before[-1] + inserts_before[-1]

    # output slot of each inserted row: V's kept rows below it, plus the
    # inserted rows before it
    a_in_d = searchsorted_rows(d_rows, a_rows)
    a_slot = a_pos - jnp.take(drops_before, a_in_d) + inserts_before[:-1]
    # the first output slot past each dropped row: from there on, V's rows
    # come one further along
    d_slot = (
        d_pos - drops_before[:-1] + jnp.take(inserts_before, d_in_a)
    )
    out_of_range = jnp.int32(capacity)
    steps = jnp.zeros((capacity,), jnp.int32)
    steps = steps.at[jnp.where(drop, d_slot, out_of_range)].add(
        1, mode="drop"
    )
    steps = steps.at[jnp.where(insert, a_slot + 1, out_of_range)].add(
        -1, mode="drop"
    )
    k = jnp.arange(capacity, dtype=jnp.int32)
    src = k + jnp.cumsum(steps, dtype=jnp.int32)
    rows = jnp.take(store.spo, jnp.clip(src, 0, store.capacity - 1), axis=0)
    # each inserted row has a slot of its own, below |V| + |A|; the others
    # aim past every slot, each at its own index
    n_a = a_rows.shape[0]
    ins_at = jnp.where(
        insert,
        a_slot,
        store.capacity + n_a + jnp.arange(n_a, dtype=jnp.int32),
    )
    rows = rows.at[ins_at].set(a_rows, mode="drop", unique_indices=True)
    rows = jnp.where((k < count)[:, None], rows, PAD)
    return TripleStore(spo=rows, n=jnp.minimum(count, capacity)), count > capacity


def rehome(store: TripleStore, capacity: int) -> TripleStore:
    """Move a store to a new capacity WITHOUT re-sorting or host transfer.

    Valid rows are already lex-sorted at the front with a PAD tail, so
    growing pads more PAD rows and shrinking slices the front. Shrinking
    requires ``store.n <= capacity`` (the broker's host-side capacity guard
    enforces this before any device-resident re-home); rows past the new
    capacity are then all PAD by construction.
    """
    c = store.spo.shape[0]
    if c == capacity:
        return store
    if c < capacity:
        spo = jnp.concatenate(
            [store.spo, jnp.full((capacity - c, 3), PAD, dtype=jnp.int32)],
            axis=0,
        )
    else:
        spo = store.spo[:capacity]
    return TripleStore(spo=spo, n=store.n)


def to_numpy(store: TripleStore) -> np.ndarray:
    spo = np.asarray(store.spo)
    return spo[spo[:, 0] != PAD]


def to_set(store: TripleStore) -> set:
    return {tuple(int(x) for x in row) for row in to_numpy(store)}


# ---------------------------------------------------------------------------
# pattern matching (XLA path; the Pallas kernel lives in repro.kernels)
# ---------------------------------------------------------------------------

def match_bitmask(spo: jax.Array, patterns: jax.Array) -> jax.Array:
    """uint32[N] bitset: bit j set iff row matches patterns[j] (-1 = wildcard).

    Padding rows (s == PAD) match nothing.
    """
    n_pat = patterns.shape[0]
    if n_pat > 32:
        raise ValueError("at most 32 patterns per bitset")
    valid = spo[:, 0] != PAD
    acc = jnp.zeros(spo.shape[0], dtype=jnp.uint32)
    for j in range(n_pat):
        pat = patterns[j]
        m = valid
        for k in range(3):
            m = m & ((pat[k] == WILDCARD) | (spo[:, k] == pat[k]))
        acc = acc | (m.astype(jnp.uint32) << j)
    return acc
