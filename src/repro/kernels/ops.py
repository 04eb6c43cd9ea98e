"""Public jit'd wrappers around the Pallas kernels with XLA fallbacks.

On non-TPU backends Pallas runs in interpret mode (Python, slow) — correct
but not fast — so the default execution path off-TPU is the pure-XLA
reference; the kernels remain the TPU target and are exercised by the test
suite in interpret mode against the oracles in :mod:`repro.kernels.ref`.

Set ``repro.kernels.ops.FORCE_KERNEL = True`` (or pass ``use_kernel=True``)
to route through the Pallas implementations everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import ref, triple_match

PAD = ref.PAD
FORCE_KERNEL = False


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _want_kernel(use_kernel: bool | None) -> bool:
    if use_kernel is None:
        return FORCE_KERNEL or _on_tpu()
    return use_kernel


def pattern_bitmask(spo: jax.Array, patterns: jax.Array, *, use_kernel: bool | None = None) -> jax.Array:
    """uint32[N] bitset of pattern matches per triple row."""
    if not _want_kernel(use_kernel):
        return ref.pattern_bitmask_ref(spo, patterns)
    tile = 128 * triple_match.BLOCK_ROWS
    n = spo.shape[0]
    n_pad = -n % tile
    if n_pad:
        spo = jnp.concatenate(
            [spo, jnp.full((n_pad, 3), PAD, dtype=jnp.int32)], axis=0
        )
    out = triple_match.triple_match_pallas(
        spo, patterns, interpret=not _on_tpu()
    )
    return out[:n]


def pattern_bitmask_words(
    spo: jax.Array,
    patterns,
    *,
    matcher=None,
    use_kernel: bool | None = None,
) -> jax.Array:
    """uint32[N, W] multi-word bitset over an arbitrary-size pattern bank.

    One uint32 bitset lane per pattern caps a single word at 32 patterns. A
    multi-interest pattern bank can exceed that, so the bank spans
    ``W = ceil(P / 32)`` words: word ``w`` holds the match bits for
    ``patterns[32w : 32w + 32]``. All W words are produced by a SINGLE
    fused pass over ``spo`` — the Pallas path emits them in one kernel
    invocation (one HBM pass over the triple tiles regardless of bank
    width), the XLA path packs one vectorized (N, P) match matrix.

    ``matcher`` (optional) must have the :func:`pattern_bitmask` signature;
    the broker threads its distribution/testing hook through here so the
    fused path and the per-interest path route through the same primitive —
    with a custom matcher the bank falls back to one chunked pass per word.
    """
    n_pat = patterns.shape[0]
    if matcher is not None:
        n_words = max(1, -(-n_pat // 32))
        words = []
        for w in range(n_words):
            chunk = patterns[w * 32 : (w + 1) * 32]
            if chunk.shape[0] == 0:
                words.append(jnp.zeros((spo.shape[0],), jnp.uint32))
            else:
                words.append(matcher(spo, chunk))
        return jnp.stack(words, axis=1)
    if n_pat == 0 or not _want_kernel(use_kernel):
        return ref.pattern_bitmask_words_ref(spo, patterns)
    tile = 128 * triple_match.BLOCK_ROWS
    n = spo.shape[0]
    n_pad = -n % tile
    if n_pad:
        spo = jnp.concatenate(
            [spo, jnp.full((n_pad, 3), PAD, dtype=jnp.int32)], axis=0
        )
    out = triple_match.triple_match_words_pallas(
        spo, patterns, interpret=not _on_tpu()
    )
    return out.T[:n]


def pattern_bitmask_words_segmented(
    spo: jax.Array,
    patterns: jax.Array,
    seg: jax.Array,
    n_seg: int,
    *,
    matcher=None,
    use_kernel: bool | None = None,
) -> jax.Array:
    """uint32[n_seg, N, W] segment-masked bank bitsets from ONE match pass.

    ``seg``: int32[N] per-row membership bitmap — bit ``f`` set iff row
    ``i`` belongs to segment ``f`` (bits >= ``n_seg`` ignored, ``n_seg <=
    32``). Plane ``f`` equals ``pattern_bitmask_words(spo[members_f])``
    scattered back to the full row space with non-member rows zeroed.

    This is the delta-encoded frontier chain's primitive: the broker hands
    it the lex-sorted union of the distinct D rows across all fired flush
    frontiers plus each frontier's membership bits, so ``F`` overlapping
    frontiers cost one bank pass over the union (the Pallas path masks the
    per-frontier planes while the words are still in registers; the XLA
    path packs one match matrix and masks per plane) instead of the F
    stacked passes of the pre-delta scheduler.

    With a custom ``matcher`` (distribution/testing hook) the words are
    produced by the chunked :func:`pattern_bitmask_words` path — the hook
    observes exactly ONE pass per 32-lane word, never one per segment.
    """
    if not 1 <= n_seg <= 32:
        raise ValueError(f"n_seg must be in [1, 32], got {n_seg}")
    if matcher is not None or patterns.shape[0] == 0 or not _want_kernel(
        use_kernel
    ):
        if matcher is not None:
            words = pattern_bitmask_words(spo, patterns, matcher=matcher)
            member = (
                (seg[None, :] >> jnp.arange(n_seg, dtype=jnp.int32)[:, None])
                & 1
            ) == 1
            return jnp.where(member[:, :, None], words[None], jnp.uint32(0))
        return ref.pattern_bitmask_words_segmented_ref(
            spo, patterns, seg, n_seg
        )
    tile = 128 * triple_match.BLOCK_ROWS
    n = spo.shape[0]
    n_pad = -n % tile
    if n_pad:
        spo = jnp.concatenate(
            [spo, jnp.full((n_pad, 3), PAD, dtype=jnp.int32)], axis=0
        )
        seg = jnp.concatenate(
            [seg, jnp.zeros((n_pad,), dtype=seg.dtype)], axis=0
        )
    out = triple_match.triple_match_words_segmented_pallas(
        spo, patterns, seg, n_seg=n_seg, interpret=not _on_tpu()
    )
    return jnp.swapaxes(out, 1, 2)[:, :n]


def lane_refine(
    spo: jax.Array,
    words: jax.Array,
    parents: jax.Array,
    residual: jax.Array,
    *,
    use_kernel: bool | None = None,
) -> jax.Array:
    """uint32[N, Wv] virtual-lane words refined from real-bank words.

    The interest-subsumption lattice's containment op: virtual lane ``v``
    holds a pattern strictly contained by real bank lane ``parents[v]``
    (child ≡ parent AND ``residual[v]``, the child's constants in exactly
    the slots the parent leaves variable). Instead of widening the bank and
    re-running the full compare loop, the child's words are the parent's
    already-computed bit (gathered out of ``words``: uint32[N, W] from
    :func:`pattern_bitmask_words` over the same ``spo``) ANDed with the
    three-term residual compare — bit-identical to what
    :func:`pattern_bitmask_words` would emit for the materialized child
    patterns. ``parents[v] == -1`` marks a dead slot (bits forced to zero);
    ``Wv = ceil(len(parents) / 32)``, min 1.
    """
    if parents.shape[0] == 0 or not _want_kernel(use_kernel):
        return ref.lane_refine_ref(spo, words, parents, residual)
    tile = 128 * triple_match.BLOCK_ROWS
    n = spo.shape[0]
    n_pad = -n % tile
    if n_pad:
        spo = jnp.concatenate(
            [spo, jnp.full((n_pad, 3), PAD, dtype=jnp.int32)], axis=0
        )
        words = jnp.concatenate(
            [words, jnp.zeros((n_pad, words.shape[1]), jnp.uint32)], axis=0
        )
    out = triple_match.lane_refine_pallas(
        spo, words, parents, residual, interpret=not _on_tpu()
    )
    return out.T[:n]


def pattern_lane_bits_batched(
    spo_b: jax.Array,
    patterns: jax.Array,
    lanes: jax.Array,
    active: jax.Array | None = None,
    *,
    matcher=None,
    use_kernel: bool | None = None,
) -> jax.Array:
    """uint32[R, N] fused bank match + lane routing for a member-stacked
    cohort: member ``k``'s local pattern ``j`` reads bank lane
    ``lanes[k, j]`` over its own rows ``spo_b[k]``; inactive (padding)
    members produce all-zero bits.

    Semantically ``lane_bits_batched(words_per_member, lanes, active)`` with
    ``words_per_member = pattern_bitmask_words`` mapped over members — but
    the Pallas path runs match + routing + masking in ONE kernel, so the
    intermediate uint32[R, N, W] bank words never leave registers. With a
    custom ``matcher`` the composed (unfused) pipeline is used so
    distribution/testing hooks observe every bank pass.
    """
    if matcher is not None:
        words = jax.vmap(
            lambda s: pattern_bitmask_words(s, patterns, matcher=matcher)
        )(spo_b)
        return lane_bits_batched(words, lanes, active=active)
    if patterns.shape[0] == 0 or not _want_kernel(use_kernel):
        return ref.pattern_lane_bits_ref(spo_b, patterns, lanes, active)
    r, n = spo_b.shape[0], spo_b.shape[1]
    tile = 128 * triple_match.BLOCK_ROWS
    n_pad = -n % tile
    if n_pad:
        spo_b = jnp.concatenate(
            [spo_b, jnp.full((r, n_pad, 3), PAD, dtype=jnp.int32)], axis=1
        )
    act = (
        jnp.ones((r, 1), jnp.int32)
        if active is None
        else active.astype(jnp.int32).reshape(r, 1)
    )
    out = triple_match.triple_match_lanes_pallas(
        spo_b, patterns, lanes, act, interpret=not _on_tpu()
    )
    return out[:, :n]


def lane_bits(words: jax.Array, lanes) -> jax.Array:
    """Route bank bitset lanes back to one plan's local pattern numbering.

    ``words``: uint32[N, W] from :func:`pattern_bitmask_words` over a shared
    pattern bank. ``lanes``: static sequence mapping this plan's local
    pattern index ``j`` to its bank lane. Returns uint32[N] with bit ``j``
    set iff bank lane ``lanes[j]`` is set — i.e. exactly what
    ``pattern_bitmask(spo, plan.patterns)`` would have produced.
    """
    acc = jnp.zeros((words.shape[0],), dtype=jnp.uint32)
    for j, lane in enumerate(lanes):
        lane = int(lane)
        bit = (words[:, lane // 32] >> np.uint32(lane % 32)) & np.uint32(1)
        acc = acc | (bit << np.uint32(j))
    return acc


def lane_bits_batched(
    words: jax.Array,
    lanes_arr: jax.Array,
    active: jax.Array | None = None,
    row_mask: jax.Array | None = None,
) -> jax.Array:
    """Batched lane routing for a subscriber cohort.

    ``words``: uint32[N, R, W] bank bitset words (per cohort member, per
    triple row). ``lanes_arr``: int32[N, nt] — member ``k``'s local pattern
    ``j`` reads bank lane ``lanes_arr[k, j]``. Returns uint32[N, R] local
    bitsets: the vectorized equivalent of calling :func:`lane_bits` once per
    member, used by the broker's vmapped cohort evaluation.

    ``active`` (optional): bool[N] member mask. The broker pads cohorts to
    power-of-two sizes so membership churn reuses cached executables; the
    padding lanes are dummy members whose bits are forced to zero here, so
    downstream evaluation sees no candidates and produces empty outputs.

    ``row_mask`` (optional): bool[N, R] per-shard row-ownership mask — the
    sharded broker's variant.  Each mesh device evaluates the same member
    rows but owns only the subset whose hash lands on it; zeroing the other
    rows' bits here partitions candidates, signature scatters, and outputs
    across shards without reshaping any executable input.
    """
    n, r, _ = words.shape
    nt = lanes_arr.shape[1]
    word_idx = jnp.broadcast_to((lanes_arr // 32)[:, None, :], (n, r, nt))
    shift = (lanes_arr % 32).astype(jnp.uint32)[:, None, :]
    g = jnp.take_along_axis(words, word_idx, axis=2)
    bits = ((g >> shift) & jnp.uint32(1)) << jnp.arange(
        nt, dtype=jnp.uint32
    )[None, None, :]
    # lanes occupy disjoint local bit positions, so sum == bitwise OR
    out = jnp.sum(bits, axis=2, dtype=jnp.uint32)
    if active is not None:
        out = jnp.where(active[:, None], out, jnp.uint32(0))
    if row_mask is not None:
        out = jnp.where(row_mask, out, jnp.uint32(0))
    return out

