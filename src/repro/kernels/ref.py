"""Pure-jnp oracles for the Pallas kernels (allclose targets for tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PAD = np.int32(np.iinfo(np.int32).max)
WILDCARD = np.int32(-1)


def pattern_bitmask_ref(spo: jax.Array, patterns: jax.Array) -> jax.Array:
    """uint32[N] bitset: bit j set iff row i matches patterns[j].

    ``patterns``: int32[P, 3] with -1 as wildcard. PAD rows match nothing.
    """
    n_pat = patterns.shape[0]
    valid = spo[:, 0] != PAD
    acc = jnp.zeros(spo.shape[0], dtype=jnp.uint32)
    for j in range(n_pat):
        pat = patterns[j]
        m = valid
        for k in range(3):
            m = m & ((pat[k] == WILDCARD) | (spo[:, k] == pat[k]))
        acc = acc | (m.astype(jnp.uint32) << j)
    return acc


def pattern_bitmask_words_ref(spo: jax.Array, patterns: jax.Array) -> jax.Array:
    """uint32[N, W] multi-word bank bitset: word ``w`` carries the match
    bits of ``patterns[32w : 32w + 32]`` (W = ceil(P / 32), min 1).

    Oracle for the single-invocation multi-word kernel
    (:func:`repro.kernels.triple_match.triple_match_words_pallas`) and the
    vectorized XLA fallback: one (N, P) match matrix packed into words,
    bit-identical to chunked per-32-lane :func:`pattern_bitmask_ref` passes.
    """
    n = spo.shape[0]
    n_pat = patterns.shape[0]
    n_words = max(1, -(-n_pat // 32))
    if n_pat == 0:
        return jnp.zeros((n, n_words), jnp.uint32)
    valid = spo[:, 0] != PAD
    m = valid[:, None]
    for k in range(3):
        pk = patterns[:, k][None, :]
        m = m & ((pk == WILDCARD) | (spo[:, k][:, None] == pk))
    pad_p = n_words * 32 - n_pat
    if pad_p:
        m = jnp.concatenate([m, jnp.zeros((n, pad_p), bool)], axis=1)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(
        m.reshape(n, n_words, 32).astype(jnp.uint32) * weights[None, None, :],
        axis=-1,
        dtype=jnp.uint32,
    )


def pattern_bitmask_words_segmented_ref(
    spo: jax.Array, patterns: jax.Array, seg: jax.Array, n_seg: int
) -> jax.Array:
    """uint32[n_seg, N, W] segment-masked multi-word bank bitset.

    ``seg``: int32[N] per-row segment membership bitmap — bit ``f`` set iff
    row ``i`` belongs to segment ``f`` (the broker's delta-encoded frontier
    chain encodes "union row i is in frontier f's composed D" this way).
    Segment ``f``'s plane equals :func:`pattern_bitmask_words_ref` with the
    non-member rows' words forced to zero — the match itself is evaluated
    exactly ONCE per row and composed per segment by masking, which is the
    whole point: ``n_seg`` overlapping row sets cost one bank pass, not
    ``n_seg``. Bits of ``seg`` at or above ``n_seg`` are ignored.

    Oracle for the single-invocation segmented kernel
    (:func:`repro.kernels.triple_match.triple_match_words_segmented_pallas`)
    and the vectorized XLA fallback.
    """
    words = pattern_bitmask_words_ref(spo, patterns)  # (N, W)
    member = (
        (seg[None, :] >> jnp.arange(n_seg, dtype=jnp.int32)[:, None]) & 1
    ) == 1  # (n_seg, N)
    return jnp.where(member[:, :, None], words[None, :, :], jnp.uint32(0))


def pattern_lane_bits_ref(
    spo_b: jax.Array,
    patterns: jax.Array,
    lanes: jax.Array,
    active: jax.Array | None = None,
) -> jax.Array:
    """uint32[R, N] fused bank emit + lane routing + member mask oracle.

    ``spo_b``: int32[R, N, 3] member-stacked rows; ``lanes``: int32[R, nt];
    ``active`` (optional): bool[R]. Member k's local bit ``j`` is bank lane
    ``lanes[k, j]``'s match bit over ``spo_b[k]``; inactive members are all
    zeros. Oracle for
    :func:`repro.kernels.triple_match.triple_match_lanes_pallas`.
    """
    words = jax.vmap(lambda s: pattern_bitmask_words_ref(s, patterns))(spo_b)
    r, n, _ = words.shape
    nt = lanes.shape[1]
    word_idx = jnp.broadcast_to((lanes // 32)[:, None, :], (r, n, nt))
    shift = (lanes % 32).astype(jnp.uint32)[:, None, :]
    g = jnp.take_along_axis(words, word_idx, axis=2)
    bits = ((g >> shift) & jnp.uint32(1)) << jnp.arange(nt, dtype=jnp.uint32)[
        None, None, :
    ]
    out = jnp.sum(bits, axis=2, dtype=jnp.uint32)
    if active is not None:
        out = jnp.where(active[:, None], out, jnp.uint32(0))
    return out


def lane_refine_ref(
    spo: jax.Array,
    words: jax.Array,
    parents: jax.Array,
    residual: jax.Array,
) -> jax.Array:
    """uint32[N, Wv] refined virtual-lane words (the containment-DAG op).

    ``words``: uint32[N, W] real-bank words (:func:`pattern_bitmask_words_ref`
    output); ``parents``: int32[Vp] parent bank lane per virtual slot (-1 =
    dead slot, bits forced to zero); ``residual``: int32[Vp, 3] with the
    child's constants in exactly the slots the parent leaves variable
    (WILDCARD elsewhere). Output word ``w`` bit ``b`` carries virtual slot
    ``v = 32w + b``: the parent lane's match bit ANDed with the residual
    equality predicate — bit-identical to what
    :func:`pattern_bitmask_words_ref` would emit for the materialized child
    rows (child ≡ parent AND residual), at residual-compare cost instead of
    a full bank-width pass. Oracle for
    :func:`repro.kernels.triple_match.lane_refine_pallas` and the XLA
    fallback.
    """
    n = spo.shape[0]
    vp = parents.shape[0]
    n_out = max(1, -(-vp // 32))
    if vp == 0:
        return jnp.zeros((n, n_out), jnp.uint32)
    live = parents >= 0
    p_safe = jnp.maximum(parents, 0)
    g = jnp.take(words, p_safe // 32, axis=1)  # (N, Vp)
    pbit = (g >> (p_safe % 32).astype(jnp.uint32)[None, :]) & jnp.uint32(1)
    m = live[None, :]
    for k in range(3):
        rk = residual[:, k][None, :]
        m = m & ((rk == WILDCARD) | (spo[:, k][:, None] == rk))
    m = m & (pbit == jnp.uint32(1))
    pad_v = n_out * 32 - vp
    if pad_v:
        m = jnp.concatenate([m, jnp.zeros((n, pad_v), bool)], axis=1)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(
        m.reshape(n, n_out, 32).astype(jnp.uint32) * weights[None, None, :],
        axis=-1,
        dtype=jnp.uint32,
    )

