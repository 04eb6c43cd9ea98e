"""Interpret-mode parity of the multi-word bank kernels vs the oracles.

Covers the single-invocation multi-word emit
(:func:`repro.kernels.triple_match.triple_match_words_pallas`) and the
fused emit + lane-routing + member-mask kernel
(:func:`repro.kernels.triple_match.triple_match_lanes_pallas`) against the
pure-jnp oracles in :mod:`repro.kernels.ref` AND against the historical
chunked composition (per-32-lane :func:`ref.pattern_bitmask_ref` words +
:func:`ops.lane_bits_batched` routing), including W = 1 banks,
non-multiple-of-32 bank widths, and all-tombstone words.

Deliberately hypothesis-free (seeded ``numpy.random``): fixed shapes keep
these tier-1 kernel parity tests cheap and reproducible.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.triple_match import (
    BLOCK_ROWS,
    triple_match_lanes_pallas,
    triple_match_words_pallas,
)

PAD = ref.PAD
TILE = 128 * BLOCK_ROWS


def _random_spo(rng, n, vocab=9, pad_frac=0.1):
    spo = rng.integers(0, vocab, size=(n, 3)).astype(np.int32)
    spo[rng.random(n) < pad_frac] = PAD
    return spo


def _random_bank(rng, n_pat, vocab=9, tombstone_frac=0.0):
    pats = rng.integers(-1, vocab, size=(n_pat, 3)).astype(np.int32)
    if tombstone_frac:
        pats[rng.random(n_pat) < tombstone_frac] = PAD
    return pats


def _chunked_words(spo, pats):
    """The pre-fusion reference: one pattern_bitmask_ref pass per word."""
    n_pat = pats.shape[0]
    n_words = max(1, -(-n_pat // 32))
    words = []
    for w in range(n_words):
        chunk = pats[w * 32 : (w + 1) * 32]
        if chunk.shape[0] == 0:
            words.append(jnp.zeros((spo.shape[0],), jnp.uint32))
        else:
            words.append(ref.pattern_bitmask_ref(spo, chunk))
    return jnp.stack(words, axis=1)


# ---------------------------------------------------------------------------
# multi-word emit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_pat", [1, 5, 31, 32, 33, 40, 63, 64, 65])
def test_words_ref_matches_chunked(n_pat):
    """Vectorized multi-word oracle == historical per-32-lane chunking
    (W = 1 and every non-multiple-of-32 width around the word boundary)."""
    rng = np.random.default_rng(n_pat)
    spo = jnp.asarray(_random_spo(rng, 777))
    pats = jnp.asarray(_random_bank(rng, n_pat))
    got = ref.pattern_bitmask_words_ref(spo, pats)
    want = _chunked_words(spo, pats)
    assert got.shape == (777, max(1, -(-n_pat // 32)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n_pat", [1, 5, 32, 33, 40, 64])
@pytest.mark.parametrize("n", [1, 100, TILE - 1, TILE, TILE + 1])
def test_words_kernel_matches_ref(n_pat, n):
    """One Pallas invocation (interpret mode) emits all W words exactly."""
    rng = np.random.default_rng(n_pat * 1000 + n)
    spo = jnp.asarray(_random_spo(rng, n))
    pats = jnp.asarray(_random_bank(rng, n_pat, tombstone_frac=0.15))
    got = ops.pattern_bitmask_words(spo, pats, use_kernel=True)
    want = ref.pattern_bitmask_words_ref(spo, pats)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_words_kernel_direct_tile_aligned():
    """The raw kernel wrapper (uint32[W, N] layout) on an exact tile."""
    rng = np.random.default_rng(7)
    spo = jnp.asarray(_random_spo(rng, TILE))
    pats = jnp.asarray(_random_bank(rng, 40))
    got = triple_match_words_pallas(spo, pats, interpret=True)
    want = ref.pattern_bitmask_words_ref(spo, pats)
    assert got.shape == (2, TILE)
    np.testing.assert_array_equal(np.asarray(got.T), np.asarray(want))


def test_words_all_tombstone_word():
    """A word whose 32 lanes are all tombstones emits exactly zero — and
    the PAD sentinel row can never match a valid triple."""
    rng = np.random.default_rng(11)
    spo = jnp.asarray(_random_spo(rng, 500, pad_frac=0.3))
    pats = np.full((64, 3), PAD, np.int32)  # word 1 entirely dead
    pats[:32] = _random_bank(rng, 32)
    pats = jnp.asarray(pats)
    for use_kernel in (False, True):
        words = ops.pattern_bitmask_words(spo, pats, use_kernel=use_kernel)
        np.testing.assert_array_equal(
            np.asarray(words[:, 1]), np.zeros((500,), np.uint32)
        )
        np.testing.assert_array_equal(
            np.asarray(words[:, 0]),
            np.asarray(ref.pattern_bitmask_ref(spo, pats[:32])),
        )


def test_words_matcher_hook_still_chunked():
    """A custom matcher (distribution/testing hook) must observe one pass
    per 32-lane word — the fused kernel may not bypass it."""
    calls = []

    def spy(spo, chunk):
        calls.append(int(chunk.shape[0]))
        return ref.pattern_bitmask_ref(spo, chunk)

    rng = np.random.default_rng(3)
    spo = jnp.asarray(_random_spo(rng, 64))
    pats = jnp.asarray(_random_bank(rng, 40))
    got = ops.pattern_bitmask_words(spo, pats, matcher=spy)
    assert calls == [32, 8]
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.pattern_bitmask_words_ref(spo, pats))
    )


# ---------------------------------------------------------------------------
# segmented emit (delta-encoded frontier chains)
# ---------------------------------------------------------------------------

from repro.kernels.triple_match import triple_match_words_segmented_pallas


def _masked_planes(spo, pats, seg, n_seg):
    """The pre-delta reference: one full words pass per segment, each over
    only that segment's member rows (non-members replaced by PAD rows)."""
    planes = []
    for f in range(n_seg):
        m = (np.asarray(seg) >> f) & 1
        spo_f = np.where(
            (m == 1)[:, None], np.asarray(spo), np.full((1, 3), PAD, np.int32)
        )
        w = ref.pattern_bitmask_words_ref(jnp.asarray(spo_f), pats)
        # PAD substitution kills the match, matching the masked-plane spec
        planes.append(jnp.where(jnp.asarray(m == 1)[:, None], w, jnp.uint32(0)))
    return jnp.stack(planes)


@pytest.mark.parametrize("n_seg", [1, 2, 5, 32])
@pytest.mark.parametrize("n_pat", [1, 33, 64])
def test_segmented_ref_matches_per_segment_passes(n_seg, n_pat):
    """One masked union pass == n_seg independent per-frontier passes."""
    rng = np.random.default_rng(n_seg * 100 + n_pat)
    spo = jnp.asarray(_random_spo(rng, 300))
    pats = jnp.asarray(_random_bank(rng, n_pat, tombstone_frac=0.1))
    seg = jnp.asarray(
        rng.integers(0, 2 ** min(n_seg + 2, 31), size=300).astype(np.int32)
    )
    got = ref.pattern_bitmask_words_segmented_ref(spo, pats, seg, n_seg)
    want = _masked_planes(spo, pats, seg, n_seg)
    assert got.shape == (n_seg, 300, max(1, -(-n_pat // 32)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n_pat", [1, 5, 33, 64])
@pytest.mark.parametrize("n", [1, 100, TILE, TILE + 1])
def test_segmented_kernel_matches_ref(n_pat, n):
    """One Pallas invocation (interpret mode) emits all segment planes."""
    rng = np.random.default_rng(n_pat * 1000 + n)
    n_seg = 3
    spo = jnp.asarray(_random_spo(rng, n))
    pats = jnp.asarray(_random_bank(rng, n_pat, tombstone_frac=0.15))
    seg = jnp.asarray(rng.integers(0, 2**n_seg, size=n).astype(np.int32))
    got = ops.pattern_bitmask_words_segmented(
        spo, pats, seg, n_seg, use_kernel=True
    )
    want = ref.pattern_bitmask_words_segmented_ref(spo, pats, seg, n_seg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_segmented_kernel_direct_tile_aligned():
    """The raw kernel wrapper (uint32[F, W, N] layout) on an exact tile."""
    rng = np.random.default_rng(17)
    spo = jnp.asarray(_random_spo(rng, TILE))
    pats = jnp.asarray(_random_bank(rng, 40))
    seg = jnp.asarray(rng.integers(0, 4, size=TILE).astype(np.int32))
    got = triple_match_words_segmented_pallas(
        spo, pats, seg, n_seg=2, interpret=True
    )
    want = ref.pattern_bitmask_words_segmented_ref(spo, pats, seg, 2)
    assert got.shape == (2, 2, TILE)
    np.testing.assert_array_equal(
        np.asarray(jnp.swapaxes(got, 1, 2)), np.asarray(want)
    )


def test_segmented_zero_membership_and_high_bits():
    """Rows with no membership bits emit zero in every plane; bits at or
    above n_seg are ignored."""
    rng = np.random.default_rng(19)
    spo = jnp.asarray(_random_spo(rng, 200, pad_frac=0.0, vocab=3))
    pats = jnp.asarray(_random_bank(rng, 33, vocab=3))
    seg = np.zeros(200, np.int32)
    seg[::2] = 1 << 5  # only bits >= n_seg set: still zero planes
    for use_kernel in (False, True):
        got = ops.pattern_bitmask_words_segmented(
            spo, pats, jnp.asarray(seg), 2, use_kernel=use_kernel
        )
        assert not np.asarray(got).any()
    # all-members plane equals the plain words pass
    seg_all = jnp.asarray(np.full(200, 1, np.int32))
    for use_kernel in (False, True):
        got = ops.pattern_bitmask_words_segmented(
            spo, pats, seg_all, 1, use_kernel=use_kernel
        )
        want = ops.pattern_bitmask_words(spo, pats, use_kernel=use_kernel)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want))


def test_segmented_matcher_hook_one_pass():
    """A custom matcher observes ONE pass per 32-lane word — never one per
    segment — and the masked planes still match the oracle."""
    calls = []

    def spy(spo, chunk):
        calls.append(int(chunk.shape[0]))
        return ref.pattern_bitmask_ref(spo, chunk)

    rng = np.random.default_rng(13)
    spo = jnp.asarray(_random_spo(rng, 64))
    pats = jnp.asarray(_random_bank(rng, 40))
    seg = jnp.asarray(rng.integers(0, 16, size=64).astype(np.int32))
    got = ops.pattern_bitmask_words_segmented(spo, pats, seg, 4, matcher=spy)
    assert calls == [32, 8]  # one chunked pass total, not per segment
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(ref.pattern_bitmask_words_segmented_ref(spo, pats, seg, 4)),
    )


def test_segmented_rejects_bad_n_seg():
    rng = np.random.default_rng(3)
    spo = jnp.asarray(_random_spo(rng, 8))
    pats = jnp.asarray(_random_bank(rng, 4))
    seg = jnp.zeros(8, jnp.int32)
    for bad in (0, 33):
        with pytest.raises(ValueError):
            ops.pattern_bitmask_words_segmented(spo, pats, seg, bad)


# ---------------------------------------------------------------------------
# fused emit + lane routing + member mask
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "n_pat,r,nt", [(1, 1, 1), (5, 2, 3), (33, 4, 2), (64, 8, 4)]
)
def test_lane_kernel_matches_composed_pipeline(n_pat, r, nt):
    """Fused kernel == per-member multi-word emit + lane_bits_batched,
    including masked (padding) members forced to zero."""
    rng = np.random.default_rng(n_pat * 100 + r * 10 + nt)
    spo_b = np.stack([_random_spo(rng, 300) for _ in range(r)])
    pats = jnp.asarray(_random_bank(rng, n_pat, tombstone_frac=0.1))
    lanes = jnp.asarray(
        rng.integers(0, n_pat, size=(r, nt)).astype(np.int32)
    )
    active = jnp.asarray(rng.random(r) < 0.7)
    spo_j = jnp.asarray(spo_b)

    words = jnp.stack(
        [ref.pattern_bitmask_words_ref(spo_j[k], pats) for k in range(r)]
    )
    want = ops.lane_bits_batched(words, lanes, active=active)
    for use_kernel in (False, True):
        got = ops.pattern_lane_bits_batched(
            spo_j, pats, lanes, active, use_kernel=use_kernel
        )
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want), err_msg=str(use_kernel)
        )
    got_ref = ref.pattern_lane_bits_ref(spo_j, pats, lanes, active)
    np.testing.assert_array_equal(np.asarray(got_ref), np.asarray(want))


def test_lane_kernel_direct_tile_aligned():
    """The raw fused kernel on an exact tile with an inactive member."""
    rng = np.random.default_rng(23)
    r, nt = 2, 3
    spo_b = jnp.asarray(np.stack([_random_spo(rng, TILE) for _ in range(r)]))
    pats = jnp.asarray(_random_bank(rng, 40))
    lanes = jnp.asarray(rng.integers(0, 40, size=(r, nt)).astype(np.int32))
    act = jnp.asarray(np.array([[1], [0]], np.int32))
    got = triple_match_lanes_pallas(spo_b, pats, lanes, act, interpret=True)
    want = ref.pattern_lane_bits_ref(
        spo_b, pats, lanes, jnp.asarray([True, False])
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got[1]).any()  # masked member: all zeros


@pytest.mark.parametrize("r,n_active", [(4, 1), (8, 5)])
def test_lane_kernel_smem_rows_per_member(r, n_active):
    """The lane map and member mask are read from SMEM at the member grid
    index: every member over several row blocks, a multi-word bank, and
    trailing padding members (mask 0) all match the oracle."""
    rng = np.random.default_rng(31 + r)
    nt = 5
    spo_b = jnp.asarray(
        np.stack([_random_spo(rng, 2 * TILE) for _ in range(r)])
    )
    pats = jnp.asarray(_random_bank(rng, 70, tombstone_frac=0.1))
    lanes = jnp.asarray(rng.integers(0, 70, size=(r, nt)).astype(np.int32))
    active = np.arange(r) < n_active
    act = jnp.asarray(active.astype(np.int32).reshape(r, 1))
    got = triple_match_lanes_pallas(spo_b, pats, lanes, act, interpret=True)
    want = ref.pattern_lane_bits_ref(spo_b, pats, lanes, jnp.asarray(active))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got[n_active:]).any()  # padding members: zeros
    assert np.asarray(got[:n_active]).any()


def test_lane_kernel_active_none_means_all_active():
    rng = np.random.default_rng(29)
    spo_b = jnp.asarray(np.stack([_random_spo(rng, 100) for _ in range(3)]))
    pats = jnp.asarray(_random_bank(rng, 5))
    lanes = jnp.asarray(rng.integers(0, 5, size=(3, 2)).astype(np.int32))
    all_on = jnp.asarray(np.ones(3, bool))
    for use_kernel in (False, True):
        got = ops.pattern_lane_bits_batched(
            spo_b, pats, lanes, use_kernel=use_kernel
        )
        want = ops.pattern_lane_bits_batched(
            spo_b, pats, lanes, all_on, use_kernel=use_kernel
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
