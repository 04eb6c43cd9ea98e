"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps."""
import jax.numpy as jnp
import numpy as np
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref
from repro.kernels.triple_match import BLOCK_ROWS

HSETTINGS = settings(
    max_examples=25, deadline=None, suppress_health_check=[HealthCheck.too_slow]
)
PAD = ref.PAD


# ---------------------------------------------------------------------------
# triple_match
# ---------------------------------------------------------------------------
@given(
    n=st.integers(1, 3000),
    n_pat=st.integers(1, 32),
    vocab=st.integers(2, 40),
    seed=st.integers(0, 2**31 - 1),
)
@HSETTINGS
def test_triple_match_matches_ref(n, n_pat, vocab, seed):
    rng = np.random.default_rng(seed)
    spo = rng.integers(0, vocab, size=(n, 3)).astype(np.int32)
    # sprinkle PAD rows
    pad_rows = rng.random(n) < 0.1
    spo[pad_rows] = np.iinfo(np.int32).max
    pats = rng.integers(-1, vocab, size=(n_pat, 3)).astype(np.int32)
    got = ops.pattern_bitmask(jnp.asarray(spo), jnp.asarray(pats), use_kernel=True)
    want = ref.pattern_bitmask_ref(jnp.asarray(spo), jnp.asarray(pats))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_triple_match_exact_tile_boundary():
    tile = 128 * BLOCK_ROWS
    rng = np.random.default_rng(0)
    for n in (tile, tile * 2, tile - 1, tile + 1):
        spo = rng.integers(0, 9, size=(n, 3)).astype(np.int32)
        pats = jnp.asarray([[1, -1, 2], [-1, -1, -1]], jnp.int32)
        got = ops.pattern_bitmask(jnp.asarray(spo), pats, use_kernel=True)
        want = ref.pattern_bitmask_ref(jnp.asarray(spo), pats)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_triple_match_wildcard_only_pattern():
    spo = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    pats = jnp.asarray([[-1, -1, -1]], jnp.int32)
    got = ops.pattern_bitmask(spo, pats, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(got), [1, 1])


def test_pattern_bitmask_default_path_is_ref():
    spo = jnp.asarray([[0, 1, 2]], jnp.int32)
    pats = jnp.asarray([[0, -1, -1]], jnp.int32)
    got = ops.pattern_bitmask(spo, pats)  # default: XLA path on CPU
    np.testing.assert_array_equal(np.asarray(got), [1])
