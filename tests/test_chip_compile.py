"""The served path's Pallas kernels compile for a TPU v5e at real widths.

Interpret-mode parity (tests/test_kernel_words.py, tests/test_kernels.py)
cannot see what Mosaic refuses: unaligned VMEM blocks, scalar operands in
the wrong memory space, gathers it cannot lower. Here each kernel is
AOT-compiled for a described (not attached) v5e chip at the widths the
broker runs: N = 65,536 rows, a bank of 96 patterns (W = 3 words), a
64-member cohort for the fused lanes kernel, and ``lane_refine`` in the
vmapped form the broker's segmented words pass uses. Every HLO must carry
the kernel as a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import time:
only one process may hold the TPU library, and every test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.triple_match import (
    lane_refine_pallas,
    triple_match_lanes_pallas,
    triple_match_pallas,
    triple_match_words_pallas,
    triple_match_words_segmented_pallas,
)

N = 1 << 16  # rows per pass
N_PAT = 96  # bank patterns -> W = 3 words
N_MEMBERS = 64  # fused-lanes cohort size
N_TGT = 8  # local patterns per cohort member
N_SEG = 4  # delta-chain frontier planes
N_VIRT = 32  # virtual (refined) lanes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to a persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _u32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _refine_vmapped(spo, words, parents, residual):
    # broker: one refine per frontier plane of the segmented words pass
    return jax.vmap(
        lambda plane: lane_refine_pallas(
            spo, plane, parents, residual, interpret=False
        )
    )(words)


CASES = {
    "triple_match": (
        lambda spo, pats: triple_match_pallas(spo, pats, interpret=False),
        lambda c: (_i32((N, 3), c), _i32((32, 3), c)),
    ),
    "words": (
        lambda spo, pats: triple_match_words_pallas(
            spo, pats, interpret=False
        ),
        lambda c: (_i32((N, 3), c), _i32((N_PAT, 3), c)),
    ),
    "words_segmented": (
        lambda spo, pats, seg: triple_match_words_segmented_pallas(
            spo, pats, seg, n_seg=N_SEG, interpret=False
        ),
        lambda c: (_i32((N, 3), c), _i32((N_PAT, 3), c), _i32((N,), c)),
    ),
    "lane_refine_vmapped": (
        _refine_vmapped,
        lambda c: (
            _i32((N, 3), c),
            _u32((N_SEG, N, N_PAT // 32), c),
            _i32((N_VIRT,), c),
            _i32((N_VIRT, 3), c),
        ),
    ),
    "lanes": (
        lambda spo_b, pats, lanes, act: triple_match_lanes_pallas(
            spo_b, pats, lanes, act, interpret=False
        ),
        lambda c: (
            _i32((N_MEMBERS, N, 3), c),
            _i32((N_PAT, 3), c),
            _i32((N_MEMBERS, N_TGT), c),
            _i32((N_MEMBERS, 1), c),
        ),
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = CASES[name]
    compiled = jax.jit(fn).lower(*shapes(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
