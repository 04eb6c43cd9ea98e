"""Pallas TPU kernels: fused multi-pattern triple matching (bitset emit).

The iRap hot loop scans every changeset triple against all registered triple
patterns. On TPU we stream structure-of-arrays (s, p, o) tiles through VMEM
and evaluate all patterns per tile on the VPU, emitting uint32 bitsets — one
HBM pass over the triple columns instead of one Jena index scan per pattern
(DESIGN.md §2). Three kernels share the tile layout and the unrolled
pattern-compare loop (:func:`_match_words`):

* :func:`triple_match_pallas` — the original single-word kernel: <= 32
  patterns, uint32[N] out.
* :func:`triple_match_words_pallas` — multi-word bank emit: all
  ``W = ceil(P / 32)`` bank words produced in ONE kernel invocation, i.e.
  one HBM pass over the (s, p, o) tiles regardless of bank width,
  uint32[W, N] out (the ops wrapper transposes to uint32[N, W]).
* :func:`triple_match_words_segmented_pallas` — segment-masked multi-word
  emit: the bank words are computed once per tile and composed into up to 32
  per-segment output planes by masking while still in registers (``seg``
  holds one membership bit per segment and row). The broker's delta-encoded
  frontier chain feeds it the distinct-row union of overlapping flush
  frontiers, so ``F`` frontiers cost ONE pass over the union instead of one
  stacked pass per frontier, uint32[F, W, N] out.
* :func:`lane_refine_pallas` — the interest-subsumption lattice's
  containment op: a *virtual* bank lane whose pattern is strictly contained
  by a real lane's pattern (constant where the parent has a variable) never
  occupies bank width — its words are the parent's already-emitted words
  ANDed with the cheap residual-constant compare, uint32[Wv, N] out.
* :func:`triple_match_lanes_pallas` — the broker's fully fused cohort path:
  multi-word emit PLUS bitset-lane routing PLUS the member (padding-lane)
  mask in one kernel. Each cohort member's triple tile is matched against
  the whole bank and its local pattern bits are composed in registers, so
  the intermediate uint32[N, W] bank words never touch HBM at all.

Layout / VMEM math: the ops wrappers reshape the N-vector columns to
(N // 128, 128) so tiles align with the (8, 128) vreg shape; a block is
(BLOCK_ROWS, 128) = BLOCK_ROWS * 128 triples, 3 columns * 4 B each. Per grid
step (BLOCK_ROWS = 32):

  inputs   3 * BLOCK_ROWS * 512 B                    =  48 KiB
  words    out W * BLOCK_ROWS * 512 B (word kernel)  =  16 KiB * W
  lanes    out BLOCK_ROWS * 512 B (lane kernel)      =  16 KiB

The W bank words of the multi-word block live in vector registers between
the compare loop and the store/route step — VMEM holds only the triple tile
and the final output block, so footprint grows with W only through the
(tiny, replicated) ``(32 W, 3)`` pattern operand and the word-kernel output
block. The lane-routing kernel additionally keeps the ``(R, nt)`` lane map
and the ``(R, 1)`` member mask whole in SMEM, reading one row per member
grid step as scalars. Its grid's first axis is the member axis, so the
kernel must not run under ``jax.vmap`` (which would prepend a batch axis to
the grid); the broker calls it on the explicit member-stacked cohort.

Every kernel takes ``interpret`` as a required keyword: the ops wrappers
pass ``interpret=not on_tpu``, so no caller can run the interpreter on the
chip by omission. Each ``pallas_call`` is named after its wrapper, which is
the name its operation carries in a device trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PAD = np.int32(np.iinfo(np.int32).max)
WILDCARD = np.int32(-1)

BLOCK_ROWS = 32  # x 128 lanes = 4096 triples per block


def _match_words(pat_ref, s, p, o, n_pat: int):
    """All ``ceil(n_pat / 32)`` uint32 bank words for one (s, p, o) tile.

    Static unroll over the whole bank: every pattern compare reuses the same
    three VMEM-resident columns, so the full multi-word emit costs one pass
    over the tile. Returns a list of per-word uint32 accumulators (vreg
    resident). Tombstoned / padding bank rows are all-PAD and can never
    match a valid triple (PAD rows themselves are masked via ``valid``).
    """
    valid = s != PAD
    n_words = max(1, -(-n_pat // 32))
    accs = []
    for w in range(n_words):
        acc = jnp.zeros(s.shape, dtype=jnp.uint32)
        for j in range(w * 32, min(n_pat, w * 32 + 32)):
            ps = pat_ref[j, 0]
            pp = pat_ref[j, 1]
            po = pat_ref[j, 2]
            m = (
                valid
                & ((ps == WILDCARD) | (s == ps))
                & ((pp == WILDCARD) | (p == pp))
                & ((po == WILDCARD) | (o == po))
            )
            acc = acc | (m.astype(jnp.uint32) << (j - w * 32))
        accs.append(acc)
    return accs


def _kernel(pat_ref, s_ref, p_ref, o_ref, out_ref, *, n_pat: int):
    out_ref[...] = _match_words(pat_ref, s_ref[...], p_ref[...], o_ref[...], n_pat)[0]


def _kernel_words(pat_ref, s_ref, p_ref, o_ref, out_ref, *, n_pat: int):
    accs = _match_words(pat_ref, s_ref[...], p_ref[...], o_ref[...], n_pat)
    for w, acc in enumerate(accs):
        out_ref[w] = acc


def _kernel_words_segmented(
    pat_ref, seg_ref, s_ref, p_ref, o_ref, out_ref, *, n_pat: int, n_seg: int
):
    """Segment-masked multi-word emit: one match, ``n_seg`` composed planes.

    The bank words for the tile are computed ONCE (vreg resident) by the
    shared compare loop; each segment's output plane is the same words with
    the rows outside that segment forced to zero (``seg`` carries one
    membership bit per segment and row). n_seg overlapping row subsets
    therefore cost one pass over the (s, p, o) tile, not n_seg.
    """
    accs = _match_words(pat_ref, s_ref[...], p_ref[...], o_ref[...], n_pat)
    seg = seg_ref[...]
    zero = jnp.zeros(seg.shape, dtype=jnp.uint32)
    for f in range(n_seg):
        m = ((seg >> f) & 1) == 1
        for w, acc in enumerate(accs):
            out_ref[f, w] = jnp.where(m, acc, zero)


def _kernel_lanes(
    pat_ref,
    lanes_ref,
    act_ref,
    s_ref,
    p_ref,
    o_ref,
    out_ref,
    *,
    n_pat: int,
    n_tgt: int,
):
    """Fused bank emit + lane routing + member mask for ONE cohort member.

    The whole ``(R, n_tgt)`` lane map and ``(R, 1)`` member mask sit in
    SMEM and are read as scalars at row ``program_id(0)`` (the member grid
    axis; Mosaic refuses them as (1, n_tgt) / (1, 1) VMEM blocks). Bank
    words stay in registers and each local pattern bit is selected out of
    its word via a static unroll over the W words (lane values are traced,
    so the word choice is a select chain, not a dynamic index).
    """
    k = pl.program_id(0)
    accs = _match_words(pat_ref, s_ref[0], p_ref[0], o_ref[0], n_pat)
    local = jnp.zeros(s_ref[0].shape, dtype=jnp.uint32)
    for t in range(n_tgt):
        lane = lanes_ref[k, t]
        wi = lane // 32
        sh = (lane % 32).astype(jnp.uint32)
        word = accs[0]
        for w in range(1, len(accs)):
            word = jnp.where(wi == w, accs[w], word)
        local = local | (((word >> sh) & jnp.uint32(1)) << jnp.uint32(t))
    active = act_ref[k, 0] != 0
    out_ref[0] = jnp.where(active, local, jnp.zeros_like(local))


@functools.partial(jax.jit, static_argnames=("interpret",))
def triple_match_pallas(spo: jax.Array, patterns: jax.Array, *, interpret: bool) -> jax.Array:
    """uint32[N] pattern bitset for lex-agnostic (N, 3) int32 triples.

    N must be a multiple of 128 * BLOCK_ROWS (the ops wrapper pads).
    """
    n = spo.shape[0]
    n_pat = patterns.shape[0]
    assert n % (128 * BLOCK_ROWS) == 0, n
    rows = n // 128
    s2 = spo[:, 0].reshape(rows, 128)
    p2 = spo[:, 1].reshape(rows, 128)
    o2 = spo[:, 2].reshape(rows, 128)

    grid = (rows // BLOCK_ROWS,)
    col_spec = pl.BlockSpec((BLOCK_ROWS, 128), lambda i: (i, 0))
    pat_spec = pl.BlockSpec((n_pat, 3), lambda i: (0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, n_pat=n_pat),
        grid=grid,
        in_specs=[pat_spec, col_spec, col_spec, col_spec],
        out_specs=col_spec,
        out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.uint32),
        interpret=interpret,
        name="triple_match_pallas",
    )(patterns, s2, p2, o2)
    return out.reshape(n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def triple_match_words_pallas(
    spo: jax.Array, patterns: jax.Array, *, interpret: bool
) -> jax.Array:
    """uint32[W, N] multi-word bank bitset in one kernel invocation.

    ``W = ceil(P / 32)`` (min 1): word ``w`` carries the match bits of
    ``patterns[32w : 32w + 32]``. One HBM pass over the (s, p, o) tiles
    regardless of bank width; N must be a multiple of 128 * BLOCK_ROWS.
    """
    n = spo.shape[0]
    n_pat = patterns.shape[0]
    n_words = max(1, -(-n_pat // 32))
    assert n % (128 * BLOCK_ROWS) == 0, n
    rows = n // 128
    s2 = spo[:, 0].reshape(rows, 128)
    p2 = spo[:, 1].reshape(rows, 128)
    o2 = spo[:, 2].reshape(rows, 128)

    grid = (rows // BLOCK_ROWS,)
    col_spec = pl.BlockSpec((BLOCK_ROWS, 128), lambda i: (i, 0))
    pat_spec = pl.BlockSpec((max(1, n_pat), 3), lambda i: (0, 0))
    out_spec = pl.BlockSpec((n_words, BLOCK_ROWS, 128), lambda i: (0, i, 0))

    out = pl.pallas_call(
        functools.partial(_kernel_words, n_pat=n_pat),
        grid=grid,
        in_specs=[pat_spec, col_spec, col_spec, col_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n_words, rows, 128), jnp.uint32),
        interpret=interpret,
        name="triple_match_words_pallas",
    )(patterns, s2, p2, o2)
    return out.reshape(n_words, n)


@functools.partial(jax.jit, static_argnames=("n_seg", "interpret"))
def triple_match_words_segmented_pallas(
    spo: jax.Array,
    patterns: jax.Array,
    seg: jax.Array,
    *,
    n_seg: int,
    interpret: bool,
) -> jax.Array:
    """uint32[n_seg, W, N] segment-masked bank bitset in one invocation.

    ``seg``: int32[N] membership bitmap (bit ``f`` = row belongs to segment
    ``f``; bits >= ``n_seg`` ignored). Each of the ``n_seg`` output planes
    equals :func:`triple_match_words_pallas` with non-member rows zeroed,
    but the pattern-compare loop runs ONCE per tile — the broker's
    delta-encoded frontier chain uses this to match the distinct-row union
    of overlapping flush frontiers a single time and compose the
    per-frontier words by masking in registers. ``n_seg <= 32``; N must be
    a multiple of 128 * BLOCK_ROWS.
    """
    n = spo.shape[0]
    n_pat = patterns.shape[0]
    n_words = max(1, -(-n_pat // 32))
    assert 1 <= n_seg <= 32, n_seg
    assert n % (128 * BLOCK_ROWS) == 0, n
    rows = n // 128
    s2 = spo[:, 0].reshape(rows, 128)
    p2 = spo[:, 1].reshape(rows, 128)
    o2 = spo[:, 2].reshape(rows, 128)
    g2 = seg.astype(jnp.int32).reshape(rows, 128)

    grid = (rows // BLOCK_ROWS,)
    col_spec = pl.BlockSpec((BLOCK_ROWS, 128), lambda i: (i, 0))
    pat_spec = pl.BlockSpec((max(1, n_pat), 3), lambda i: (0, 0))
    out_spec = pl.BlockSpec(
        (n_seg, n_words, BLOCK_ROWS, 128), lambda i: (0, 0, i, 0)
    )

    out = pl.pallas_call(
        functools.partial(
            _kernel_words_segmented, n_pat=n_pat, n_seg=n_seg
        ),
        grid=grid,
        in_specs=[pat_spec, col_spec, col_spec, col_spec, col_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n_seg, n_words, rows, 128), jnp.uint32),
        interpret=interpret,
        name="triple_match_words_segmented_pallas",
    )(patterns, g2, s2, p2, o2)
    return out.reshape(n_seg, n_words, n)


def _kernel_refine(
    par_ref,
    res_ref,
    w_ref,
    s_ref,
    p_ref,
    o_ref,
    out_ref,
    *,
    n_virt: int,
    n_words_in: int,
):
    """Containment-DAG refinement: parent word bit AND residual compare.

    Virtual slot ``v`` gathers its parent bank lane's bit out of the
    already-computed real-bank words (lane values are traced, so the word
    choice is a select chain over the ``n_words_in`` input planes) and ANDs
    the child's residual constant compares — the three-term predicate the
    parent left unconstrained. Dead slots (parent -1) are forced to zero.
    PAD rows need no extra mask: the parent bit is already zero for them.
    """
    s = s_ref[...]
    p = p_ref[...]
    o = o_ref[...]
    n_out = max(1, -(-n_virt // 32))
    for wo in range(n_out):
        acc = jnp.zeros(s.shape, dtype=jnp.uint32)
        for v in range(wo * 32, min(n_virt, wo * 32 + 32)):
            par = par_ref[v, 0]
            wi = par // 32
            sh = (par % 32).astype(jnp.uint32)
            word = w_ref[0]
            for w in range(1, n_words_in):
                word = jnp.where(wi == w, w_ref[w], word)
            pbit = (word >> sh) & jnp.uint32(1)
            rs = res_ref[v, 0]
            rp = res_ref[v, 1]
            ro = res_ref[v, 2]
            m = (
                (pbit == jnp.uint32(1))
                & (par >= 0)
                & ((rs == WILDCARD) | (s == rs))
                & ((rp == WILDCARD) | (p == rp))
                & ((ro == WILDCARD) | (o == ro))
            )
            acc = acc | (m.astype(jnp.uint32) << (v - wo * 32))
        out_ref[wo] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def lane_refine_pallas(
    spo: jax.Array,
    words: jax.Array,
    parents: jax.Array,
    residual: jax.Array,
    *,
    interpret: bool,
) -> jax.Array:
    """uint32[Wv, N] refined virtual-lane words from real-bank words.

    ``words``: uint32[N, W] real-bank planes (PAD rows must already be
    zero, as :func:`triple_match_words_pallas` guarantees); ``parents``:
    int32[Vp] parent bank lane per virtual slot (-1 = dead); ``residual``:
    int32[Vp, 3] child constants in the parent's variable slots (WILDCARD
    elsewhere). Bit-identical to matching the materialized child patterns
    with the words kernel, at residual-compare cost — no bank-width pass.
    ``Wv = ceil(Vp / 32)``; N must be a multiple of 128 * BLOCK_ROWS.
    """
    n = spo.shape[0]
    vp = parents.shape[0]
    n_words_in = words.shape[1]
    n_out = max(1, -(-vp // 32))
    assert n % (128 * BLOCK_ROWS) == 0, n
    rows = n // 128
    s2 = spo[:, 0].reshape(rows, 128)
    p2 = spo[:, 1].reshape(rows, 128)
    o2 = spo[:, 2].reshape(rows, 128)
    w2 = words.T.reshape(n_words_in, rows, 128)
    par2 = parents.reshape(vp, 1)

    grid = (rows // BLOCK_ROWS,)
    col_spec = pl.BlockSpec((BLOCK_ROWS, 128), lambda i: (i, 0))
    par_spec = pl.BlockSpec((vp, 1), lambda i: (0, 0))
    res_spec = pl.BlockSpec((vp, 3), lambda i: (0, 0))
    w_spec = pl.BlockSpec((n_words_in, BLOCK_ROWS, 128), lambda i: (0, i, 0))
    out_spec = pl.BlockSpec((n_out, BLOCK_ROWS, 128), lambda i: (0, i, 0))

    out = pl.pallas_call(
        functools.partial(
            _kernel_refine, n_virt=vp, n_words_in=n_words_in
        ),
        grid=grid,
        in_specs=[par_spec, res_spec, w_spec, col_spec, col_spec, col_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n_out, rows, 128), jnp.uint32),
        interpret=interpret,
        name="lane_refine_pallas",
    )(par2, residual, w2, s2, p2, o2)
    return out.reshape(n_out, n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def triple_match_lanes_pallas(
    spo_b: jax.Array,
    patterns: jax.Array,
    lanes: jax.Array,
    active: jax.Array,
    *,
    interpret: bool,
) -> jax.Array:
    """uint32[R, N] fused multi-word emit + lane routing for a cohort.

    ``spo_b``: int32[R, N, 3] member-stacked triple rows; ``lanes``:
    int32[R, nt] member k's local pattern j reads bank lane ``lanes[k, j]``;
    ``active``: int32[R, 1] member mask (0 = padding lane, bits forced to
    zero). Equivalent to emitting the bank words per member and routing via
    :func:`repro.kernels.ops.lane_bits_batched`, minus the HBM round trip of
    the intermediate words. N must be a multiple of 128 * BLOCK_ROWS.
    """
    r, n = spo_b.shape[0], spo_b.shape[1]
    n_pat = patterns.shape[0]
    n_tgt = lanes.shape[1]
    assert n % (128 * BLOCK_ROWS) == 0, n
    rows = n // 128
    s2 = spo_b[:, :, 0].reshape(r, rows, 128)
    p2 = spo_b[:, :, 1].reshape(r, rows, 128)
    o2 = spo_b[:, :, 2].reshape(r, rows, 128)

    grid = (r, rows // BLOCK_ROWS)
    col_spec = pl.BlockSpec((1, BLOCK_ROWS, 128), lambda k, i: (k, i, 0))
    pat_spec = pl.BlockSpec((max(1, n_pat), 3), lambda k, i: (0, 0))
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        functools.partial(_kernel_lanes, n_pat=n_pat, n_tgt=n_tgt),
        grid=grid,
        in_specs=[pat_spec, smem_spec, smem_spec, col_spec, col_spec, col_spec],
        out_specs=col_spec,
        out_shape=jax.ShapeDtypeStruct((r, rows, 128), jnp.uint32),
        interpret=interpret,
        name="triple_match_lanes_pallas",
    )(patterns, lanes, active, s2, p2, o2)
    return out.reshape(r, n)
