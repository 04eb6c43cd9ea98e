"""Kernel microbenchmarks: XLA-path wall time + interpret-mode validation.

On CPU the Pallas kernels run in interpret mode (correctness only), so the
timed path is the XLA fallback; the derived column records the interpret-mode
allclose check against the oracle so every benchmark run re-validates the
kernels it ships.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

from .common import csv_row, save_json


def _time(fn, *args, iters=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run_triple_match(n=1 << 18, n_pat=8) -> str:
    rng = np.random.default_rng(0)
    spo = jnp.asarray(rng.integers(0, 1 << 20, size=(n, 3)), jnp.int32)
    pats = jnp.asarray(rng.integers(-1, 64, size=(n_pat, 3)), jnp.int32)
    f = jax.jit(lambda s, p: ref.pattern_bitmask_ref(s, p))
    dt = _time(f, spo, pats)
    # interpret-mode validation on a slice
    sl = spo[: 1 << 14]
    ok = bool(
        jnp.all(
            ops.pattern_bitmask(sl, pats, use_kernel=True)
            == ref.pattern_bitmask_ref(sl, pats)
        )
    )
    gbs = n * 12 / dt / 1e9
    save_json(
        "kernel_triple_match",
        {"n": n, "n_patterns": n_pat, "s_per_call": dt, "GBps_xla_cpu": gbs,
         "interpret_matches_ref": ok},
    )
    return csv_row(
        "kernel_triple_match", dt * 1e6,
        f"GB/s={gbs:.2f};n={n};pats={n_pat};interpret_ok={ok}",
    )

