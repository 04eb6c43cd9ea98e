"""JAX's persistent compilation cache, at one fixed place per checkout.

The broker compiles one executable per cohort shape, so a cold process pays
for every one of them. Entry points (``chip_smoke.py``,
``benchmarks/run.py``, the examples) call :func:`enable_compile_cache`
before their first compile, so processes that share a checkout — or a
machine that sets ``JAX_COMPILATION_CACHE_DIR`` — compile each shape once.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache; returns the directory it uses.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads it
    itself) and no other directory is set here. Otherwise the cache lives
    at ``<checkout>/.jax_cache``: a fixed path, never one built from a temp
    name, a PID or the time, since entries are only found again under the
    same directory. Every compile is written, however short, because the
    broker's many small set-algebra programs add up on a cold start.

    The key includes the HLO's metadata. Without it a program that differs
    only in its ``op_name``s (the cohort step's device scopes) loads an
    entry compiled without them, and :func:`repro.core.tracing.scope_table`
    reads no scope from it. Source paths in that metadata are taken
    relative to the checkout, so two checkouts of one tree share entries.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(CHECKOUT_CACHE.parent) + os.sep))
    return path
