"""Pallas TPU kernels for the iRap hot spots + XLA fallbacks.

Kernels (each: <name>.py kernel + ops.py wrapper + ref.py oracle):
  * triple_match — fused multi-pattern triple matching (uint32 bitset emit)
"""
from . import ops, ref, triple_match  # noqa: F401
