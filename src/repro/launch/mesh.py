"""Production mesh construction (TPU v5e pods; 256 chips/pod).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
boots with 512 placeholder host devices while tests/benches must see 1.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link

CHIPS_PER_POD = 256


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for multi-host-device tests (8 host devices)."""
    return jax.make_mesh(
        (n_data, n_model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )


def dp_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def n_chips(mesh) -> int:
    return int(mesh.devices.size)
