"""Production mesh definition (multi-pod dry-run contract).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state.
"""

from __future__ import annotations

import jax
from jax import make_mesh
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds a leading pure-DP 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Mesh over whatever devices exist (tests / reduced smoke runs)."""
    n = len(jax.devices())
    if data * model > n:
        data, model = n, 1
    return make_mesh((data, model), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))


# TPU v5e hardware constants (roofline targets)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (per-chip effective, conservative)
HBM_BYTES = 16e9              # per chip
