"""Production mesh construction (TPU v5e pods; CPU host devices in dry-run).

Kept as functions (never module-level constants) so importing this module
never touches JAX device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the default Explicit axes type
    every intermediate's sharding, which the serve/ merges and the
    dry-run's sharding rules are not written for."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model: int = 1, data: int = None):
    """Small mesh over the first data * model local devices (all of them
    by default)."""
    n = jax.device_count()
    data = data or max(1, n // model)
    return _auto_mesh((data, model), ("data", "model"),
                      devices=jax.devices()[:data * model])


# TPU v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
