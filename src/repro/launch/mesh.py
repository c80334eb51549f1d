"""Production mesh definitions (defined as functions — importing this module
never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants used by the roofline (§Roofline).
PEAK_BF16_FLOPS = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (~per direction)
HBM_BYTES = 16 * 1024 ** 3        # 16 GiB per chip


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_for(n_devices: int, model_parallel: int = 16):
    """Elastic variant: best (data, model) mesh for an arbitrary device
    count (used by the elastic re-mesh path)."""
    tp = min(model_parallel, n_devices)
    while n_devices % tp:
        tp //= 2
    return jax.make_mesh((n_devices // tp, tp), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
