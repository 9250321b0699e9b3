"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any JAX
initialization, and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes the compiler shards automatically (GSPMD), the
    mode the step functions' sharding constraints are written for."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: 16x16 (256 chips) per pod; 2 pods = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (CPU smoke / examples)."""
    n = len(jax.devices())
    data = max(1, n // model)
    return _auto_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants (per chip) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link (lumped per-chip figure)
HBM_BYTES = 16 * 2**30            # 16 GiB HBM per v5e chip
