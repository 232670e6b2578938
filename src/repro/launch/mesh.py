"""Production mesh builders (TPU v5e pods).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state — dryrun.py must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_auto_mesh(shape, axes):
    """jax.make_mesh with every axis of Auto type (GSPMD-propagated)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the production axis names (CPU tests)."""
    return make_auto_mesh((1, 1), ("data", "model"))


# TPU v5e hardware constants (per chip) — roofline denominators
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link
