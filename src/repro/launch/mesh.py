"""Production mesh definitions (spec-mandated shapes).

A FUNCTION (not module-level constant) so importing never touches jax
device state; callers control XLA_FLAGS before first jax init.
"""

from __future__ import annotations

import jax


def make_auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: arrays placed on it carry no
    sharding in their types, so a sharded build's outputs stay usable by
    the single-device engines (Explicit axes, the default of current JAX,
    make their gathers raise ``ShardingTypeError``)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (host platform device count
    must already be >= prod(shape))."""
    return make_auto_mesh(shape, axes)


# TPU v5e hardware model for the roofline (EXPERIMENTS.md SSRoofline)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link (~per-direction per chip, 2D torus)
HBM_PER_CHIP = 16 * 2**30  # 16 GiB
