"""Production meshes.

A function (not a module-level constant) so importing this module never
touches jax device state — smoke tests must keep seeing 1 CPU device.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """A mesh over ``jax.devices()`` whose axes are all Auto-typed."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the production axis names (smoke / examples)."""
    return make_mesh((1, 1), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link
HBM_BYTES = 16 * 2**30            # capacity per chip
