"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production meshes: 16x16 per pod (256 chips), and the
    2-pod 512-chip mesh with a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None):
    """Arbitrary mesh for tests / small runs (e.g. (2, 2) on 4 CPU
    devices); ``devices`` picks which (default: all of them).  Every axis
    is Auto: the sharding rules annotate logical axes and leave the
    partitioning to the compiler."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def single_device_mesh():
    return make_mesh((1, 1), ("data", "model"))
