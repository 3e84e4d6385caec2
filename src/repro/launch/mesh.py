"""Production meshes.

Single pod : (16, 16) axes ('data', 'model')          — 256 chips (v5e pod)
Multi-pod  : (2, 16, 16) axes ('pod', 'data', 'model') — 512 chips

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax init).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """A device mesh with Auto axis types: the framework relies on GSPMD
    sharding propagation, and a bare ``jax.make_mesh`` gives Explicit
    axes."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """A 1-device mesh for CPU tests of the distributed code paths."""
    return make_mesh((1, 1), ("data", "model"))


def axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
