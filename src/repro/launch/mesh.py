"""Production meshes.

Functions (never module-level constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before the
first device query.
"""
from __future__ import annotations

import math

import jax

from repro.compat import make_mesh


def _mesh(shape, axes, devices=None):
    """A mesh of ``prod(shape)`` devices. ``devices`` is the explicit
    device slice to build it on (a serving replica's own chips); by
    default the first ``prod(shape)`` visible devices. When the shape
    covers every visible device this is :func:`repro.compat.make_mesh`
    over the physical topology; a SMALLER shape builds a SUB-mesh — what
    a rank-death standby replica runs on (the shrunk ``G'-1`` subgroup
    excludes the quarantined device)."""
    n = math.prod(shape)
    if devices is not None:
        devices = list(devices)
        if len(devices) != n:
            raise ValueError(
                f"mesh shape {shape} needs {n} devices; given "
                f"{len(devices)}"
            )
        return make_mesh(shape, axes, devices=devices)
    visible = jax.devices()
    if n == len(visible):
        return make_mesh(shape, axes)
    if n > len(visible):
        raise ValueError(
            f"mesh shape {shape} needs {n} devices; only "
            f"{len(visible)} visible"
        )
    return make_mesh(shape, axes, devices=visible[:n])


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def mesh_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def make_smoke_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU smoke tests (axis names match production)."""
    return _mesh((data, model), ("data", "model"))
