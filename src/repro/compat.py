"""The jax API spellings this repo uses, in one place.

``shard_map`` without the replication check and meshes with explicit
Auto axis types: every caller goes through here so the tree stays on
one spelling.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` (``check_vma`` off by default)."""
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=check_vma,
    )


def make_mesh(shape, axes, devices=None):
    """A mesh with explicit Auto axis types. Over every visible device
    (``devices=None``) this is ``jax.make_mesh``, which orders devices by
    the physical topology; over an explicit device list the devices are
    laid out in the order given."""
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=types)
    return Mesh(np.asarray(devices).reshape(shape), axes, axis_types=types)
