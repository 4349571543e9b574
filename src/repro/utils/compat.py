"""Mesh and ``shard_map`` helpers shared by the step builders and the tests.

``make_mesh`` gives every axis the ``Auto`` type, and ``shard_map`` turns the
replication check off by default: the rehearsal bodies return per-worker
buffers that are varying along the data axis by construction.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

set_mesh = jax.set_mesh


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axis types."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def cost_analysis(compiled):
    """Compiled-module cost analysis as a dict (empty when unavailable)."""
    return compiled.cost_analysis() or {}


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma)
