"""Production mesh construction.

Single pod: 16x16 = 256 chips, axes ('data', 'model').
Multi-pod:  2x16x16 = 512 chips, axes ('pod', 'data', 'model') — the 'pod' axis
composes with 'data' for batch/buffer sharding, so data-parallel workers span pods
and rehearsal exchange modes can choose whether to cross the inter-pod links
(DESIGN.md §2, exchange='full' vs 'pod_local').

Defined as functions (never module-level constants): importing this module must not
touch jax device state — the dry-run sets XLA_FLAGS before the first jax call.
"""
from __future__ import annotations

from repro.utils.compat import make_mesh as _compat_make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _compat_make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/benchmarks (Auto axis types where supported)."""
    return _compat_make_mesh(shape, axes)


def memory_kinds(mesh) -> set:
    """Memory kinds addressable by the mesh's devices (e.g. {'device',
    'pinned_host', 'unpinned_host'} on a TPU), for the launch log."""
    from repro.buffer.tiered import device_memory_kinds

    kinds = set()
    for dev in mesh.devices.flat:
        kinds |= device_memory_kinds(dev)
    return kinds


def describe(mesh) -> str:
    return " x ".join(f"{a}={s}" for a, s in mesh.shape.items())
