"""Production meshes.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state. The dry-run entry point sets
``--xla_force_host_platform_device_count=512`` before any jax import.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
pure data parallelism (gradient all-reduce only — DCN-tolerant).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(*, multi_pod: bool = False):
    """Tiny meshes for fast local iteration (8/16 fake devices)."""
    if multi_pod:
        return make_mesh((2, 2, 4), ("pod", "data", "model"))
    return make_mesh((2, 4), ("data", "model"))
