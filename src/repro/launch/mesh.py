"""Production meshes. Importing this module never touches jax device state —
meshes are built lazily inside the factory functions."""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices):
    """``jax.make_mesh`` with every axis in Auto mode, so GSPMD places what
    the program does not pin (Explicit axes make vmap and friends demand
    matching shardings)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

    The process must expose enough devices (the dry-run sets
    ``--xla_force_host_platform_device_count=512`` before any jax import).
    Single-pod uses the first 256 of whatever is available."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 2 * 16 * 16 if multi_pod else 16 * 16
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return make_mesh(shape, axes, devices[:n])


def make_debug_mesh(*, multi_pod: bool = False, data: int = 2, model: int = 2):
    """Tiny mesh for tests (e.g. 8 forced host devices)."""
    shape = (2, data, model) if multi_pod else (data, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    return make_mesh(shape, axes, jax.devices()[:n])
