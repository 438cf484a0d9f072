"""Production mesh construction (TPU v5e target).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before the first
jax call, and everything else (smoke tests, benches) sees 1 device.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: (16, 16) = 256 chips as ("data", "model").
    Multi-pod: (2, 16, 16) = 512 chips as ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2) -> jax.sharding.Mesh:
    """Small mesh for CPU-host sharding tests (requires enough host
    devices, e.g. XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
    return _make_mesh((n_data, n_model), ("data", "model"))


def data_axes(mesh: jax.sharding.Mesh):
    """The axes the batch/cohort dimension shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis_size(mesh: jax.sharding.Mesh) -> int:
    return mesh.shape.get("model", 1)
