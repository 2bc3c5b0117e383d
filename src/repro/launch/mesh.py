"""Mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state. The dry-run entry point sets
``--xla_force_host_platform_device_count=512`` *before* importing jax.
"""

from __future__ import annotations

import jax

__all__ = ["make_data_mesh", "make_production_mesh", "make_smoke_mesh"]


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16×16 = 256 chips per pod; 2×16×16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_data_mesh() -> jax.sharding.Mesh:
    """Every attached device on the data axis: ``(pod, data, model) =
    (1, n_devices, 1)`` — the clustering engines shard rows, never features."""
    return _mesh((1, len(jax.devices()), 1), ("pod", "data", "model"))


def make_smoke_mesh() -> jax.sharding.Mesh:
    """Trivial 1×1×1 mesh so model code paths (shard_map islands included)
    run unchanged on a single CPU device."""
    return _mesh((1, 1, 1), ("pod", "data", "model"))
