"""Mesh context + logical sharding rules (MaxText-style, but explicit).

The production mesh axes are ``("pod", "data", "model")`` (the single-pod
mesh simply has no "pod" axis). Model code never names mesh axes directly;
it uses *logical* axes which this module maps to mesh axes:

  batch    -> ("pod", "data")     activations' leading dim / FSDP weight dim
  seq      -> "model"             sequence parallelism at layer boundaries
  tensor   -> "model"             heads / ff / vocab / experts' ff
  expert   -> "model"             expert-parallel all_to_all groups

Helpers degrade gracefully: on a trivial mesh (smoke tests, 1 CPU device)
every constraint is a no-op; axes that don't divide a dimension are dropped
rather than letting GSPMD pad silently — except where padding is explicitly
acceptable (vocab).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "use_mesh",
    "current_mesh",
    "axis_size",
    "batch_axes",
    "shard",
    "named_sharding",
    "logical_to_spec",
]


_local = threading.local()


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None):
    prev = getattr(_local, "mesh", None)
    _local.mesh = mesh
    try:
        yield mesh
    finally:
        _local.mesh = prev


def current_mesh() -> Mesh | None:
    return getattr(_local, "mesh", None)


def axis_size(name: str) -> int:
    mesh = current_mesh()
    if mesh is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def batch_axes() -> tuple[str, ...]:
    """The data-parallel mesh axes present on the current mesh."""
    mesh = current_mesh()
    if mesh is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _dim_spec(entry, size: int):
    """Resolve one logical entry to mesh axes that actually divide ``size``."""
    if entry is None:
        return None
    names = entry if isinstance(entry, tuple) else (entry,)
    resolved: list[str] = []
    total = 1
    for name in names:
        if name == "batch":
            resolved.extend(batch_axes())
        elif name in ("seq", "tensor", "expert", "model"):
            if axis_size("model") > 1:
                resolved.append("model")
        elif name in ("pod", "data"):
            mesh = current_mesh()
            if mesh is not None and name in mesh.axis_names:
                resolved.append(name)
        else:
            raise ValueError(f"unknown logical axis {name!r}")
    resolved = list(dict.fromkeys(resolved))  # dedupe, keep order
    for name in list(resolved):
        total *= axis_size(name)
    # Drop the whole entry if it doesn't divide: explicit > silent padding.
    if not resolved or size % total != 0:
        return None
    return tuple(resolved) if len(resolved) > 1 else resolved[0]


def logical_to_spec(logical: Sequence, shape: Sequence[int]) -> P:
    """Map logical axes to a PartitionSpec, dropping non-dividing axes."""
    assert len(logical) == len(shape), (logical, shape)
    return P(*[_dim_spec(l, s) for l, s in zip(logical, shape)])


def shard(x: jax.Array, *logical) -> jax.Array:
    """Apply a logical sharding constraint (no-op without a mesh)."""
    mesh = current_mesh()
    if mesh is None or np.prod(list(mesh.shape.values())) == 1:
        return x
    spec = logical_to_spec(logical, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(logical: Sequence, shape: Sequence[int]) -> NamedSharding | None:
    mesh = current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, logical_to_spec(logical, shape))
