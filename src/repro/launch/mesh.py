"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never
touches jax device state).  Single pod: (data=16, model=16) = 256 chips
of TPU v5e; multi-pod: (pod=2, data=16, model=16) = 512 chips, with the
batch sharded over (pod, data) and parameters over model.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: Optional[int] = None, model: int = 1) -> Mesh:
    """Small mesh over however many (host) devices exist -- used by tests
    and examples, never by the dry-run."""
    n = jax.device_count()
    if data is None:
        data = n // model
    assert data * model == n, (data, model, n)
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Every axis ``Auto``: shardings come from the step's in/out
    shardings and ``constrain`` hints, not from array types (jax 0.9's
    ``make_mesh`` defaults to ``Explicit`` axes, under which the
    replicated-vocab embedding gather raises ``ShardingTypeError``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
