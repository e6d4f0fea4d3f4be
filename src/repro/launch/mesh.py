"""Production meshes.

Single pod: (16, 16) = 256 chips, axes ('data', 'model').
Multi-pod:  (2, 16, 16) = 512 chips, axes ('pod', 'data', 'model') —
the 'pod' axis crosses the slower DCN links and carries either data
parallelism (default) or pipeline stages (PP mode).

Functions, not module constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_host_mesh", "make_mesh"]


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD propagation —
    the sharding rules place params and constrain activations; nothing
    is typed ``Explicit``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, data: int | None = None, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = jax.device_count()
    data = data if data is not None else n // model
    assert data * model <= n, f"mesh {data}x{model} > {n} devices"
    return make_mesh((data, model), ("data", "model"))
