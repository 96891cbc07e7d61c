"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """`jax.make_mesh` with Auto axes: the sharding rules place arrays with
    `with_sharding_constraint`, which refuses the Explicit axes that
    `make_mesh` gives by default."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_small_mesh(n_data: int = 4, n_model: int = 2, *, multi_pod: bool = False):
    """Reduced mesh for in-CI sharding tests (8 host devices)."""
    if multi_pod:
        return _auto_mesh((2, n_data // 2, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    """Mesh axes that jointly shard the batch / FSDP dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    out = 1
    for n in names:
        if n in mesh.axis_names:
            out *= mesh.shape[n]
    return out
