"""Production mesh construction (task-spec meshes).

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init).
"""

from __future__ import annotations

from typing import Sequence

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_test_mesh", "dp_axes",
           "dp_size"]


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types: the sharding rules are
    partition-spec annotations that GSPMD propagates (jax's own default
    is Explicit)."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi_pod adds a leading 2-pod axis.

    Axis roles: ``pod`` — pure data parallel (gradient all-reduce crosses
    the DCN once per step); ``data`` — batch/FSDP; ``model`` — TP/EP.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Small mesh for CPU tests (same axis names as production)."""
    return make_mesh((data, model), ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    """Mesh axes carrying the batch (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def dp_size(mesh) -> int:
    out = 1
    for a in dp_axes(mesh):
        out *= mesh.shape[a]
    return out
