"""Mesh builders.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — required because smoke tests
run with the single real CPU device while the dry-run requests 512
placeholder devices before its backend starts.

Every mesh the program builds goes through :func:`make_mesh`, which
uses ``Auto`` axis types: the sharding rules in ``sharding.py`` are
written for GSPMD propagation with ``with_sharding_constraint``, and
``jax.make_mesh`` defaults to ``Explicit`` axes since jax 0.7.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh",
           "MESH_AXES"]

MESH_AXES = ("pod", "data", "model")


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod 16×16 = 256 chips, or 2-pod 2×16×16 = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh over the first ``data * model`` local
    devices."""
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[:data * model])


def data_axes(mesh) -> tuple[str, ...]:
    """Axes that shard the batch: ('pod', 'data') when present."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
