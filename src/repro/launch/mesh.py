"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so that
importing this module never touches JAX device state — the dry-run must set
XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axes: the engine's programs are explicit
    shard_maps, so no axis is in Explicit sharding mode (jax's default)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """The deployment mesh: one v5e pod 16×16 (data × model), or two pods
    2×16×16 (pod × data × model). 'model' is Hydra's pipeline-stage axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 4, multi_pod: bool = False):
    """Small mesh for CPU integration tests (fake host devices)."""
    if multi_pod:
        return make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
