"""Production mesh builders.

A function (not a module-level constant) so importing never touches jax device
state. Single pod = 16x16 = 256 chips ("data", "model"); multi-pod adds a
leading "pod" axis (2 pods = 512 chips). Batch/FSDP dims shard over the
compound ("pod", "data") axes so N-pod scaling only grows the leading axis;
gradient reductions then naturally hierarchize: reduce-scatter over intra-pod
ICI first, cross-pod DCI last.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """Auto-typed axes: the sharding rules (distributed/sharding.py) are
    GSPMD annotations that the compiler propagates, not explicit-sharding
    types, and ``jax.make_mesh`` in the installed JAX defaults to Explicit
    axes, under which those annotations fail to type-check."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — for tests."""
    return _mesh((data, model), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Compound batch/FSDP axes: ('pod','data') on multi-pod meshes."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def model_axes(mesh) -> tuple:
    return ("model",)
