"""Production meshes.

Target hardware: TPU v5e pods — 16×16 = 256 chips per pod; the multi-pod
configuration adds a leading "pod" axis over 2 pods (512 chips, ICI+DCN).

Defined as FUNCTIONS so importing this module never touches jax device
state (device count is locked on first backend init — the dry-run sets
XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis ``Auto``: the model code places
    arrays with bare ``PartitionSpec`` constraints, which only refer to
    Auto axes (``make_mesh`` defaults to ``Explicit`` axes)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, *, multi_pod: bool = False):
    """Small mesh for CI (needs only data*model [*2] host devices)."""
    if multi_pod:
        return _auto_mesh((2, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
