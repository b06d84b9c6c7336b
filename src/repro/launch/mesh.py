"""Production mesh construction.

A FUNCTION, not a module constant — importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).

Single pod: 16x16 = 256 chips ("data", "model").
Multi-pod:  2x16x16 = 512 chips ("pod", "data", "model") — the "pod" axis
carries either data parallelism (default) or pipeline stages
(distributed/pipeline.py), both exercised by the dry-run.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple, axes: tuple):
    # Auto axes: the models place arrays through sharding constraints
    # (distributed/sharding.py); explicit axes, make_mesh's default since
    # jax 0.7, would make every gather of a sharded table name its output
    # sharding
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    return _mesh((data, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """Axes carrying the batch: ("pod","data") on multi-pod, else ("data",)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
