"""Production mesh construction (assignment: MULTI-POD DRY-RUN step 1).

The training paths place arrays with ``NamedSharding`` and let the
compiler propagate shardings, so their meshes use ``AxisType.Auto`` axes
(``jax.make_mesh`` defaults to Explicit axes).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh():
    """Whatever this host actually has (CPU: 1 device) -> (1, 1) mesh so the
    same pjit code paths run locally."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))
