"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips, axes
(data, model).  Multi-pod: 2x16x16 = 512 chips, axes (pod, data, model) —
the ``pod`` axis is pure data parallelism across ICI-disjoint pods (DCN).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the model code places arrays with sharding constraints,
    # which explicit axes (jax.make_mesh's default) refuse
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 2, data: int = 2, pod: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    want = model * data * pod
    if want > n:
        model = data = pod = 1
        model = min(2, n)
        data = n // model
    if pod > 1:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
