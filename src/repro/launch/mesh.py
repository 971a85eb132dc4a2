"""Production mesh construction.

``make_production_mesh()`` is a FUNCTION (importing this module never touches
jax device state). Single-pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis carries
only data-parallel gradient reduction (DCI-friendly), ``model`` stays inside
a pod's ICI domain.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    # Auto axes: the sharding rules (sharding/rules.py) place tensors with
    # with_sharding_constraint and leave propagation to the compiler.
    # jax.make_mesh defaults to Explicit axes, under which e.g. the
    # embedding gather raises DuplicateSpecError.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """(data=1, model=n) mesh over every local device: one device for CPU
    smoke tests, the whole host (e.g. a v5e 2x2) for the sharded serve."""
    return _mesh((1, len(jax.devices())), ("data", "model"))

