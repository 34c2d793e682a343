"""Production mesh builders (functions, not module constants: importing this
module never touches jax device state).

Every axis is ``AxisType.Auto``: the model code places activations with
``with_sharding_constraint`` and leaves the rest to GSPMD, which only Auto
axes accept (``jax.make_mesh`` defaults to Explicit axes)."""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / small fake-device runs / real slices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's production mesh: 16x16 per pod (256 chips),
    optionally 2 pods = 512 chips with a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def single_device_mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
