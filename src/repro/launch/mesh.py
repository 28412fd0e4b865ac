"""Production meshes.

Pure functions — importing this module never touches jax device state; the
mesh is built only when called (after the dry-run has set XLA_FLAGS).

Physical topology assumption (v5e): a pod is a 16x16 ICI torus (256 chips);
pods are joined over DCN.  Mesh-axis order is outermost-first =
slowest-interconnect-first, so GSPMD maps 'pod' collectives onto DCN and
keeps 'model' collectives on adjacent ICI links.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: GSPMD propagates shardings from
    the annotations, as the model code and ``sharding.constrain`` expect
    (``jax.make_mesh`` defaults to ``Explicit`` axes)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def describe(mesh) -> str:
    return " x ".join(f"{k}={v}" for k, v in mesh.shape.items())
