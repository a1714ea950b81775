"""The mesh helper shared by the launchers and the tests."""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """jax.make_mesh with every axis explicitly Auto (all our meshes are)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))
