"""Sharding utilities: mesh construction + logical-axis partitioning.

All mesh construction in the repo goes through ``make_mesh`` so every
mesh axis gets the same (``Auto``) sharding type.
"""

from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              *, axis_types=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` unless
    ``axis_types`` says otherwise."""
    shape = tuple(shape)
    axes = tuple(axes)
    if axis_types is None:
        axis_types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=axis_types)
