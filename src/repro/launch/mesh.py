"""Mesh construction (see MULTI-POD DRY-RUN in the system spec).

FUNCTIONS, not module constants: importing this module never touches jax
device state.  Every mesh in the repo is built through :func:`make_mesh`,
which pins each axis to ``AxisType.Auto``: ``jax.make_mesh`` defaults to
Explicit axes, under which indexing a sharded pool (``_slice_shard``) and
``with_sharding_constraint`` are refused.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis Auto (GSPMD-propagated sharding)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Small mesh over however many (real or fake) local devices exist."""
    n = len(jax.devices())
    data = max(n // model_axis, 1)
    return make_mesh((data, model_axis), ("data", "model"))
