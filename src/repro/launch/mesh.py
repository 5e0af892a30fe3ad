"""Production mesh construction.

IMPORTANT: functions, not module-level constants — importing this module must
never touch jax device state (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init;
everything else must see the real device count).

Every mesh axis is ``AxisType.Auto``: GSPMD partitions whatever the
programs do not shard_map by hand.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """v5e production mesh: 16x16 single pod, or 2 pods x 16 x 16."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int | None = None) -> Mesh:
    """A mesh over whatever devices actually exist (tests / examples)."""
    n = jax.device_count()
    mp = model_parallel or 1
    assert n % mp == 0
    return _auto_mesh((n // mp, mp), ("data", "model"))
