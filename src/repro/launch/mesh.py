"""Mesh construction: the one helper every launcher, benchmark and test
builds its meshes with, plus the production and debug layouts.

A TPU v5e pod is modeled as a 16 x 16 = 256-chip (data, model) mesh; the
multi-pod deployment adds a leading "pod" axis (2 x 16 x 16 = 512 chips).
Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> Mesh:
    """A mesh whose axes are all ``Auto``: GSPMD partitions around the
    sharding hints (``jax.make_mesh`` would default to ``Explicit``
    axes, which the hints and the shard_map bodies here do not use).
    ``devices`` defaults to every visible device."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False) -> Mesh:
    """Small mesh for CPU integration tests (8 virtual devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


__all__ = ["make_mesh", "make_production_mesh", "make_debug_mesh"]
