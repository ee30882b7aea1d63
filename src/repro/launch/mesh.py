"""Mesh construction — the one constructor every mesh in the repo goes through.

Every ``shard_map`` body in this repo (the RAF executor, serving parity
fixture, expert-parallel MoE) is written against *auto* sharding: the
compiler propagates shardings and inserts collectives where the body's
explicit ``psum``\\ s do not.  ``jax.make_mesh`` now defaults to
``AxisType.Explicit`` axes, under which GSPMD refuses contractions over a
sharded dimension; :func:`make_mesh` states ``AxisType.Auto`` for every
axis so the code keeps the semantics it was written for.

Production shapes: single pod 256 TPU v5e chips as (data=16, model=16);
multi-pod 2 pods × 256 chips as (pod=2, data=16, model=16) — the ``pod``
axis is pure data parallelism (per DESIGN.md §5), so cross-pod traffic is
gradient all-reduce only.

Defined as functions (never module-level constants) so importing this module
never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import and only then calls these.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh

__all__ = [
    "make_mesh",
    "make_production_mesh",
    "make_abstract_mesh",
    "data_axes",
    "MODEL_AXIS",
]

MODEL_AXIS = "model"


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """A mesh over ``jax.devices()`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_abstract_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> AbstractMesh:
    """Device-free mesh (same ``Auto`` axes) for sharding-rule tables."""
    return AbstractMesh(tuple(shape), tuple(axes),
                        axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The batch-parallel axes of a mesh (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)
