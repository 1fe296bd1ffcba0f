"""Mesh construction — the one place a ``jax.sharding.Mesh`` is made.

The sharded search and model code is written for meshes whose axes are
``AxisType.Auto`` (the compiler propagates shardings through gathers,
scatters and reshapes).  ``jax.make_mesh`` defaults to Explicit axes,
under which those ops raise ``ShardingTypeError``, so every mesh in the
library, tests, examples and benchmarks comes from :func:`make_mesh`.

Single pod: (16, 16) = ("data", "model") — 256 chips.
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips; the "pod"
axis carries only data parallelism (gradient all-reduce crosses the DCN/ICI
pod boundary once per step).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh", "auto_axes", "make_production_mesh", "make_host_mesh"]


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(AxisType.Auto,) * len(axes), devices=devices,
    )


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis ``Auto``."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    mp = model_parallel
    while mp > 1 and n % mp:
        mp //= 2
    return make_mesh((n // mp, mp), ("data", "model"))
