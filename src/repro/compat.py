"""The JAX symbols this repo reaches through one file.

The installed JAX (0.9.0, pinned in ``pyproject.toml``) exposes:

  * ``pallas.tpu.CompilerParams`` for Pallas TPU compiler options;
  * ``jax.sharding.AxisType``, accepted by ``jax.make_mesh(axis_types=...)``;
  * ``jax.shard_map(..., check_vma=...)``.

Convention (recorded in ROADMAP.md): NO module outside this file touches a
JAX symbol that has been renamed or gated across JAX versions.  Kernels
call :func:`tpu_compiler_params`, mesh builders call :func:`make_mesh` /
:func:`mesh_axis_types`, and a future JAX upgrade means editing this one
file instead of five kernels and every test body.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax

__all__ = ["tpu_compiler_params", "mesh_axis_types", "make_mesh",
           "shard_map"]


def tpu_compiler_params(
    *, dimension_semantics: Optional[Sequence[str]] = None, **kwargs: Any
):
    """Pallas TPU compiler params; an option this JAX lacks raises."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                **kwargs)


def mesh_axis_types(n_axes: int):
    """``axis_types`` tuple for an all-Auto mesh."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices=None):
    """``jax.make_mesh`` with all-Auto axis types."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=mesh_axis_types(len(axis_names)),
                         devices=devices)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """Per-shard mapping (``check_vma`` toggles varying-axes checking)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
