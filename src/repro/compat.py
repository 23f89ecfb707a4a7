"""The JAX APIs that drifted between releases, behind one module.

The repo runs on the installed JAX (0.9).  Four API families moved in
recent releases, and every caller in the repo reaches them through here
so that the next move is fixed in one place:

* ``jax.shard_map(..., check_vma=)`` (was ``jax.experimental.shard_map``
  with ``check_rep=``);
* the Pallas TPU compiler params, ``pltpu.CompilerParams`` (was
  ``pltpu.TPUCompilerParams``);
* ``jax.make_mesh(..., axis_types=)`` with ``jax.sharding.AxisType``;
* the scoped x64 context, ``jax.enable_x64`` (was
  ``jax.experimental.enable_x64``).

Lint rule R1 keeps everything else off the moved names.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
from jax.experimental.pallas import tpu as pltpu

__all__ = ["shard_map", "tpu_compiler_params", "make_mesh", "axis_size",
           "enable_x64"]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with keyword-only specs."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_size(name) -> int:
    """Static size of a named mesh axis, usable inside ``shard_map``."""
    return jax.lax.axis_size(name)


_COMPILER_PARAMS_FIELDS = frozenset(
    f.name for f in dataclasses.fields(pltpu.CompilerParams))


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams``; an unknown field raises, so a renamed
    ``dimension_semantics`` or ``vmem_limit_bytes`` cannot be dropped
    without notice."""
    unknown = sorted(set(kwargs) - _COMPILER_PARAMS_FIELDS)
    if unknown:
        raise TypeError(
            f"pltpu.CompilerParams of JAX {jax.__version__} has no "
            f"field(s) {unknown}; known: {sorted(_COMPILER_PARAMS_FIELDS)}")
    return pltpu.CompilerParams(**kwargs)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence] = None,
              explicit: bool = False):
    """``jax.make_mesh`` with every axis typed ``Auto`` (or ``Explicit``
    when ``explicit=True``), so the behaviour does not follow whatever
    the default axis type drifts to."""
    ty = (jax.sharding.AxisType.Explicit if explicit
          else jax.sharding.AxisType.Auto)
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(ty,) * len(tuple(axis_names)),
                         **kwargs)


def enable_x64(new_val: bool = True):
    """Context manager that turns 64-bit dtypes on (or off) inside it."""
    return jax.enable_x64(new_val)
