"""The single routing point for jax spellings that move between releases.

The repo is pinned to the jax 0.9 line (pyproject.toml).  Every spelling that
has been renamed across jax releases is funneled through here, so the next
rename is a one-file change and the analysis linter (rule `compat-api`) can
forbid the raw spellings everywhere else in src/repro:

  * ``pltpu.CompilerParams``      — Mosaic compiler parameters.
  * ``jax.make_mesh(axis_types=)`` — meshes with every axis in Auto mode.
  * ``jax.shard_map``             — with the ``check_vma`` flag.
  * ``jax.jit(in_shardings=)``    — jit with explicit placements.
"""

from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

# The public shim surface.  tests/test_compat.py pins this list so a removal
# is an API break, not a silent hole in the lint.
__all__ = ["shard_map", "jit_sharded", "tpu_compiler_params",
           "make_auto_mesh"]


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = True):
    """``jax.shard_map``; ``axis_names`` is the manual axis subset."""
    kw = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


def jit_sharded(fn, *, in_shardings=None, out_shardings=None, **kwargs):
    """``jax.jit`` with explicit in/out shardings — the sharded serving
    engine funnels every placement-carrying jit through here."""
    return jax.jit(fn, in_shardings=in_shardings,
                   out_shardings=out_shardings, **kwargs)


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams`` for a Pallas TPU kernel."""
    return pltpu.CompilerParams(**kwargs)


def make_auto_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis in Auto sharding mode."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
