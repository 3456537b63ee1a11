"""Mesh helpers shared by the launchers, models and distributed code.

* ``make_mesh(shapes, names)`` — ``jax.make_mesh`` with every axis typed
  ``Auto``, so sharding propagates through GSPMD as the rest of the code
  expects (annotations are hints, not explicit-sharding types).
* ``shard_map(...)`` — ``jax.shard_map`` with ``check_vma`` off by default and
  ``axis_names`` (the mesh axes that are manual inside the body) taken as any
  iterable.
* ``manual_axis_names()`` — mesh axes that are Manual at the current trace
  point, read from ``jax.sharding.get_abstract_mesh``.
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "shard_map", "manual_axis_names"]


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis auto-typed."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), axis_names, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False,
              axis_names=None):
    """``jax.shard_map``; ``axis_names`` is the set of mesh axes that are
    manual inside the body (all of them when None)."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=check_vma)
    if axis_names is not None:
        kwargs["axis_names"] = frozenset(axis_names)
    return jax.shard_map(f, **kwargs)


def manual_axis_names() -> set:
    """Mesh axes that are Manual at the current trace point (may be empty)."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or not am.axis_names:
        return set()
    return {n for n, t in zip(am.axis_names, am.axis_types)
            if t == jax.sharding.AxisType.Manual}
