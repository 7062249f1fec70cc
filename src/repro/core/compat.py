"""The two jax mesh/shard_map entry points the engine builds on.

The repo targets the installed jax (0.9.0) only. Everything else in core/
imports these names from here, so the engine's shard_map semantics are
chosen in one place:

  - ``make_mesh``: every axis is ``AxisType.Auto`` (the SUMMA/3D programs
    place data with explicit shard_map specs, not sharding-in-types).
  - ``shard_map``: varying-manual-axes checking (``check_vma``) is off.
    The engine's bodies were written and validated under the unchecked
    semantics: windowed ``lax.cond`` skip branches return invariant
    constants, and scan carries start invariant and become varying after
    the first collective. Both are correct per-device programs that the
    checker's typing rules refuse. The mapped function is jitted: called
    eagerly, jax 0.9 runs a shard_map body one primitive at a time and
    compiles each primitive anew on every call.
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis typed ``Auto``."""
    axis_shapes = tuple(axis_shapes)
    return jax.make_mesh(axis_shapes, tuple(axis_names), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_shapes))


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with ``check_vma=False``, jitted."""
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))
