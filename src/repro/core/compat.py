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
    compiles each primitive anew on every call. Every call of the program
    runs in an ``obs`` span ``program.<f>``, ``<f>`` the function that
    defines the body: on the host it covers tracing, lowering, the
    persistent-cache read and the dispatch.
"""
from __future__ import annotations

import jax
from jax._src import source_info_util

from ..obs import recorder as _obs

# A program's ops give the line that calls the program as their source
# (the profiler's ``source`` stat, which bench/ops.json reads), not the
# wrapper below that makes the call.
source_info_util.register_exclusion(__file__)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis typed ``Auto``."""
    axis_shapes = tuple(axis_shapes)
    return jax.make_mesh(axis_shapes, tuple(axis_names), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_shapes))


class _Program:
    """A jitted shard_map program whose calls run in an ``obs`` span; every
    other attribute (``lower``, ``trace``, ...) is the jitted function's."""

    __slots__ = ("jitted", "name")

    def __init__(self, jitted, name: str):
        self.jitted = jitted
        self.name = name

    def __call__(self, *args, **kwargs):
        with _obs.span(self.name):
            return self.jitted(*args, **kwargs)

    def __getattr__(self, attr):
        return getattr(self.jitted, attr)


def program_name(f) -> str:
    """``program.<g>``, ``<g>`` the function whose body ``f`` is (or ``f``
    itself where it is defined at module level)."""
    qual = getattr(f, "__qualname__", type(f).__name__).split(".<locals>.")
    return "program." + (qual[-2] if len(qual) > 1 else qual[0])


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with ``check_vma=False``, jitted; called in the
    span ``program_name(f)``."""
    return _Program(jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                          out_specs=out_specs,
                                          check_vma=False)),
                    program_name(f))
