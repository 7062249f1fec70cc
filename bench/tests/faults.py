"""Faults planted in a cell's timed call, for the tests that see
``correct`` come out false. Each is a ``wrap`` for ``bench.run.run``:
it takes the timed call and returns the broken one."""
from __future__ import annotations

import numpy as np


def _alter(out):
    """One answer changed where it is produced."""
    if isinstance(out, tuple):                  # (rows, cols, vals)
        r, c, v = out
        v = np.array(v)
        v[len(v) // 2] += 1
        return r, c, v
    if isinstance(out, np.ndarray):             # BFS levels, one row a search
        lv = out.copy()
        flat = lv.reshape(-1)
        flat[np.flatnonzero(flat > 0)[0]] += 1
        return lv
    return out + 1                              # a count


def _halve(out):
    """Half of the answer left out."""
    if isinstance(out, tuple):
        return tuple(x[: len(x) // 2] for x in out)
    if isinstance(out, np.ndarray):             # half of the searches
        return out[: len(out) // 2]
    return out // 2


def _faulty(fault):
    def wrap(call):
        def broken(item):
            out, info = call(item)
            return fault(out), info
        return broken
    return wrap


altered = _faulty(_alter)
halved = _faulty(_halve)


def control(call):
    """The reference one step below the stated precision or guarantee, in
    the program's place (``Traffic.control``)."""
    traffic = call.__self__
    return lambda item: (traffic.control(item), {})


def no_exchange(call):
    """The exchange between chips left out: every all-gather in the call
    hands each device its own tile in every slot, as if no other device
    had sent one."""
    import jax
    import jax.numpy as jnp

    def local(x, axis_name, *, axis=0, tiled=False, **kw):
        full = gather(x, axis_name, axis=axis, tiled=tiled, **kw)
        if tiled:
            return jnp.concatenate([x] * (full.shape[axis] // x.shape[axis]),
                                   axis=axis)
        return jnp.broadcast_to(jnp.expand_dims(x, axis), full.shape)

    gather = jax.lax.all_gather

    def broken(item):
        jax.lax.all_gather = local
        try:
            return call(item)
        finally:
            jax.lax.all_gather = gather
    return broken
