"""Pallas TPU kernel: segmented semiring reduce (DESIGN.md §4.4).

The merge engine's last stage — and every ``COO.reduce`` — is a segmented
reduction of a value stream by sorted segment ids. XLA's ``segment_sum``
lowers to scatter-add, which TPUs emulate serially; this kernel instead
keeps a tile of the *output* VMEM-resident as the running accumulator and
streams the input past it:

  grid = (S/bs, N/bn) with the input dimension innermost, so output tile j
  stays in VMEM across the whole input sweep (revisits = 1, like the
  matmul kernel's K axis). ``@pl.when(k == 0)`` initializes the
  accumulator to the monoid identity; a second ``@pl.when`` skips input
  blocks whose id range cannot touch this output tile — for the sorted
  streams the merge engine produces, each input block intersects O(1)
  output tiles, so the sweep does O(N·bs + S·bn) work, not O(N·S).
  Blocks are ``BLOCK`` = 1024 wide (the HBM tiling of a 1-D array).

Per surviving (tile, block) pair the segment combine is a broadcast
compare-and-reduce on the VPU (no scatter): hit[t, i] = (ids[i] == t),
acc[t] ⊕= reduce_i(values[i] where hit).

Only tagged monoids ('sum'/'min'/'max') are supported — the kernel must
name a VPU reduction. ``register()`` installs it as the backend behind
``core.semiring.segment_reduce``; anything it cannot take (untagged
monoids, vector-valued entries) falls through to the pure-JAX path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Block width of the output tiles and input blocks: XLA tiles a 1-D 32-bit
# array in HBM by 1024, and Mosaic refuses a narrower 1-D block of a
# longer array.
BLOCK = 1024

_IDENT = dict(sum=0, min=float("inf"), max=float("-inf"))


def _extreme(tag: str, dtype) -> jnp.ndarray:
    """Accumulator fill: 0 for sum, the dtype extreme for min/max."""
    if tag == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return jnp.asarray(info.max if tag == "min" else info.min, dtype)
    return jnp.asarray(_IDENT[tag], dtype)


def _kernel(s_ref, v_ref, o_ref, t_ref, *, tag: str, bs: int):
    k = pl.program_id(1)
    j = pl.program_id(0)
    t0 = j * bs

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, _extreme(tag, o_ref.dtype))
        t_ref[...] = jnp.zeros_like(t_ref)

    s = s_ref[...]
    v = v_ref[...]

    # sorted ids ⇒ this block touches segment range [min(s), max(s)] only;
    # skip blocks that cannot intersect the resident output tile
    @pl.when((jnp.min(s) < t0 + bs) & (jnp.max(s) >= t0))
    def _accumulate():
        bn = s.shape[0]
        tids = t0 + jax.lax.broadcasted_iota(jnp.int32, (bs, bn), 0)
        hit = tids == s[None, :]
        t_ref[...] = t_ref[...] + jnp.sum(hit.astype(jnp.int32), axis=1)
        fill = _extreme(tag, v.dtype)
        cand = jnp.where(hit, v[None, :], fill)
        if tag == "sum":
            o_ref[...] = o_ref[...] + jnp.sum(cand, axis=1)
        elif tag == "min":
            o_ref[...] = jnp.minimum(o_ref[...], jnp.min(cand, axis=1))
        elif tag == "max":
            o_ref[...] = jnp.maximum(o_ref[...], jnp.max(cand, axis=1))
        else:  # pragma: no cover - guarded by the wrapper
            raise ValueError(tag)


@functools.partial(jax.jit, static_argnames=("num_segments", "tag",
                                             "identity", "bs", "bn",
                                             "interpret"))
def segment_reduce_pallas(values, seg_ids, num_segments: int, tag: str,
                          *, identity=None, bs: int = BLOCK, bn: int = BLOCK,
                          interpret: bool):
    """Segmented reduce of a SORTED id stream under a tagged monoid.

    ids outside [0, num_segments) are dropped. Untouched segments hold
    ``identity`` (the monoid's declared identity — which may differ from
    the dtype extreme, e.g. MAX_INT's -(2^31)+1) for min/max, and 0 for
    sum, exactly matching ``core.semiring.segment_reduce``. The kernel
    accumulates against dtype extremes and counts touches; the identity
    substitution happens here, so touched segments keep their true
    reduction even when values lie below the declared identity.
    """
    assert values.ndim == 1, "kernel path is scalar-valued"
    assert tag in ("sum", "min", "max"), tag
    if values.dtype == jnp.bool_:
        # Mosaic has no bool reductions: reduce 0/1 as int32 (lor = max)
        ident = (tag != "max") if identity is None else identity
        out = segment_reduce_pallas(values.astype(jnp.int32), seg_ids,
                                    num_segments, tag, identity=int(ident),
                                    bs=bs, bn=bn, interpret=interpret)
        return out.astype(jnp.bool_)
    n = values.shape[0]
    s = int(num_segments)
    if s == 0:
        return jnp.zeros((0,), values.dtype)
    bs = min(bs, max(s, 8))
    bn = min(bn, max(n, 8))
    sp = -(-s // bs) * bs
    np_ = -(-n // bn) * bn
    fill = _extreme(tag, values.dtype)
    v = jnp.concatenate([values, jnp.full((np_ - n,), fill, values.dtype)]) \
        if np_ != n else values
    # out-of-range and padding ids -> sp (never matches a tile id)
    ids = jnp.where((seg_ids >= 0) & (seg_ids < s),
                    seg_ids.astype(jnp.int32), sp)
    ids = jnp.concatenate([ids, jnp.full((np_ - n,), sp, jnp.int32)]) \
        if np_ != n else ids
    grid = (sp // bs, np_ // bn)
    out, touched = pl.pallas_call(
        functools.partial(_kernel, tag=tag, bs=bs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn,), lambda j, k: (k,)),
            pl.BlockSpec((bn,), lambda j, k: (k,)),
        ],
        out_specs=[pl.BlockSpec((bs,), lambda j, k: (j,)),
                   pl.BlockSpec((bs,), lambda j, k: (j,))],
        out_shape=[jax.ShapeDtypeStruct((sp,), values.dtype),
                   jax.ShapeDtypeStruct((sp,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="segment_reduce_pallas",
    )(ids, v)
    out = out[:s]
    if tag != "sum":
        ident = jnp.asarray(fill if identity is None else identity,
                            values.dtype)
        out = jnp.where(touched[:s] > 0, out, ident)
    return out


# --------------------------------------------------------------------------
# segment_reduce backend registration (core.semiring dispatch)
# --------------------------------------------------------------------------

# The sweep visits every (output tile, input block) pair, skipped or not.
# Past this many grid steps the backend declines and XLA's segment ops
# take the reduction; a sweep that visits only the blocks each tile needs
# would lift the bound.
MAX_GRID_STEPS = 1 << 20

# Trace-time dispatch counts of the backend: segment_reduce calls the
# kernel took ("compiled" / "interpret") and calls it declined for the
# step bound ("declined"). A call is counted when its caller is traced.
CALLS = {"compiled": 0, "interpret": 0, "declined": 0}


def _backend(values, seg_ids, num_segments, tag, identity, *, interpret):
    """Adapter: returns None for inputs the kernel does not take, which
    makes ``segment_reduce`` fall through to its pure-JAX paths."""
    if values.ndim != 1 or tag not in ("sum", "min", "max"):
        return None
    if jnp.issubdtype(values.dtype, jnp.bool_) and tag == "sum":
        return None
    ident = None if tag == "sum" else identity
    if ident is not None:
        if not isinstance(ident, (int, float, bool)):
            return None                  # identity must be a static scalar
        if jnp.issubdtype(values.dtype, jnp.integer) and \
                not math.isfinite(ident):
            return None                  # inf-identity monoid on int values
    n, s = values.shape[0], int(num_segments)
    if -(-s // BLOCK) * -(-n // BLOCK) > MAX_GRID_STEPS:
        CALLS["declined"] += 1
        return None
    CALLS["interpret" if interpret else "compiled"] += 1
    return segment_reduce_pallas(values, seg_ids, s, tag, identity=ident,
                                 interpret=interpret)


def register(*, interpret: bool) -> None:
    """Install the Pallas kernel behind ``core.semiring.segment_reduce``.

    ``interpret=False`` compiles it for the TPU (what the lazy automatic
    registration does there); ``interpret=True`` runs the Pallas
    interpreter, a validation tool for CPU tests, not a fast path.
    """
    from ..core import semiring
    semiring.register_segment_reduce_backend(
        functools.partial(_backend, interpret=interpret))


def unregister() -> None:
    from ..core import semiring
    semiring.register_segment_reduce_backend(None)
