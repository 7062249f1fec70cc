"""Pallas TPU kernel: dense-tile semiring matmul (DESIGN.md §4.2).

The paper's hash-table local SpGEMM accumulates scattered products in O(1)
per product. TPUs have no efficient scatter, but the MXU/VPU make a dense
VMEM accumulator tile the equivalent structure: the (i,j) slot of the tile
*is* the hash bucket, collision-free by construction.

Grid: (M/bm, N/bn, K/bk), K innermost so the output tile stays resident in
VMEM across the contraction (revisits = 1). The accumulator lives in the
output ref (dimension_semantics mark K as a reduction axis).

Algebras: 'plus_times' uses the MXU (jnp.dot); 'min_plus', 'max_min',
'or_and' run on the VPU via broadcast-reduce over the K tile. Anything
outside this set falls back to the pure-JAX path (the paper's
"arithmetic-only on device" rule, §5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


IDENTITY = dict(plus_times=0.0, min_plus=jnp.inf, max_min=-jnp.inf,
                or_and=False)


def _kernel(a_ref, b_ref, o_ref, *, kind: str, bk: int):
    k = pl.program_id(2)
    a = a_ref[...]
    b = b_ref[...]

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, IDENTITY[kind])

    if kind == "plus_times":
        o_ref[...] += jnp.dot(a, b, preferred_element_type=o_ref.dtype)
    elif kind == "min_plus":
        cand = jnp.min(a[:, :, None] + b[None, :, :], axis=1)
        o_ref[...] = jnp.minimum(o_ref[...], cand)
    elif kind == "max_min":
        cand = jnp.max(jnp.minimum(a[:, :, None], b[None, :, :]), axis=1)
        o_ref[...] = jnp.maximum(o_ref[...], cand)
    elif kind == "or_and":
        cand = jnp.any(a[:, :, None] & b[None, :, :], axis=1)
        o_ref[...] = jnp.logical_or(o_ref[...], cand)
    else:
        raise ValueError(kind)


@functools.partial(jax.jit, static_argnames=("kind", "bm", "bn", "bk",
                                             "interpret"))
def semiring_matmul(a, b, *, kind: str = "plus_times", bm: int = 128,
                    bn: int = 128, bk: int = 128, interpret: bool):
    """C = A ⊕.⊗ B with MXU-aligned VMEM tiling. A: (M,K), B: (K,N)."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
        "pad operands to the block size"
    if kind == "plus_times":
        out_dtype = jnp.promote_types(a.dtype, b.dtype)
    elif kind == "or_and":
        out_dtype = jnp.bool_
    else:
        out_dtype = jnp.promote_types(a.dtype, b.dtype)
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_kernel, kind=kind, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
