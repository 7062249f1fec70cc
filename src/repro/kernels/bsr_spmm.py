"""Pallas TPU kernel: blocked-ELL sparse × dense SpMM (DESIGN.md §4.2/§5).

Format: block-row r stores up to K dense (bm × bk) blocks with their block
-column ids (−1 = padding) — an ELL layout at BLOCK granularity. This is
the TPU-native answer to DCSC/CSC: regular strides for the sequencer, MXU
-aligned dense blocks, sparsity expressed block-wise. The same kernel is
the MoE expert engine: a block-diagonal A makes it a grouped matmul.

Grid: (R, N/bn, K) — for each block-row and output column tile, scan the
stored blocks; the block-column id (scalar-prefetched from SMEM) drives the
x BlockSpec index_map, so only the needed x tile is pulled into VMEM per
step. Padding blocks contribute via a zeroed multiplicand (branchless).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(cols_ref, vals_ref, x_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    c = cols_ref[pl.program_id(0), k]
    blk = vals_ref[...]                    # (bm, bk)
    xt = x_ref[...]                        # (bk, bn)
    contrib = jnp.dot(blk, xt, preferred_element_type=o_ref.dtype)
    o_ref[...] += jnp.where(c >= 0, contrib, 0.0)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def bsr_spmm(block_cols, block_vals, x, *, bn: int = 128,
             interpret: bool):
    """y = A @ x. block_cols: (R,K) i32; block_vals: (R,K,bm,bk);
    x: (n_cols, n) with n_cols % bk == 0. Returns (R*bm, n)."""
    R, K, bm, bk = block_vals.shape
    n_cols, n = x.shape
    assert n_cols % bk == 0
    bn = min(bn, n)
    assert n % bn == 0
    out_dtype = jnp.promote_types(block_vals.dtype, x.dtype)
    grid = (R, n // bn, K)

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, None, bm, bk),
                             lambda r, j, k, cols: (r, k, 0, 0)),
                # x block chosen by the scalar-prefetched block-column id;
                # clamp padding (-1) to 0 — the kernel zeroes it out
                pl.BlockSpec((bk, bn),
                             lambda r, j, k, cols:
                             (jnp.maximum(cols[r, k], 0), j)),
            ],
            out_specs=pl.BlockSpec((None, bm, bn),
                                   lambda r, j, k, cols: (r, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((R, bm, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_cols, block_vals, x).reshape(R * bm, n)


def to_blocked_ell(dense, bm: int, bk: int, max_blocks: int | None = None):
    """Host helper: dense (M, N) -> (block_cols, block_vals)."""
    import numpy as np
    M, N = dense.shape
    assert M % bm == 0 and N % bk == 0
    R, C = M // bm, N // bk
    blocks = dense.reshape(R, bm, C, bk).transpose(0, 2, 1, 3)
    nz = np.asarray([[np.any(blocks[r, c]) for c in range(C)]
                     for r in range(R)])
    K = max_blocks or max(int(nz.sum(1).max()), 1)
    cols = np.full((R, K), -1, np.int32)
    vals = np.zeros((R, K, bm, bk), dense.dtype)
    for r in range(R):
        js = np.nonzero(nz[r])[0][:K]
        cols[r, :len(js)] = js
        for t, c in enumerate(js):
            vals[r, t] = blocks[r, c]
    return cols, vals
