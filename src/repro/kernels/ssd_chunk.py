"""Pallas TPU kernel: Mamba2 SSD intra-chunk contraction.

One grid step processes one (batch, chunk) pair entirely in VMEM:
  y_diag[q,h,p] = Σ_{k≤q} (C_q·B_k) · exp(ΔAcum_q − ΔAcum_k) · dt_k · x[k,h,p]
  state[h,p,n] = Σ_k B_k ⊗ (exp(ΔAcum_last − ΔAcum_k)·dt_k·x[k,h,p])

The (q×q) score matrix C·Bᵀ is one MXU matmul; the decay kernel L is a
VPU exp of a cumulative-sum difference. The inter-chunk state recurrence
stays outside (a lax.scan over tiny (H,P,N) states — latency-bound, not
worth a kernel). Heads are mapped to the grid so each program's working
set is (chunk × P) — VMEM-sized by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref):
    x = x_ref[0, :, 0, :].astype(jnp.float32)        # (q, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)         # (q,)
    A = a_ref[0]                                     # scalar decay
    Bm = b_ref[0].astype(jnp.float32)                # (q, N)
    Cm = c_ref[0].astype(jnp.float32)                # (q, N)
    q = x.shape[0]
    dA = dt * A
    dA_cum = jnp.cumsum(dA)
    seg = dA_cum[:, None] - dA_cum[None, :]          # (q, q)
    iq = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(iq >= ik, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    w = scores * L * dt[None, :]                     # (q, q)
    y_ref[0, :, 0, :] = jax.lax.dot(
        w, x, preferred_element_type=jnp.float32).astype(y_ref.dtype)
    decay_last = jnp.exp(dA_cum[-1] - dA_cum) * dt   # (q,)
    xw = x * decay_last[:, None]                     # (q, P)
    st = jax.lax.dot_general(xw, Bm, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    st_ref[0, 0] = st                                # (P, N)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk(xc, dtc, A, Bc, Cc, *, interpret: bool):
    """Intra-chunk SSD over all (batch·chunk, head) pairs.

    xc: (G, q, H, P); dtc: (G, q, H); A: (H,); Bc/Cc: (G, q, N) where
    G = batch·num_chunks. Returns (y (G, q, H, P), states (G, H, P, N)).
    """
    G, q, H, P = xc.shape
    N = Bc.shape[-1]
    grid = (G, H)
    y, st = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, 1, P), lambda g, h: (g, 0, h, 0)),
            pl.BlockSpec((1, q, 1), lambda g, h: (g, 0, h)),
            pl.BlockSpec((1,), lambda g, h: (h,)),
            pl.BlockSpec((1, q, N), lambda g, h: (g, 0, 0)),
            pl.BlockSpec((1, q, N), lambda g, h: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, 1, P), lambda g, h: (g, 0, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda g, h: (g, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, q, H, P), jnp.float32),
            jax.ShapeDtypeStruct((G, H, P, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xc, dtc, A, Bc, Cc)
    return y, st
