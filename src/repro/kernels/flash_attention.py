"""Pallas TPU kernel: causal flash attention (online softmax).

Grid: (B·H, S/bq, S/bkv) with the KV axis innermost ('arbitrary'); running
max/denominator live in VMEM scratch, the output tile is rescaled in place.
Block-causal skip: KV tiles strictly above the diagonal contribute nothing
and are branchlessly masked (on TPU the grid itself cannot be triangular;
masked tiles still cost MXU issue — the §Perf log quantifies the 2× and
the pure-JAX twin in models/layers.py mirrors the same structure).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bkv: int, scale: float, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                              # (bq, d)
    k = k_ref[0]                              # (bkv, d)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bkv", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bkv: int = 128, interpret: bool):
    """q/k/v: (B, S, H, d) — same-head attention (repeat KV for GQA first).

    Returns (B, S, H, d)."""
    B, S, H, d = q.shape
    bq, bkv = min(bq, S), min(bkv, S)
    assert S % bq == 0 and S % bkv == 0
    scale = 1.0 / np.sqrt(d)
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    kr = k.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    vr = v.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    grid = (B * H, S // bq, S // bkv)
    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bkv=bkv, scale=scale,
                          causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, d), q.dtype),
        scratch_shapes=[
            # VMEM scratch: running max, denominator, f32 accumulator
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, S, d).transpose(0, 2, 1, 3)
