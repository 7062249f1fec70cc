"""Generalized semiring support (CombBLAS 2.0 §1 "User-Defined Operations").

A semiring here is ``(add-monoid, mul)`` where the add monoid carries its
identity (the sparse "zero": entries equal to it are *not stored*) and an
optional ``tag`` naming a hardware-fast reduction. CombBLAS 2.0's headline
generalization — heterogeneous algebras, where the two inputs and the output
come from *different* sets — is supported directly: ``mul`` may accept two
different dtypes (even vector-valued elements) and produce a third; the add
monoid only ever sees the output type.

Anything jit-traceable works as ``add``/``mul``; tagged monoids additionally
get XLA's native segment reductions and the MXU path in the Pallas kernels.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..obs import recorder as _obs

Array = jax.Array

_FAST_TAGS = ("sum", "min", "max")


@dataclasses.dataclass(frozen=True)
class Monoid:
    """Associative, commutative binary op with identity.

    ``tag`` ∈ {'sum','min','max',None}: names a reduction XLA implements
    natively (used by ``segment_reduce`` fast paths and by kernels). ``None``
    selects the generic sorted segmented-scan path, which accepts *any*
    jit-traceable associative op.
    """

    op: Callable[[Any, Any], Any]
    identity: Any
    tag: str | None = None
    name: str = "monoid"

    def identity_like(self, dtype, vdims: tuple[int, ...] = ()) -> Array:
        return jnp.full(vdims, self.identity, dtype=dtype) if vdims else jnp.asarray(
            self.identity, dtype=dtype
        )

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"Monoid({self.name}, tag={self.tag})"


@dataclasses.dataclass(frozen=True)
class Semiring:
    """``add`` is a Monoid over the output set; ``mul`` maps (a, b) -> c.

    ``add.identity`` must annihilate ``mul`` (mul(zero, x) == zero) for
    implicit sparse zeros to be correct — the classical GraphBLAS contract.
    """

    add: Monoid
    mul: Callable[[Any, Any], Any]
    name: str = "semiring"

    def out_dtype(self, a_dtype, b_dtype):
        """Result dtype of ``mul`` under JAX promotion (heterogeneous OK)."""
        a = jax.eval_shape(self.mul, jax.ShapeDtypeStruct((), a_dtype),
                           jax.ShapeDtypeStruct((), b_dtype))
        return a.dtype

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"Semiring({self.name})"


# --------------------------------------------------------------------------
# Standard monoids / semirings
# --------------------------------------------------------------------------

PLUS = Monoid(jnp.add, 0, "sum", "plus")
MIN = Monoid(jnp.minimum, jnp.inf, "min", "min")
MAX = Monoid(jnp.maximum, -jnp.inf, "max", "max")
MIN_INT = Monoid(jnp.minimum, 2**31 - 1, "min", "min_int")
MAX_INT = Monoid(jnp.maximum, -(2**31) + 1, "max", "max_int")
LOR = Monoid(jnp.logical_or, False, "max", "lor")  # or == max over bool
LAND = Monoid(jnp.logical_and, True, "min", "land")
TIMES_MONOID = Monoid(jnp.multiply, 1, None, "times")


def _select2nd(a, b):
    del a
    return b


ARITHMETIC = Semiring(PLUS, jnp.multiply, "plus_times")
BOOLEAN = Semiring(LOR, jnp.logical_and, "lor_land")
MIN_PLUS = Semiring(MIN, jnp.add, "min_plus")          # tropical / shortest path
MAX_PLUS = Semiring(MAX, jnp.add, "max_plus")
MAX_MIN = Semiring(MAX, jnp.minimum, "max_min")        # bottleneck paths
MIN_MAX = Semiring(MIN, jnp.maximum, "min_max")
MIN_SELECT2ND = Semiring(MIN, _select2nd, "min_select2nd")      # BFS parents
MAX_SELECT2ND = Semiring(MAX, _select2nd, "max_select2nd")
MIN_INT_SELECT2ND = Semiring(MIN_INT, _select2nd, "min_int_select2nd")
PLUS_FIRST = Semiring(PLUS, lambda a, b: a, "plus_first")
PLUS_SECOND = Semiring(PLUS, _select2nd, "plus_second")
PLUS_PAIR = Semiring(PLUS, lambda a, b: jnp.ones((), a.dtype if hasattr(a, "dtype") else jnp.float32), "plus_pair")


def semiring(add_op, add_identity, mul_op, *, tag=None, name="user") -> Semiring:
    """Construct a user-defined semiring from plain callables."""
    return Semiring(Monoid(add_op, add_identity, tag, name + "_add"), mul_op, name)


# --------------------------------------------------------------------------
# Segment reduction under an arbitrary monoid
# --------------------------------------------------------------------------

# Accelerator backend (kernels/segreduce.py registers the Pallas
# segmented-reduce kernel here). The backend is called first for tagged
# monoids; returning None falls through to the pure-JAX paths below.
# Resolution is lazy: the first tagged segment_reduce on a TPU registers
# the compiled kernel, so CPU runs never pay the pallas import and keep
# XLA's native segment ops. Tests register the interpreted kernel
# themselves.
_SEGREDUCE_BACKEND = None
_SEGREDUCE_RESOLVED = False


def register_segment_reduce_backend(fn) -> None:
    """Install ``fn(values, seg_ids, num_segments, tag, identity) ->
    Array | None`` as the tagged-monoid segment_reduce backend (None
    uninstalls; also pins resolution so lazy auto-register won't rerun)."""
    global _SEGREDUCE_BACKEND, _SEGREDUCE_RESOLVED
    _SEGREDUCE_BACKEND = fn
    _SEGREDUCE_RESOLVED = True


def _resolve_segreduce_backend() -> None:
    global _SEGREDUCE_RESOLVED
    if _SEGREDUCE_RESOLVED:
        return
    _SEGREDUCE_RESOLVED = True
    if jax.default_backend() == "tpu":
        from ..kernels import segreduce
        segreduce.register(interpret=False)


def _segmented_scan_reduce(values: Array, seg_ids: Array, num_segments: int,
                           monoid: Monoid) -> Array:
    """Generic path: values sorted by ``seg_ids``. O(n log n) associative scan.

    combine((k1,v1),(k2,v2)) = (k2, add(v1,v2) if k1==k2 else v2) is
    associative when the sequence is sorted by key; the running value at the
    last slot of each segment is the segment reduction.
    """

    def combine(l, r):
        lk, lv = l
        rk, rv = r
        same = (lk == rk)
        if values.ndim > 1:
            samev = same.reshape(same.shape + (1,) * (values.ndim - 1))
        else:
            samev = same
        return rk, jnp.where(samev, monoid.op(lv, rv), rv)

    _, scanned = jax.lax.associative_scan(combine, (seg_ids, values))
    n = seg_ids.shape[0]
    nxt = jnp.concatenate([seg_ids[1:], jnp.full((1,), -1, seg_ids.dtype)])
    is_last = seg_ids != nxt
    out = jnp.full((num_segments,) + values.shape[1:], monoid.identity,
                   dtype=values.dtype)
    # write each segment's last scanned value; out-of-range ids are dropped
    tgt = jnp.where(is_last, seg_ids, num_segments)
    out = out.at[tgt].set(scanned, mode="drop")
    return out


@_obs.timed("segreduce")
def segment_reduce(values: Array, seg_ids: Array, num_segments: int,
                   monoid: Monoid, *, sorted_ids: bool = False) -> Array:
    """Reduce ``values`` by ``seg_ids`` under ``monoid``.

    ids >= num_segments (padding) are dropped. A registered accelerator
    backend (the Pallas segreduce kernel) takes tagged scalar streams
    first; remaining fast paths use XLA's native segment ops; the generic
    path requires (and if needed performs) a sort.
    """
    if monoid.tag in _FAST_TAGS:
        if not _SEGREDUCE_RESOLVED:
            _resolve_segreduce_backend()
        if _SEGREDUCE_BACKEND is not None:
            out = _SEGREDUCE_BACKEND(values, seg_ids, num_segments,
                                     monoid.tag, monoid.identity)
            if out is not None:
                return out
    if monoid.tag == "sum":
        return jax.ops.segment_sum(values, seg_ids, num_segments,
                                   indices_are_sorted=sorted_ids)
    if monoid.tag == "min":
        out = jax.ops.segment_min(values, seg_ids, num_segments,
                                  indices_are_sorted=sorted_ids)
        return jnp.where(_touched(seg_ids, num_segments, values), out,
                         jnp.asarray(monoid.identity, values.dtype))
    if monoid.tag == "max":
        out = jax.ops.segment_max(values, seg_ids, num_segments,
                                  indices_are_sorted=sorted_ids)
        return jnp.where(_touched(seg_ids, num_segments, values), out,
                         jnp.asarray(monoid.identity, values.dtype))
    if not sorted_ids:
        order = jnp.argsort(seg_ids)
        seg_ids = seg_ids[order]
        values = values[order]
    return _segmented_scan_reduce(values, seg_ids, num_segments, monoid)


def _touched(seg_ids, num_segments, values):
    hit = jax.ops.segment_sum(jnp.ones_like(seg_ids), seg_ids, num_segments) > 0
    if values.ndim > 1:
        hit = hit.reshape(hit.shape + (1,) * (values.ndim - 1))
    return hit


# --------------------------------------------------------------------------
# Dense semiring contraction (reference + fallback for non-MXU semirings)
# --------------------------------------------------------------------------

def dense_semiring_matmul(a: Array, b: Array, sr: Semiring,
                          k_chunk: int = 512) -> Array:
    """C[i,j] = add_k mul(A[i,k], B[k,j]) for dense A (m,k), B (k,n).

    Fast path: arithmetic semiring -> jnp.dot (MXU). Otherwise a k-chunked
    broadcast-reduce that keeps peak memory at m*n*k_chunk.
    """
    if sr.add.tag == "sum" and sr.mul in (jnp.multiply,):
        return jnp.dot(a, b)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    out_dtype = sr.out_dtype(a.dtype, b.dtype)
    ident = jnp.asarray(sr.add.identity, out_dtype)
    nchunk = max(1, -(-k // k_chunk))
    kp = nchunk * k_chunk
    a_p = jnp.pad(a, ((0, 0), (0, kp - k)), constant_values=0)
    b_p = jnp.pad(b, ((0, kp - k), (0, 0)), constant_values=0)
    # padding contributes mul(0_a, 0_b); to keep identity semantics we mask it
    kc_pow2 = 1
    while kc_pow2 < k_chunk:
        kc_pow2 *= 2

    def body(carry, idx):
        a_c = jax.lax.dynamic_slice_in_dim(a_p, idx * k_chunk, k_chunk, 1)
        b_c = jax.lax.dynamic_slice_in_dim(b_p, idx * k_chunk, k_chunk, 0)
        prod = sr.mul(a_c[:, :, None], b_c[None, :, :])  # (m, kc, n)
        kk = idx * k_chunk + jnp.arange(k_chunk)
        prod = jnp.where((kk < k)[None, :, None], prod, ident)
        # log-depth pairwise tree over the chunk axis: emits O(log k_chunk)
        # ops instead of the k_chunk-long sequential chain (a 512-op
        # compile-time blowup for non-arithmetic semirings)
        red = prod
        if kc_pow2 != k_chunk:
            red = jnp.concatenate(
                [red, jnp.full((m, kc_pow2 - k_chunk, n), ident, out_dtype)],
                axis=1)
        while red.shape[1] > 1:
            half = red.shape[1] // 2
            red = sr.add.op(red[:, :half, :], red[:, half:, :])
        return sr.add.op(carry, red[:, 0, :]), None

    init = jnp.full((m, n), ident, out_dtype)
    out, _ = jax.lax.scan(body, init, jnp.arange(nchunk))
    return out


def dense_semiring_matvec(a: Array, x: Array, sr: Semiring) -> Array:
    """y[i] = add_k mul(A[i,k], x[k]) — dense reference for SpMV tests."""
    if sr.add.tag == "sum" and sr.mul in (jnp.multiply,):
        return a @ x
    prod = sr.mul(a, x[None, :])
    out_dtype = prod.dtype
    ident = jnp.asarray(sr.add.identity, out_dtype)
    red = jnp.full((a.shape[0],), ident, out_dtype)
    def body(i, red):
        return sr.add.op(red, prod[:, i])
    return jax.lax.fori_loop(0, a.shape[1], body, red)
