"""Local (single-tile) SpGEMM under arbitrary semirings (paper §4.1).

CombBLAS 2.0 ships heap-, hash-, and hybrid heap/hash column-by-column
Gustavson SpGEMM. On TPU neither a heap nor a hash table is efficient; the
faithful adaptation (DESIGN.md §4.2) keeps the paper's *structure* — an
O(flops) expansion followed by a merge whose data structure is chosen by
compression ratio — with TPU-native merges:

 - ``spgemm_esc``   expand → lax.sort → segmented reduce. Sort-based merge
                    (the heap's role: wins at LOW compression ratio, where
                    the product list is short relative to the output).
 - ``spgemm_dense`` expand into a dense accumulator tile (the hash table's
                    role: O(1) accumulation, wins at HIGH compression ratio
                    where many products collapse into few outputs) — and it
                    is the MXU-friendly path.
 - ``spgemm_auto``  the paper's hybrid: picks by estimated compression ratio.

All paths are O(flops)-expansion faithful: we never densify the *inputs* in
the ESC path, and the flops estimate (phase 1 of the paper's three-phase
scheme) is computed exactly as nnz-weighted column counts.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ..obs import recorder as _obs
from .coo import COO, SENTINEL, column_range, row_range
from .semiring import ARITHMETIC, Monoid, Semiring, dense_semiring_matmul

Array = jax.Array


def spgemm_flops(a: COO, b: COO) -> Array:
    """Phase 1 (paper §4.1): exact flops = Σ_t nnz(A(:, B.row[t])).

    Sorted fast path (DESIGN.md §4.2): when B carries the row-major tag the
    same sum is Σ_u nnz(B(A.col[u], :)) over A's entries via binary search on
    B's row pointers — no sort of either operand. Otherwise A is col-sorted
    (free when A already carries the 'col' tag).
    """
    if b.order == "row" and a.order != "col":
        start, end = row_range(b.row, jnp.where(a.mask(), a.col, SENTINEL))
        return jnp.sum(jnp.where(a.mask(), end - start, 0))
    sa = a.sort("col")
    start, end = column_range(sa.col, jnp.where(b.mask(), b.row, SENTINEL))
    return jnp.sum(jnp.where(b.mask(), end - start, 0))


def _slot_owner(off: Array, prod_cap: int) -> Array:
    """Operand entry that owns each of ``prod_cap`` product slots.

    Entry ``t`` owns slots ``off[t] .. off[t+1] - 1``, ``off`` being the
    exclusive prefix sum of the entries' product counts. Each entry marks
    its first slot with its id; where entries share an offset, all but the
    last own no slot and the max keeps the last. A running max then carries
    each id over its entry's slots: O(prod_cap) work, where a binary search
    per slot is O(prod_cap · log cap). ``off`` is nondecreasing, so the
    scatter needs no sort; offsets past ``prod_cap`` are dropped. Slots at
    or past the product count get the last owner; callers mask them.
    """
    ids = jnp.arange(off.shape[0], dtype=jnp.int32)
    marks = jnp.zeros(prod_cap, jnp.int32).at[off].max(
        ids, mode="drop", indices_are_sorted=True)
    return jax.lax.cummax(marks)


@_obs.timed("expand")
def _expand(a: COO, b: COO, sr: Semiring, prod_cap: int):
    """ESC expansion: one slot per scalar multiply (O(flops) work).

    Returns (rows, cols, vals, nprod, ok). Padding slots hold SENTINEL/zero.

    Two symmetric formulations, selected by the order tags (DESIGN.md §4.2):
      - B row-sorted (the maintained 'row' invariant): walk A's entries and
        binary-search B's row ranges. Sort-free — the fast path.
      - otherwise: col-sort A (free when tagged 'col') and walk B's entries
        against A's column ranges (the seed formulation).
    Both enumerate the identical product multiset, so downstream merge and
    overflow flags are unchanged.
    """
    if b.order == "row" and a.order != "col":
        return _expand_sorted_b(a, b, sr, prod_cap)
    sa = a.sort("col")
    sb = b
    # per-B-nonzero column ranges of A (DCSC-style binary search)
    k = jnp.where(sb.mask(), sb.row, SENTINEL)
    start, end = column_range(sa.col, k)
    cnt = jnp.where(sb.mask(), end - start, 0)
    off = jnp.cumsum(cnt) - cnt                       # exclusive prefix
    nprod = jnp.sum(cnt)
    ok = nprod <= prod_cap

    s = jnp.arange(prod_cap, dtype=jnp.int32)
    tc = _slot_owner(off, prod_cap)              # B-nonzero of slot s
    a_idx = jnp.clip(start[tc] + (s - off[tc]), 0, sa.cap - 1)
    valid = s < nprod

    out_dtype = sr.out_dtype(a.dtype, b.dtype)
    rows = jnp.where(valid, sa.row[a_idx], SENTINEL)
    cols = jnp.where(valid, sb.col[tc], SENTINEL)
    vals = sr.mul(sa.val[a_idx], sb.val[tc]).astype(out_dtype)
    vdims = vals.shape[1:]
    vals = jnp.where(valid.reshape((-1,) + (1,) * len(vdims)), vals,
                     jnp.asarray(sr.add.identity, out_dtype))
    return rows, cols, vals, nprod, ok


def _expand_sorted_b(a: COO, b: COO, sr: Semiring, prod_cap: int):
    """Sort-free expansion against a row-sorted B (the 'row' invariant path)."""
    # per-A-nonzero row ranges of B (CSR-style binary search on the tag)
    k = jnp.where(a.mask(), a.col, SENTINEL)
    start, end = row_range(b.row, k)
    cnt = jnp.where(a.mask(), end - start, 0)
    off = jnp.cumsum(cnt) - cnt                       # exclusive prefix
    nprod = jnp.sum(cnt)
    ok = nprod <= prod_cap

    s = jnp.arange(prod_cap, dtype=jnp.int32)
    tc = _slot_owner(off, prod_cap)              # A-nonzero of slot s
    b_idx = jnp.clip(start[tc] + (s - off[tc]), 0, b.cap - 1)
    valid = s < nprod

    out_dtype = sr.out_dtype(a.dtype, b.dtype)
    rows = jnp.where(valid, a.row[tc], SENTINEL)
    cols = jnp.where(valid, b.col[b_idx], SENTINEL)
    vals = sr.mul(a.val[tc], b.val[b_idx]).astype(out_dtype)
    vdims = vals.shape[1:]
    vals = jnp.where(valid.reshape((-1,) + (1,) * len(vdims)), vals,
                     jnp.asarray(sr.add.identity, out_dtype))
    return rows, cols, vals, nprod, ok


def spgemm_esc(a: COO, b: COO, sr: Semiring = ARITHMETIC, *,
               prod_cap: int, out_cap: int, order: str = "row",
               mask=None, val_pred=None) -> Tuple[COO, Array]:
    """Expand-Sort-Compress SpGEMM. Returns (C, ok_flag).

    ``mask`` (a ``mask.LocalMask``) drops expanded products before the merge
    (the §4.7 pushdown — ``out_cap`` may then be mask-sized); ``val_pred``
    drops merged entries by output value before the capacity clamp.
    """
    assert a.shape[1] == b.shape[0], (a.shape, b.shape)
    rows, cols, vals, nprod, ok = _expand(a, b, sr, prod_cap)
    shape = (a.shape[0], b.shape[1])
    if mask is not None:
        from .mask import filter_products
        rows, cols, vals = filter_products(rows, cols, vals, shape, mask,
                                           sr.add.identity)
    prods = COO(rows, cols, vals, jnp.minimum(nprod, prod_cap).astype(jnp.int32),
                shape, "none")
    d = prods.dedup(sr.add, order=order)
    if val_pred is not None:
        from .mask import apply_val_pred
        d = apply_val_pred(d, val_pred, sr.add.identity)
    # check the PRE-clamp nnz: with_cap truncates nnz to out_cap, so
    # testing after the clamp would never detect output overflow
    ok = ok & (d.nnz <= out_cap)
    return d.with_cap(out_cap, sr.add.identity), ok


def spgemm_dense(a: COO, b: COO, sr: Semiring = ARITHMETIC, *,
                 out_cap: int, order: str = "row",
                 mask=None, val_pred=None) -> Tuple[COO, Array]:
    """Dense-accumulator SpGEMM (hash-table analogue; MXU path).

    Densifies inputs into tiles and contracts with the semiring; the
    accumulator is the dense output tile (VMEM-resident on TPU via the
    ``semiring_matmul`` Pallas kernel — see kernels/). Masks apply on the
    dense accumulator (the member matrix is the mask's natural dense view).
    """
    assert a.shape[1] == b.shape[0]
    zero = sr.add.identity
    ad = a.to_dense(zero)
    bd = b.to_dense(zero)
    cd = dense_semiring_matmul(ad, bd, sr)
    if mask is not None:
        from .mask import mask_dense
        member = mask_dense(mask, (a.shape[0], b.shape[1]))
        cd = jnp.where(member, cd, jnp.asarray(zero, cd.dtype))
    if val_pred is not None:
        cd = jnp.where(val_pred(cd), cd, jnp.asarray(zero, cd.dtype))
    c = COO.from_dense(cd, out_cap, zero=zero, order=order)
    ok = jnp.sum(cd != zero) <= out_cap
    return c, ok


def compression_ratio(a: COO, b: COO, sample_out: int | None = None) -> Array:
    """flops / nnz(C) estimate. The paper's hybrid selector statistic.

    nnz(C) is estimated optimistically as min(flops, m*n) when no symbolic
    phase is run; callers with a symbolic pass can supply the true value.
    """
    fl = spgemm_flops(a, b).astype(jnp.float32)
    mn = jnp.float32(a.shape[0] * b.shape[1])
    est_nnz = jnp.minimum(fl, mn)
    return fl / jnp.maximum(est_nnz, 1.0)


def spgemm_auto(a: COO, b: COO, sr: Semiring = ARITHMETIC, *,
                prod_cap: int, out_cap: int, order: str = "row",
                dense_threshold: float = 4.0,
                dense_tile_limit: int = 1 << 22,
                mask=None, val_pred=None) -> Tuple[COO, Array]:
    """Hybrid selector (paper's hash/heap hybrid, adapted).

    Dense-accumulator path when the estimated compression ratio is high and
    the output tile fits the accumulator budget; ESC otherwise. The branch is
    resolved at trace time from static shapes when possible, otherwise via
    lax.cond so both costs stay visible to XLA.
    """
    m, n = a.shape[0], b.shape[1]
    if m * n > dense_tile_limit:
        return spgemm_esc(a, b, sr, prod_cap=prod_cap, out_cap=out_cap,
                          order=order, mask=mask, val_pred=val_pred)
    ratio = compression_ratio(a, b)

    def dense_path(_):
        c, ok = spgemm_dense(a, b, sr, out_cap=out_cap, order=order,
                             mask=mask, val_pred=val_pred)
        return c, ok

    def esc_path(_):
        c, ok = spgemm_esc(a, b, sr, prod_cap=prod_cap, out_cap=out_cap,
                           order=order, mask=mask, val_pred=val_pred)
        return c, ok

    return jax.lax.cond(ratio >= dense_threshold, dense_path, esc_path,
                        operand=None)
