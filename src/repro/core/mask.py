"""Output masks for SpGEMM / SpMSpV — GraphBLAS C⟨M⟩ semantics, pushed down.

CombBLAS 2.0's biggest application wins (masked triangle counting, HipMCL
pruning, direction-optimized BFS) come from discarding non-mask products
*during* the multiply, not after it: the mask is a core primitive of the
GraphBLAS model, not a post-filter. This module makes that first-class:

  **MaskSpec** — the user-facing description of an output mask:
    - *structural*  keep C entries whose (row, col) is stored in a mask
                    matrix M (tile-aligned with C — no communication);
    - *complement*  keep entries NOT stored in M;
    - *pred*        sub-select which stored M entries count as members
                    (a predicate over the mask's values);
    - *vector*      for SpMSpV: membership is ``pred(m[row])`` over a dense
                    ``DistVec`` in the output's piece layout (BFS passes the
                    visited/levels vector here, complemented);
    - *val_pred*    a predicate over the OUTPUT values, applied inside the
                    merge pipeline's final compaction (fused GraphBLAS
                    select — HipMCL's prune). Unlike the pattern masks it
                    cannot shrink merge capacities (selectivity is unknown
                    until values exist), but it removes the separate prune
                    pass and keeps the returned tile small.

  **LocalMask** — the per-tile device representation: the mask tile's
  (row, col) pairs packed into ONE sorted integer key array (reusing the
  merge engine's ``pack_keys``), plus an optional ``allow`` payload for
  value-predicate sub-selection. Membership of a candidate entry is a
  vectorized sorted probe: one ``searchsorted`` against the mask keys —
  O(log nnz(M)) per candidate, no densification of the mask.

Where the filter runs (DESIGN.md §4.7): expanded products are filtered
against the LocalMask *before any merge stage* — before the per-stage kv
compaction on the engine paths (``merge.kv_from_products(mask=...)``),
before the concat-and-sort on the legacy path — so the planner can size
``out_cap`` (and therefore every stage compaction and merge-tree slot
count) from the mask-intersected nnz estimate instead of the full nnz(C).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..obs import recorder as _obs
from .coo import COO, SENTINEL
from .dist import DistSpMat, DistSpMat3D, DistVec
from .merge import _unpack, key_dtype, pack_keys

Array = jax.Array


# --------------------------------------------------------------------------
# local (per-tile) mask: sorted packed keys + membership probe
# --------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LocalMask:
    """Sorted packed-key view of one mask tile (device-resident).

    ``keys`` are ascending with dtype-max padding (the pack_keys contract);
    ``allow`` (optional) marks which mask entries count as members — kept as
    a payload aligned with ``keys`` so value-predicate masks never need a
    re-sort. ``complement`` flips membership for live candidates. ``order``
    records the key packing ('row'/'col'); probes pack candidates with the
    SAME order, so callers running a different sort order still probe
    correctly.
    """

    keys: Array                       # (mask_cap,) sorted packed keys
    allow: Optional[Array]            # (mask_cap,) bool or None
    complement: bool = dataclasses.field(
        default=False, metadata=dict(static=True))
    order: str = dataclasses.field(
        default="row", metadata=dict(static=True))


def local_mask(tile: COO, *, pred: Callable | None = None,
               complement: bool = False, order: str = "row") -> LocalMask:
    """Build a LocalMask from a canonical (deduplicated) mask tile.

    Row-sorted tiles (the §4.3 invariant) pack for free; untagged tiles pay
    one packed argsort of the mask — never of the products it will filter.
    """
    if key_dtype(tile.shape) is None:
        raise ValueError(
            "masked kernels need a packable tile key space "
            f"(shape {tile.shape}); increase the process grid "
            "(paper §1, 32-bit local indices)")
    t = tile if tile.order == order else tile.sort(order)
    keys = pack_keys(t.row, t.col, t.shape, order)
    allow = None
    if pred is not None:
        allow = jnp.asarray(pred(t.val)).reshape(t.cap, -1).all(axis=-1) \
            & t.mask()
    return LocalMask(keys, allow, complement, order)


@_obs.timed("mask_probe")
def mask_member(keys: Array, m: LocalMask) -> Array:
    """Vectorized sorted-membership probe.

    ``keys``: packed candidate keys (dtype-max = padding). Returns the KEEP
    flags under the mask semantics: padding is never kept; live candidates
    keep iff stored-and-allowed in the mask (xor complement).
    """
    kmax = jnp.asarray(jnp.iinfo(keys.dtype).max, keys.dtype)
    mk = m.keys.astype(keys.dtype)
    pos = jnp.searchsorted(mk, keys, side="left").astype(jnp.int32)
    posc = jnp.clip(pos, 0, mk.shape[0] - 1)
    live = keys != kmax
    hit = (mk[posc] == keys) & live
    if m.allow is not None:
        hit = hit & m.allow[posc]
    keep = (live & ~hit) if m.complement else hit
    return keep


def filter_products(rows: Array, cols: Array, vals: Array, shape,
                    m: LocalMask, identity):
    """Drop expanded products failing the mask (pre-merge pushdown).

    Dropped entries become canonical padding in place (SENTINEL coords,
    identity value) — downstream dedup/kv compaction already treats them as
    slack, so no re-compaction sort is needed here. Candidate keys pack
    with the MASK's order, whatever sort order the caller runs in.
    """
    keys = pack_keys(rows, cols, shape, m.order)
    keep = mask_member(keys, m)
    vdims = vals.shape[1:]
    km = keep.reshape((-1,) + (1,) * len(vdims))
    return (jnp.where(keep, rows, SENTINEL),
            jnp.where(keep, cols, SENTINEL),
            jnp.where(km, vals, jnp.asarray(identity, vals.dtype)))


def filter_tile(c: COO, m: LocalMask, identity) -> COO:
    """Post-hoc mask application to a MERGED tile (the postfilter fallback).

    Same semantics as pushing the mask through the multiply
    (``filter_products``), applied after the fact instead — the degradation
    ladder's first rung (robust/recover.py) computes C unmasked and calls
    this per tile. Stable compaction preserves the tile's order tag.
    """
    keys = pack_keys(c.row, c.col, c.shape, m.order)
    keep = mask_member(keys, m)
    return c.prune(lambda _v: keep, fill=identity)


def mask_dense(m: LocalMask, shape) -> Array:
    """Dense boolean member matrix (the dense-accumulator kernel's view)."""
    kmax = jnp.iinfo(m.keys.dtype).max
    valid = m.keys != kmax
    if m.allow is not None:
        valid = valid & m.allow
    row, col = _unpack(jnp.where(valid, m.keys, 0), shape, m.order)
    mem = jnp.zeros(shape, bool).at[row, col].max(valid, mode="drop")
    return ~mem if m.complement else mem


def apply_val_pred(c: COO, val_pred: Callable | None, identity) -> COO:
    """Fused output-value select: drop merged entries failing ``val_pred``.

    Runs after duplicate fusion (values are final) and before the caller's
    capacity clamp — the merge pipeline's last compaction stage.
    """
    if val_pred is None:
        return c
    keep = jnp.asarray(val_pred(c.val)).reshape(c.cap, -1).all(axis=-1)
    return c.prune(lambda _v: keep, fill=identity)


# --------------------------------------------------------------------------
# distributed mask description
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MaskSpec:
    """Output mask for a distributed multiply. Build via the constructors
    below (``structural`` / ``complement_of`` / ``vector_mask`` /
    ``value_mask``); exactly one pattern operand (or none, for a pure
    value mask) may be set, and it must be tile/piece-aligned with the
    output — masks never communicate.
    """

    mat: DistSpMat | None = None      # SpGEMM 2D pattern operand
    mat3: DistSpMat3D | None = None   # SpGEMM 3D pattern operand ('csub')
    vec: DistVec | None = None        # SpMSpV row-membership operand
    complement: bool = False
    pred: Callable | None = None      # over mask operand values
    val_pred: Callable | None = None  # over OUTPUT values (fused select)

    def __post_init__(self):
        operands = sum(x is not None for x in (self.mat, self.mat3, self.vec))
        if operands > 1:
            raise ValueError("MaskSpec takes at most one pattern operand")
        if operands == 0 and self.val_pred is None:
            raise ValueError("empty MaskSpec: no pattern operand, no val_pred")
        if self.vec is not None and self.pred is None:
            raise ValueError(
                "dense-vector masks need pred to define membership")

    def local(self, tile: COO) -> LocalMask:
        """LocalMask over one (already localized) mask tile."""
        return local_mask(tile, pred=self.pred, complement=self.complement)


def structural(m: DistSpMat | DistSpMat3D, *, complement: bool = False,
               pred: Callable | None = None,
               val_pred: Callable | None = None) -> MaskSpec:
    """Keep output entries stored in ``m`` (complement: NOT stored)."""
    if isinstance(m, DistSpMat3D):
        return MaskSpec(mat3=m, complement=complement, pred=pred,
                        val_pred=val_pred)
    return MaskSpec(mat=m, complement=complement, pred=pred,
                    val_pred=val_pred)


def complement_of(m: DistSpMat | DistSpMat3D, *,
                  pred: Callable | None = None,
                  val_pred: Callable | None = None) -> MaskSpec:
    return structural(m, complement=True, pred=pred, val_pred=val_pred)


def vector_mask(v: DistVec, pred: Callable, *,
                complement: bool = False) -> MaskSpec:
    """SpMSpV row mask: keep output rows where ``pred(v[row])`` (xor
    complement). ``v`` must be piece-aligned with the output vector
    (layout 'row' on the matrix grid) — BFS passes visited levels here."""
    return MaskSpec(vec=v, complement=complement, pred=pred)


def value_mask(val_pred: Callable) -> MaskSpec:
    """Pure output-value mask (fused GraphBLAS select, e.g. HipMCL prune)."""
    return MaskSpec(val_pred=val_pred)


def mask_allowed_count(mask: MaskSpec) -> int | None:
    """Host-side count of mask-admissible output slots (planner input).

    Vector masks: number of admissible rows. Pattern masks are accounted
    per-tile by ``plan_spgemm`` instead (this returns None for them).
    """
    if mask.vec is None:
        return None
    member = jnp.asarray(mask.pred(mask.vec.data))
    if mask.complement:
        member = ~member
    _obs.counter_add("host.sync.plan.mask_allowed")
    return int(jax.device_get(jnp.sum(member)))
