"""Distributed SpGEMM: 2D SUMMA (rotation + all-gather) and 3D CA (paper §3.2).

2D (paper's Sparse SUMMA, hardware-adapted — DESIGN.md §4.1, §4.8):
  - schedule='rotate' (variant='rotation', default): Cannon-style systolic
    schedule. One multi-axis collective-permute performs the initial skew,
    then q stages of neighbor rotation (A left along 'col', B up along 'row')
    each followed by a local O(flops) expansion. Communication volume per
    device equals the paper's Table 1 bandwidth term O(nnz(A+B)/√p); the
    primitive is the torus-native permute instead of an MPI broadcast.
  - schedule='alltoall' (variant='allgather'): the literal broadcast
    formulation — each device all-gathers its process row of A and process
    column of B in one shot, then runs the q local multiplies. Same volume,
    √q-deeper buffers (the memory/latency tradeoff the paper describes for
    2D SUMMA at scale).
  - schedule='bcast' / per-stage tuple (variant='hybrid'): SUMMA stage order
    k=s with a masked-psum broadcast per stage — O(1) extra buffering like
    'rotate' but addressable per stage, so a tuple schedule can batch its
    sparsest stages into ONE fused eager exchange ('gather' entries, the
    all-to-all leg of McFarland et al. arXiv 2504.06408) while streaming the
    dense stages as per-stage broadcasts.

Overlap (§4.8): by default (overlap=True) every stage loop is double
buffered — stage s+1's ppermute/psum is issued before stage s's local
expand+mask-filter+merge, so XLA can run the collective under the compute.
overlap=False reproduces the bulk-synchronous MPI model by pinning each
stage's merge outputs before the next exchange's inputs with an
optimization_barrier. Both orders run identical per-stage math, so their
results are bitwise equal (the overlap toggle is a pure scheduling choice).

Compressed exchanges (compress='int8'): value payloads are quantized to
per-tile symmetric int8 at the host boundary and travel the wire compressed
(the scale rides along in the fused tree permute); each stage dequantizes
just before expansion. Error feedback across spgemm_2d_batched batches
re-injects the quantization residual of A (re-sent every batch) so the
error does not accumulate. Requires floating values and an additive
identity of 0 (padding must survive the round trip). The int8 payload is
bracketed by the 'dist.compressed_exchange' audit/fault site.

3D CA (paper Fig 2): inputs on a (L, q, q) grid, A column-sliced and B
row-sliced across layers. Each layer runs an independent 2D multiply over a
contraction dim shrunk by L (broadcast/rotation volume shrinks by the
paper's √c factor on the smaller communicator), then one inter-layer
all-to-all scatters partial C column sub-blocks and a local semiring merge
forms C distributed like A. With overlap=True the three field exchanges are
fused into one tree-level all_to_all issued as soon as the radix placement
finishes; overlap=False barriers the placement and exchanges per field.

Merging (paper §5 "binary merge scheme", DESIGN.md §4.4): every stage
product buffer is compacted (per-stage packed-key dedup to
min(prod_cap, out_cap) slots) and then combined through the merge engine:

  merge='deferred'    pairwise merge tree over the q compacted stage
                      buffers — O(n) rank-placement merges, never a sort of
                      the q·prod_cap concatenation (and never of its
                      padding slack).
  merge='incremental' O(n) merge_sorted of each compacted stage into the
                      row-sorted accumulator (less memory, more steps).
  merge='sort'        the seed behavior — concatenate all q padded stage
                      buffers and dedup once. Kept for tiny problems (the
                      planner picks it when q·prod_cap is small) and as the
                      benchmark baseline.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..dist.compression import quantize_payload
from ..obs import recorder as _obs
from ..robust import audit as _audit
from .compat import shard_map
from .coo import COO, SENTINEL
from .dist import DistSpMat, DistSpMat3D, specs_of
from .local_spgemm import _expand
from .mask import LocalMask, MaskSpec, apply_val_pred, filter_products
from .merge import (key_dtype, kv_empty, kv_from_products, kv_merge2,
                    kv_to_coo, kv_tree, merge_stage_products, pack_keys)
from .semiring import ARITHMETIC, Semiring

Array = jax.Array

# variant (planner-facing algorithm family) -> whole-sweep schedule
_VARIANT_SCHEDULE = {"rotation": "rotate", "allgather": "alltoall",
                     "hybrid": "bcast"}


def _schedule_from(variant, schedule, q):
    """Resolve the (variant, schedule) pair to an executable schedule.

    A schedule is either 'rotate' (whole-sweep Cannon), 'alltoall' (one-shot
    gather of all stage operands), 'bcast' (per-stage masked-psum broadcast,
    SUMMA stage order), or a length-q tuple of 'bcast'|'gather' picking the
    exchange per stage ('gather' stages are batched into one fused eager
    exchange). Cannon's rotation cannot be mixed per stage: after the skew,
    device (i,j) multiplies k=(i+j+s) mod q at stage s — a different k per
    device — while a broadcast stage needs the same k everywhere, so
    'rotate' is only available as a whole sweep (DESIGN.md §4.8).
    """
    if schedule is None:
        try:
            return _VARIANT_SCHEDULE[variant]
        except KeyError:
            raise ValueError(f"unknown SpGEMM variant {variant!r}") from None
    if isinstance(schedule, (tuple, list)):
        sched = tuple(schedule)
        if len(sched) != q:
            raise ValueError(
                f"per-stage schedule has {len(sched)} entries for q={q}")
        bad = [s for s in sched if s not in ("bcast", "gather")]
        if bad:
            raise ValueError(f"per-stage schedule entries must be "
                             f"'bcast'|'gather', got {bad!r}")
        return sched
    if schedule not in ("rotate", "alltoall", "bcast"):
        raise ValueError(f"unknown schedule {schedule!r}")
    return schedule


@lru_cache(maxsize=None)
def _cannon_perms(q, skew_a=True):
    """(src, dst) pairs on a row-major q×q grid for the initial skew.

    Memoized on q: the table is loop-invariant and trace-time constant, so
    it is built once per grid size instead of once per traced permute.
    """
    if skew_a:  # A(i, j) -> A(i, (j - i) mod q)
        return tuple((r * q + c, r * q + (c - r) % q)
                     for r in range(q) for c in range(q))
    # B(i, j) -> B((i - j) mod q, j)
    return tuple((r * q + c, ((r - c) % q) * q + c)
                 for r in range(q) for c in range(q))


@lru_cache(maxsize=None)
def _shift_perm(q, axis_len, left=True):
    return tuple((s, (s - 1) % axis_len) if left else (s, (s + 1) % axis_len)
                 for s in range(axis_len))


def _tile_permute(tile: COO, axes, perm, scale=None):
    """Move whole tiles between devices in ONE tree-level ppermute.

    All four fields (and the int8 dequantization scale, when the payload is
    compressed) travel in a single collective-permute instead of four — one
    launch, one fusion boundary. Each tile keeps its internal order.
    Returns (tile, scale); scale is None when no scale was passed.
    """
    fields = (tile.row, tile.col, tile.val, tile.nnz)
    with _obs.span("exchange"):
        if scale is None:
            r, c, v, n = jax.lax.ppermute(fields, axes, perm)
            return COO(r, c, v, n, tile.shape, tile.order), None
        r, c, v, n, s = jax.lax.ppermute(fields + (scale,), axes, perm)
    return COO(r, c, v, n, tile.shape, tile.order), s


def _deq(tile: COO, scale):
    """Dequantize an int8-compressed tile (identity when scale is None)."""
    if scale is None:
        return tile
    return COO(tile.row, tile.col, tile.val.astype(scale.dtype) * scale,
               tile.nnz, tile.shape, tile.order)


def _merge_products(rows, cols, vals, nvalid, shape, sr, out_cap,
                    order="row", val_pred=None):
    prods = COO(rows, cols, vals,
                jnp.minimum(nvalid, rows.shape[0]).astype(jnp.int32),
                shape, "none")
    d = prods.dedup(sr.add, order=order)
    d = apply_val_pred(d, val_pred, sr.add.identity)
    # overflow must be read from the PRE-clamp nnz: with_cap() truncates
    # nnz to out_cap, which would make this check vacuously true
    ok = d.nnz <= out_cap
    return d.with_cap(out_cap, sr.add.identity), ok


def _rotate_sweep(q, overlap, rotate, step, state0, at0, as0, bt0, bs0):
    """Run the q Cannon stages, double-buffered or bulk-synchronous.

    overlap=True: each scan iteration issues the NEXT rotation before the
    current stage's expand+merge, so XLA can run the permute under the
    compute; the epilogue stage multiplies the last operands without
    rotating them (the dead final rotation of the serial formulation is
    dropped — 1/q of the rotation volume).
    overlap=False: q iterations, each pinning its merge outputs before the
    next rotation's inputs with an optimization_barrier (the MPI
    bulk-synchronous model). Stage order and per-stage math are identical
    either way, so results are bitwise equal.

    ``step(state, at, bt) -> (state, y_or_None)`` consumes dequantized
    tiles; ``rotate`` moves the (possibly compressed) wire payload.
    """
    def deq_step(state, at, as_, bt, bs_):
        return step(state, _deq(at, as_), _deq(bt, bs_))

    if overlap:
        def body(carry, _):
            at, as_, bt, bs_, state = carry
            nxt = rotate(at, as_, bt, bs_)   # issued before this stage's work
            state, y = deq_step(state, at, as_, bt, bs_)
            return nxt + (state,), y

        (at, as_, bt, bs_, state), ys = jax.lax.scan(
            body, (at0, as0, bt0, bs0, state0), None, length=q - 1)
        state, y = deq_step(state, at, as_, bt, bs_)   # epilogue: no rotate
        if y is not None:
            ys = jax.tree.map(lambda s, e: jnp.concatenate([s, e[None]]),
                              ys, y)
        return state, ys

    def body(carry, _):
        at, as_, bt, bs_, state = carry
        state, y = deq_step(state, at, as_, bt, bs_)
        # bulk-synchronous: the next rotation may not launch until this
        # stage's merge has completed
        (state, y), (at, as_, bt, bs_) = jax.lax.optimization_barrier(
            ((state, y), (at, as_, bt, bs_)))
        return rotate(at, as_, bt, bs_) + (state,), y

    (_, _, _, _, state), ys = jax.lax.scan(
        body, (at0, as0, bt0, bs0, state0), None, length=q)
    return state, ys


def _staged_tail(outs, shape, sr, merge, prod_cap, stage_cap, out_cap,
                 mask, val_pred):
    """Merge q per-stage _expand outputs (shared by alltoall/bcast paths)."""
    ident = sr.add.identity
    ok = jnp.all(jnp.stack([o[4] for o in outs]))
    if merge == "sort":
        # seed path: concatenate q full padded buffers, sort once —
        # masked products are dropped per stage, before the concat
        if mask is not None:
            outs = [(*filter_products(r, c_, v, shape, mask, ident), n, o)
                    for (r, c_, v, n, o) in outs]
        rows = jnp.concatenate([o[0] for o in outs])
        cols = jnp.concatenate([o[1] for o in outs])
        vals = jnp.concatenate([o[2] for o in outs])
        total = sum(o[3] for o in outs)
        c, ok2 = _merge_products(rows, cols, vals, total, shape, sr,
                                 out_cap, val_pred=val_pred)
        return c, ok & ok2
    # merge engine: mask-filter + compact each stage, then fold the q
    # sorted streams
    c, okm = merge_stage_products(
        [(r, c_, v, jnp.minimum(n, prod_cap)) for (r, c_, v, n, _) in outs],
        shape, sr.add, stage_cap, out_cap, mask=mask)
    return apply_val_pred(c, val_pred, ident), ok & okm


def _local_spgemm_2d(a_tile: COO, b_tile: COO, sr, q, prod_cap, out_cap,
                     schedule, merge, overlap=True,
                     mask: LocalMask | None = None, val_pred=None,
                     a_scale=None, b_scale=None):
    """Body run per device under shard_map for the 2D algorithm.

    The engine paths ('deferred'/'incremental') run at the kv level:
    per-stage compaction to stage_cap = min(prod_cap, out_cap) — sound
    because a stage's distinct count is bounded by the final nnz(C), and
    checked pre-clamp by the ok flags — then rank-placement merging of the
    compacted streams, decoding rows/cols exactly once.

    ``mask`` prunes every stage's expanded products against the local mask
    tile BEFORE any merge stage (§4.7): a masked stage's distinct count is
    bounded by the masked nnz(C), so mask-sized out/stage caps stay sound
    (still guarded pre-clamp by the ok flags). ``val_pred`` drops merged
    entries by output value in the final compaction.

    ``a_scale``/``b_scale`` are per-tile int8 dequantization scales (scalar
    per device) when the value payload is compressed; tiles dequantize just
    before expansion, AFTER every collective, so the wire stays int8.
    """
    shape = (a_tile.shape[0], b_tile.shape[1])
    stage_cap = min(prod_cap, out_cap)
    ident = sr.add.identity
    if key_dtype(shape) is None:
        merge = "sort"        # unpackable tile: the engine needs x64 keys

    if schedule == "alltoall":
        # gather my process row of A and process column of B (the broadcast
        # formulation; all stages' operands live simultaneously)
        with _obs.span("exchange"):
            ar = jax.tree.map(lambda x: jax.lax.all_gather(x, "col"), a_tile)
            bc = jax.tree.map(lambda x: jax.lax.all_gather(x, "row"), b_tile)
            asg = (None if a_scale is None
                   else jax.lax.all_gather(a_scale, "col"))
            bsg = (None if b_scale is None
                   else jax.lax.all_gather(b_scale, "row"))
        if not overlap:
            # bulk-synchronous: every stage's operands must land before any
            # local multiply starts
            ar, bc, asg, bsg = jax.lax.optimization_barrier(
                (ar, bc, asg, bsg))

        def stage(s):
            at = COO(ar.row[s], ar.col[s], ar.val[s], ar.nnz[s],
                     a_tile.shape, a_tile.order)
            bt = COO(bc.row[s], bc.col[s], bc.val[s], bc.nnz[s],
                     b_tile.shape, b_tile.order)
            at = _deq(at, None if asg is None else asg[s])
            bt = _deq(bt, None if bsg is None else bsg[s])
            return _expand(at, bt, sr, prod_cap)

        outs = [stage(s) for s in range(q)]
        return _staged_tail(outs, shape, sr, merge, prod_cap, stage_cap,
                            out_cap, mask, val_pred)

    if schedule != "rotate":
        # hybrid SUMMA stage order k=s: per-stage masked-psum broadcast
        # ('bcast'), with the tuple schedule's 'gather' stages batched into
        # ONE fused eager exchange up front (the all-to-all leg)
        sched = (schedule if isinstance(schedule, tuple)
                 else ("bcast",) * q)
        ri = jax.lax.axis_index("row")
        ci = jax.lax.axis_index("col")
        apay = (a_tile, a_scale)
        bpay = (b_tile, b_scale)

        def sel(pay, pos, s):
            # only stage s's owner contributes; the psum reduces the zeros
            # away and delivers the owner's tile to the whole axis
            return jax.tree.map(
                lambda x: jnp.where(pos == s, x, jnp.zeros_like(x)), pay)

        gs = [s for s in range(q) if sched[s] == "gather"]
        eag_a = eag_b = None
        if gs:
            stack = lambda *xs: jnp.stack(xs)
            ga = jax.tree.map(stack, *[sel(apay, ci, s) for s in gs])
            gb = jax.tree.map(stack, *[sel(bpay, ri, s) for s in gs])
            with _obs.span("exchange"):
                eag_a = jax.lax.psum(ga, "col")
                eag_b = jax.lax.psum(gb, "row")

        def fetch(s):
            if s in gs:
                i = gs.index(s)
                return (jax.tree.map(lambda x: x[i], eag_a),
                        jax.tree.map(lambda x: x[i], eag_b))
            sa, sb = sel(apay, ci, s), sel(bpay, ri, s)
            with _obs.span("exchange"):
                return jax.lax.psum(sa, "col"), jax.lax.psum(sb, "row")

        outs = []
        cur = fetch(0)
        for s in range(q):
            if overlap and s + 1 < q:
                nxt = fetch(s + 1)      # issued before this stage's expand
            (ap, asx), (bp, bsx) = cur
            y = _expand(_deq(ap, asx), _deq(bp, bsx), sr, prod_cap)
            if not overlap and s + 1 < q:
                # bulk-synchronous: the next broadcast's source payload may
                # not be read until this stage's expansion has completed
                y, (apay, bpay) = jax.lax.optimization_barrier(
                    (y, (apay, bpay)))
                nxt = fetch(s + 1)
            outs.append(y)
            if s + 1 < q:
                cur = nxt
        return _staged_tail(outs, shape, sr, merge, prod_cap, stage_cap,
                            out_cap, mask, val_pred)

    # rotation (Cannon)
    axes = ("row", "col")
    a_rot, as_rot = _tile_permute(a_tile, axes, _cannon_perms(q, True),
                                  a_scale)
    b_rot, bs_rot = _tile_permute(b_tile, axes, _cannon_perms(q, False),
                                  b_scale)
    ok0 = jnp.bool_(True)

    def rotate(at, as_, bt, bs_):
        at, as_ = _tile_permute(at, "col", _shift_perm(q, q, left=True), as_)
        bt, bs_ = _tile_permute(bt, "row", _shift_perm(q, q, left=True), bs_)
        return at, as_, bt, bs_

    if merge == "incremental":
        kacc, vacc, nacc = kv_empty(
            shape, out_cap, vals_dtype(sr, a_tile, b_tile, a_scale, b_scale),
            sr.add)

        def step(state, at, bt):
            kacc, vacc, nacc, ok = state
            r, c, v, n, okx = _expand(at, bt, sr, prod_cap)
            # mask-filter + compact the stage, then O(n) rank-placement
            # merge into the sorted kv accumulator — never re-sorted
            ks, vs, ns, okc = kv_from_products(
                r, c, v, jnp.minimum(n, prod_cap), shape, sr.add, stage_cap,
                mask=mask)
            kacc, vacc, nacc, okm = kv_merge2(kacc, vacc, nacc, ks, vs, ns,
                                              sr.add, out_cap)
            return (kacc, vacc, nacc, ok & okx & okc & okm), None

        state, _ = _rotate_sweep(q, overlap, rotate, step,
                                 (kacc, vacc, nacc, ok0),
                                 a_rot, as_rot, b_rot, bs_rot)
        kacc, vacc, nacc, ok = state
        c = kv_to_coo(kacc, vacc, nacc, shape, sr.add, out_cap)
        return apply_val_pred(c, val_pred, ident), ok

    if merge == "sort":
        # seed path: collect q padded product buffers, concat, sort once
        def step(ok, at, bt):
            r, c, v, n, okx = _expand(at, bt, sr, prod_cap)
            if mask is not None:
                r, c, v = filter_products(r, c, v, shape, mask, ident)
            return ok & okx, (r, c, v)

        ok, (rs, cs, vs) = _rotate_sweep(q, overlap, rotate, step, ok0,
                                         a_rot, as_rot, b_rot, bs_rot)
        rows = rs.reshape(-1)
        cols = cs.reshape(-1)
        vals = vs.reshape((-1,) + vs.shape[2:])
        c, ok2 = _merge_products(rows, cols, vals, rows.shape[0], shape, sr,
                                 out_cap, val_pred=val_pred)
        return c, ok & ok2

    # deferred (merge tree): mask-filter + compact each stage inside the
    # scan, then fold the q sorted kv streams pairwise — no concat-and-sort
    def step(ok, at, bt):
        r, c, v, n, okx = _expand(at, bt, sr, prod_cap)
        ks, vs, ns, okc = kv_from_products(
            r, c, v, jnp.minimum(n, prod_cap), shape, sr.add, stage_cap,
            mask=mask)
        return ok & okx & okc, (ks, vs, ns)

    ok, (ks, vs, ns) = _rotate_sweep(q, overlap, rotate, step, ok0,
                                     a_rot, as_rot, b_rot, bs_rot)
    items = [(ks[s], vs[s], ns[s]) for s in range(q)]
    k, v, nn, okm = kv_tree(items, sr.add, out_cap)
    c = kv_to_coo(k, v, nn, shape, sr.add, out_cap)
    return apply_val_pred(c, val_pred, ident), ok & okm


def vals_dtype(sr, a_tile, b_tile, a_scale=None, b_scale=None):
    # compressed tiles carry int8 on the wire; the scale keeps the
    # original value dtype, which is what expansion produces after deq
    ad = a_scale.dtype if a_scale is not None else a_tile.dtype
    bd = b_scale.dtype if b_scale is not None else b_tile.dtype
    return sr.out_dtype(ad, bd)


def _compress_operand(mat, sr, site, resid=None):
    """Quantize a DistSpMat's value payload to int8 at the host boundary.

    The returned matrix carries int8 values (the wire payload — guarded by
    the ``dist.compressed_exchange`` audit/fault site) plus a per-tile
    scale array; ``new_resid`` is the quantization error for error
    feedback (exactly val+resid − dequantized).
    """
    q8, scale, new_resid = quantize_payload(mat.val, mat.nnz, resid)
    if _obs.recording():
        # comm-volume tier: value-payload bytes before/after quantization
        # (the int8 wire adds one scale scalar per tile)
        import numpy as np
        live = int(np.sum(np.asarray(mat.nnz)))
        _obs.counter_add("dist.compress.bytes_in",
                         live * mat.val.dtype.itemsize)
        _obs.counter_add("dist.compress.bytes_out",
                         live * q8.dtype.itemsize
                         + scale.size * scale.dtype.itemsize)
    mat = dataclasses.replace(mat, val=q8)
    mat = _audit.guard_exchange(site, mat)
    return mat, scale, new_resid


@_obs.timed("spgemm2d")
def spgemm_2d(a: DistSpMat, b: DistSpMat, sr: Semiring = ARITHMETIC, *,
              mesh: Mesh, prod_cap: int, out_cap: int,
              variant: str = "rotation", merge: str = "deferred",
              mask: MaskSpec | None = None, schedule=None,
              overlap: bool = True, compress: str | None = None,
              ef_resid=None):
    """C = A ⊕.⊗ B (optionally C⟨M⟩). Returns (DistSpMat, ok[pr,pc]).

    ``schedule`` overrides the variant-derived exchange schedule: 'rotate',
    'alltoall', 'bcast', or a length-q tuple of 'bcast'|'gather' (§4.8).
    ``overlap`` toggles double-buffered (default) vs bulk-synchronous stage
    loops; results are bitwise equal either way. ``compress='int8'``
    quantizes the value payloads for the wire (floating values with an
    additive identity of 0 only); passing ``ef_resid`` (a residual array
    like ``a.val``, start with zeros) enables error feedback for A and
    makes the return a 3-tuple (C, ok, new_resid).

    ``mask.mat`` must be tile-aligned with C (same grid, C's shape): the
    mask never communicates, and each device prunes its expanded products
    against its own mask tile before any merge stage (§4.7).
    """
    assert a.grid == b.grid and a.pr == a.pc, "2D SpGEMM needs a square grid"
    assert a.shape[1] == b.shape[0]
    q = a.pr
    sched = _schedule_from(variant, schedule, q)
    # operands are about to enter the exchange collectives: this is the
    # wire boundary the audit checksums bracket (and the fault sites
    # corrupt) — see robust/audit.guard_exchange
    a = _audit.guard_exchange("spgemm2d.comm_a", a)
    b = _audit.guard_exchange("spgemm2d.comm_b", b)
    a_scale = b_scale = new_resid = None
    if ef_resid is not None and compress is None:
        raise ValueError("ef_resid is only meaningful with compress='int8'")
    if compress is not None:
        if compress != "int8":
            raise ValueError(f"unknown compress mode {compress!r}")
        if not (jnp.issubdtype(a.val.dtype, jnp.floating)
                and jnp.issubdtype(b.val.dtype, jnp.floating)):
            raise ValueError("compressed exchange needs floating values")
        if sr.add.identity != 0.0:
            raise ValueError(
                "compressed exchange needs an additive identity of 0 "
                "(padding must survive the int8 round trip)")
        with _obs.span("spgemm2d.compress"):
            a, a_scale, new_resid = _compress_operand(
                a, sr, "dist.compressed_exchange", ef_resid)
            b, b_scale, _ = _compress_operand(
                b, sr, "dist.compressed_exchange")
    mm = mask.mat if mask is not None else None
    val_pred = mask.val_pred if mask is not None else None
    if mask is not None and (mask.mat3 is not None or mask.vec is not None):
        raise ValueError("spgemm_2d takes a 2D mask operand (MaskSpec.mat)")
    if mm is not None:
        assert mm.grid == a.grid and mm.shape == (a.shape[0], b.shape[1]), \
            "mask must be tile-aligned with C"

    def body(at, bt, *extra):
        i = 0
        lm = None
        if mm is not None:
            lm = mask.local(extra[i].tile())
            i += 1
        asx = bsx = None
        if a_scale is not None:
            asx = extra[i].reshape(())
            bsx = extra[i + 1].reshape(())
        c, ok = _local_spgemm_2d(
            at.tile(), bt.tile(),
            sr, q, prod_cap, out_cap, sched, merge, overlap=overlap,
            mask=lm, val_pred=val_pred, a_scale=asx, b_scale=bsx)
        return (c.row[None, None], c.col[None, None], c.val[None, None],
                c.nnz[None, None], ok[None, None])

    in_specs = (specs_of(a), specs_of(b))
    args = (a, b)
    if mm is not None:
        in_specs = in_specs + (specs_of(mm),)
        args = args + (mm,)
    if a_scale is not None:
        in_specs = in_specs + (P("row", "col"), P("row", "col"))
        args = args + (a_scale, b_scale)
    out_specs = (P("row", "col", None), P("row", "col", None),
                 P("row", "col", None), P("row", "col"), P("row", "col"))
    f = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    # the SUMMA stage loop itself is traced (inside shard_map) — the host
    # span brackets the whole dispatch, blocking when recording so the
    # span covers device execution, not just async dispatch
    with _obs.span("spgemm2d.execute", q=q, variant=variant, merge=merge,
                   schedule=sched if isinstance(sched, str) else "hybrid",
                   overlap=overlap, compress=compress or "none"):
        row, col, val, nnz, ok = f(*args)
        _obs.sync((row, col, val, nnz, ok))
    # every merge path ends in dedup(order='row'), so C keeps the invariant
    cmat = DistSpMat(row, col, val, nnz, (a.shape[0], b.shape[1]), a.grid,
                     order="row")
    _audit.audit_obj(cmat, "spgemm2d.out", min_level=_audit.FULL)
    if ef_resid is not None:
        return cmat, ok, new_resid
    return cmat, ok


@_obs.timed("spgemm3d")
def spgemm_3d(a3: DistSpMat3D, b3: DistSpMat3D, sr: Semiring = ARITHMETIC, *,
              mesh: Mesh, prod_cap: int, out_cap: int,
              merge: str = "deferred", variant: str = "rotation",
              mask: MaskSpec | None = None, schedule=None,
              overlap: bool = True):
    """Communication-avoiding SpGEMM on a (L, q, q) grid (paper Fig 2).

    Returns (C3 [dist='csub'], ok[L,q,q]). ``schedule``/``overlap`` select
    the per-layer 2D exchange schedule and double-buffering exactly as in
    :func:`spgemm_2d`; ``overlap`` additionally fuses the inter-layer
    all-to-all into one tree-level exchange (overlap=False barriers the
    radix placement and exchanges the three fields separately — the
    bulk-synchronous reference; both move identical bytes, results are
    bitwise equal).

    ``mask.mat3`` must be C-distributed ('csub', same grid). Each layer
    all-gathers the mask's L column sub-pieces of its C tile along the
    (cheap, nnz(M)-sized) 'layer' axis, so the per-layer 2D multiply prunes
    expanded products before any merge stage AND before the inter-layer
    all-to-all — masked entries never travel. ``mask.val_pred`` applies
    only after the inter-layer merge (layer partials are incomplete sums).
    """
    assert a3.dist == "acol" and b3.dist == "brow"
    assert a3.grid == b3.grid
    a3 = _audit.guard_exchange("spgemm3d.comm_a", a3)
    b3 = _audit.guard_exchange("spgemm3d.comm_b", b3)
    L, q = a3.L, a3.q
    sched = _schedule_from(variant, schedule, q)
    tr_a, tc_a = a3.block_sizes()
    tr_b, tc_b = b3.block_sizes()
    assert tc_a == tr_b, (tc_a, tr_b)
    kbl = tc_b // L          # C column sub-block width after layer split
    c_shape = (a3.shape[0], b3.shape[1])
    m3 = mask.mat3 if mask is not None else None
    val_pred = mask.val_pred if mask is not None else None
    if mask is not None and (mask.mat is not None or mask.vec is not None):
        raise ValueError("spgemm_3d takes a 3D mask operand (MaskSpec.mat3)")
    if m3 is not None:
        assert m3.dist == "csub" and m3.grid == a3.grid \
            and m3.shape == c_shape, "mask must be C-distributed (csub)"
        if key_dtype((tr_a, tc_b)) is None:
            raise ValueError("masked 3D SpGEMM needs a packable C tile")

    def body(at, bt, *mt):
        a_tile = COO(at.row.reshape(-1), at.col.reshape(-1),
                     at.val.reshape(-1), at.nnz.reshape(()),
                     (tr_a, tc_a), a3.order)
        b_tile = COO(bt.row.reshape(-1), bt.col.reshape(-1),
                     bt.val.reshape(-1), bt.nnz.reshape(()),
                     (tr_b, tc_b), b3.order)
        lm = None
        if mt:
            # assemble the FULL C-tile mask from the L csub sub-pieces:
            # sub-piece l covers tile columns [l·kbl, (l+1)·kbl)
            with _obs.span("exchange"):
                mrow = jax.lax.all_gather(mt[0].row.reshape(-1), "layer")
                mcol = jax.lax.all_gather(mt[0].col.reshape(-1), "layer")
                mval = jax.lax.all_gather(mt[0].val.reshape(-1), "layer")
            fcol = jnp.where(
                mcol != SENTINEL,
                mcol + jnp.arange(L, dtype=jnp.int32)[:, None] * kbl,
                SENTINEL)
            keys = pack_keys(mrow.reshape(-1), fcol.reshape(-1),
                             (tr_a, tc_b), "row")
            if mask.pred is not None:
                allow = jnp.asarray(mask.pred(mval.reshape(-1))) \
                    & (mrow.reshape(-1) != SENTINEL)
                keys, allow = jax.lax.sort([keys, allow], num_keys=1,
                                           is_stable=False)
            else:
                allow = None
                keys = jax.lax.sort([keys], num_keys=1)[0]
            lm = LocalMask(keys, allow, mask.complement, "row")
        # per-layer 2D multiply ('row'/'col' collectives are layer-local)
        c_part, ok = _local_spgemm_2d(a_tile, b_tile, sr, q,
                                      prod_cap, prod_cap, sched, merge,
                                      overlap=overlap, mask=lm)
        # ---- inter-layer all-to-all (Fig 2, right) --------------------
        # destination layer of an entry = its column sub-block
        dest = jnp.where(c_part.mask(), c_part.col // kbl, L)
        cap_l = c_part.cap // L
        # radix-place each entry at dest*cap_l + rank_within_dest
        order = jnp.argsort(dest, stable=True)
        d_sorted = dest[order]
        seg_start = jnp.searchsorted(d_sorted, jnp.arange(L + 1),
                                     side="left").astype(jnp.int32)
        counts = seg_start[1:] - seg_start[:-1]
        ok = ok & jnp.all(counts <= cap_l)
        within = jnp.arange(c_part.cap, dtype=jnp.int32) - \
            seg_start[jnp.clip(d_sorted, 0, L - 1)]
        slot = jnp.where(d_sorted < L,
                         d_sorted * cap_l + jnp.minimum(within, cap_l - 1),
                         L * cap_l)  # dropped
        buf_r = jnp.full((L * cap_l,), SENTINEL, jnp.int32)
        buf_c = jnp.full((L * cap_l,), SENTINEL, jnp.int32)
        buf_v = jnp.full((L * cap_l,), sr.add.identity, c_part.val.dtype)
        keep = (d_sorted < L) & (within < cap_l)
        # dropped entries write out-of-bounds (mode='drop') — never a live slot
        slotk = jnp.where(keep, slot, L * cap_l)
        rs, cs_, vs = (c_part.row[order], c_part.col[order],
                       c_part.val[order])
        buf_r = buf_r.at[slotk].set(rs, mode="drop")
        buf_c = buf_c.at[slotk].set(cs_, mode="drop")
        buf_v = buf_v.at[slotk].set(vs, mode="drop")
        # exchange: piece t -> layer t
        if overlap:
            # one fused tree-level all-to-all, issued as soon as the radix
            # placement finishes — XLA can overlap it with the argsort of
            # the next shard_map program and fuses three launches into one
            with _obs.span("exchange"):
                buf_r, buf_c, buf_v = jax.lax.all_to_all(
                    (buf_r.reshape(L, cap_l), buf_c.reshape(L, cap_l),
                     buf_v.reshape(L, cap_l)), "layer", 0, 0, tiled=False)
            buf_r = buf_r.reshape(L * cap_l)
            buf_c = buf_c.reshape(L * cap_l)
            buf_v = buf_v.reshape(L * cap_l)
        else:
            # bulk-synchronous reference: placement completes, then three
            # separate per-field exchanges
            buf_r, buf_c, buf_v = jax.lax.optimization_barrier(
                (buf_r, buf_c, buf_v))

            def a2a(x):
                with _obs.span("exchange"):
                    x = jax.lax.all_to_all(x.reshape(L, cap_l), "layer", 0,
                                           0, tiled=False)
                return x.reshape(L * cap_l)

            buf_r, buf_c, buf_v = a2a(buf_r), a2a(buf_c), a2a(buf_v)
        my_layer = jax.lax.axis_index("layer")
        # localize columns to my sub-block and merge
        valid = buf_r != SENTINEL
        lc = jnp.where(valid, buf_c - my_layer * kbl, SENTINEL)
        lr = jnp.where(valid, buf_r, SENTINEL)
        if merge == "sort" or key_dtype((tr_a, kbl)) is None:
            # seed path: one dedup over the whole exchanged buffer
            d = COO(lr, lc, buf_v, jnp.sum(valid).astype(jnp.int32),
                    (tr_a, kbl), "none").dedup(sr.add)
            d = apply_val_pred(d, val_pred, sr.add.identity)
            ok = ok & (d.nnz <= out_cap)         # pre-clamp nnz
            merged = d.with_cap(out_cap, sr.add.identity)
        else:
            # merge engine (§4.4): each received piece is a stable-compacted
            # slice of a row-sorted dedup output, so the L chunks are
            # sorted unique-key streams — fold them pairwise, never re-sort
            items = []
            for t in range(L):
                sl = slice(t * cap_l, (t + 1) * cap_l)
                items.append((pack_keys(lr[sl], lc[sl], (tr_a, kbl), "row"),
                              buf_v[sl],
                              jnp.sum(valid[sl]).astype(jnp.int32)))
            k, v, nn, okm = kv_tree(items, sr.add, out_cap)
            merged = kv_to_coo(k, v, nn, (tr_a, kbl), sr.add, out_cap)
            merged = apply_val_pred(merged, val_pred, sr.add.identity)
            ok = ok & okm
        return (merged.row[None, None, None], merged.col[None, None, None],
                merged.val[None, None, None], merged.nnz[None, None, None],
                ok[None, None, None])

    in_specs = (specs_of(a3), specs_of(b3))
    args = (a3, b3)
    if m3 is not None:
        in_specs = in_specs + (specs_of(m3),)
        args = args + (m3,)
    out_specs = (P("layer", "row", "col", None),
                 P("layer", "row", "col", None),
                 P("layer", "row", "col", None),
                 P("layer", "row", "col"), P("layer", "row", "col"))
    f = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    with _obs.span("spgemm3d.execute", L=L, q=q, variant=variant,
                   merge=merge, overlap=overlap):
        row, col, val, nnz, ok = f(*args)
        _obs.sync((row, col, val, nnz, ok))
    c3 = DistSpMat3D(row, col, val, nnz, c_shape, a3.grid, "csub",
                     order="row")  # final inter-layer merge is a row dedup
    _audit.audit_obj(c3, "spgemm3d.out", min_level=_audit.FULL)
    return c3, ok


def spgemm_2d_batched(a: DistSpMat, b: DistSpMat, sr: Semiring = ARITHMETIC,
                      *, mesh: Mesh, prod_cap: int, out_cap: int,
                      nbatch: int, variant: str = "rotation",
                      mask: MaskSpec | None = None, schedule=None,
                      overlap: bool = True, compress: str | None = None):
    """Batched SpGEMM (paper §7.2): form C in ``nbatch`` column batches.

    Each batch multiplies A by the column-slab restriction of B, yielding a
    DistSpMat for that slab; the caller consumes batches one at a time
    (HipMCL-style) so the full C never needs to exist in memory. Returns a
    list of (C_batch, ok) with C_batch's shape = full C shape (entries only
    in the slab).

    With ``compress='int8'`` the quantization residual of A (re-sent every
    batch) is carried across batches as error feedback, so A's wire error
    does not accumulate over the batch loop.
    """
    nb_cols = b.nb  # tile width of B
    slab = -(-nb_cols // nbatch)
    resid = jnp.zeros_like(a.val) if compress is not None else None
    outs = []
    for t in range(nbatch):
        with _obs.span("spgemm2d.batch", batch=t, nbatch=nbatch):
            bt = _restrict_cols(b, t * slab, slab)
            if compress is not None:
                c, ok, resid = spgemm_2d(
                    a, bt, sr, mesh=mesh, prod_cap=prod_cap,
                    out_cap=out_cap, variant=variant, mask=mask,
                    schedule=schedule, overlap=overlap, compress=compress,
                    ef_resid=resid)
            else:
                c, ok = spgemm_2d(a, bt, sr, mesh=mesh, prod_cap=prod_cap,
                                  out_cap=out_cap, variant=variant,
                                  mask=mask, schedule=schedule,
                                  overlap=overlap)
            outs.append((c, ok))
    return outs


def _restrict_cols(b: DistSpMat, lo: int, width: int) -> DistSpMat:
    """Zero out entries outside tile-local columns [lo, lo+width)."""
    keep = (b.col >= lo) & (b.col < lo + width) & (b.col != SENTINEL)
    # compact each tile: sort kept-first along the cap axis; the stable
    # compaction preserves each tile's entry order, so the order tag survives
    order = jnp.argsort(~keep, axis=-1, stable=True)
    row = jnp.take_along_axis(jnp.where(keep, b.row, SENTINEL), order, -1)
    col = jnp.take_along_axis(jnp.where(keep, b.col, SENTINEL), order, -1)
    val = jnp.take_along_axis(jnp.where(keep, b.val, 0), order, -1)
    nnz = jnp.sum(keep, axis=-1).astype(jnp.int32)
    return DistSpMat(row, col, val, nnz, b.shape, b.grid, order=b.order)
