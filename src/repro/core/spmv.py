"""Distributed SpMV / SpMSpV on the 2D grid (paper §3.1, Table 1).

y = A ⊕.⊗ x with A 2D-distributed and x fully distributed (DistVec layout
'col': block j of x is owned collectively by process column j).

SpMV pipeline (the classic 2D algorithm the paper's Table 1 analyses):
  1. all-gather x pieces along the 'row' axis → every device in process
     column j holds the full column block x_j           [O(n/√p) bytes/dev]
  2. local SpMV variant (row- or col-partitioned, §4.2)
  3. reduce partial y along the 'col' axis. For tagged monoids this is a
     reduce-scatter (psum_scatter), yielding y fully distributed in layout
     'row' — no replication, exactly the paper's vector distribution.

SpMSpV keeps the frontier sparse end-to-end (§4.3): sparse pieces are
all-gathered along 'row' (O(nf/√p)), the local SpMSpV variant produces a
sparse partial, and partials merge along 'col' either densely
(psum_scatter) or sparsely (bucketed all-to-all — the fine-grained scheme).

Square grids are required for vectors to round-trip between layouts with a
single transpose permute (CombBLAS restricts most vector ops similarly).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..obs import recorder as _obs
from ..robust import audit as _audit
from .compat import shard_map
from .coo import COO, SENTINEL
from .dist import DistSpMat, DistSpVec, DistVec, specs_of
from .semiring import ARITHMETIC, Monoid, Semiring, segment_reduce
from . import spmv_local as L

Array = jax.Array


def transpose_layout(v: DistVec, *, mesh: Mesh) -> DistVec:
    """Swap piece (i,j) <-> (j,i): converts layout 'row' <-> 'col'."""
    pr, pc = v.grid
    assert pr == pc, "layout transpose needs a square grid"
    q = pr
    perm = [(i * q + j, j * q + i) for i in range(q) for j in range(q)]

    def body(d):
        return jax.lax.ppermute(d, ("row", "col"), perm)

    out = shard_map(body, mesh=mesh, in_specs=P("row", "col", None),
                        out_specs=P("row", "col", None))(v.data)
    new_layout = "row" if v.layout == "col" else "col"
    return DistVec(out, v.n, v.grid, new_layout)


@_obs.timed("spmv")
def spmv(a: DistSpMat, x: DistVec, sr: Semiring = ARITHMETIC, *,
         mesh: Mesh, variant: str = "row") -> DistVec:
    """y = A x. x must be layout 'col'; result is layout 'row'."""
    assert x.layout == "col", "spmv expects a column-layout input vector"
    assert a.shape[1] == x.n or True  # padded blocks make this a soft check
    pr, pc = a.grid
    local_fn = L.spmv_row if variant == "row" else L.spmv_col

    def body(at, xd):
        tile = at.tile()
        xj = jax.lax.all_gather(xd.reshape(-1), "row", tiled=True)  # (nb,)
        y_part = local_fn(tile, xj, sr)                             # (mb,)
        if sr.add.tag == "sum":
            y_piece = jax.lax.psum_scatter(y_part, "col", scatter_dimension=0,
                                           tiled=True)
        else:
            parts = jax.lax.all_gather(y_part, "col")               # (pc, mb)
            red = parts[0]
            for t in range(1, pc):
                red = sr.add.op(red, parts[t])
            j = jax.lax.axis_index("col")
            piece = red.reshape(pc, -1)[j]
            y_piece = piece
        return y_piece[None, None]

    out = shard_map(body, mesh=mesh,
                        in_specs=(specs_of(a), P("row", "col", None)),
                        out_specs=P("row", "col", None))(a, x.data)
    return DistVec(out, a.shape[0], a.grid, "row")


def spmv_iter(a: DistSpMat, x: DistVec, sr: Semiring = ARITHMETIC, *,
              mesh: Mesh, variant: str = "row") -> DistVec:
    """SpMV returning a column-layout vector (ready for the next iteration)."""
    return transpose_layout(spmv(a, x, sr, mesh=mesh, variant=variant),
                            mesh=mesh)


@_obs.timed("spmspv")
def spmspv(a: DistSpMat, x: DistSpVec, sr: Semiring = ARITHMETIC, *,
           mesh: Mesh, variant: str = "sort", merge: str = "sparse",
           prod_cap: int, out_cap: int, mask=None):
    """y = A x with sparse x. Returns (DistSpVec layout 'row', ok[pr,pc]).

    merge='sparse': partial outputs stay sparse; destination pieces receive
    entries via a bucketed all-to-all along 'col' (paper §3.3 fine-grained).
    merge='dense' : partial SPA vectors are psum_scattered (tag 'sum' only).

    ``mask`` (a ``mask.vector_mask`` MaskSpec over a layout-'row' DistVec,
    piece-aligned with y) drops products on non-admissible output rows
    inside the local expansion — BEFORE the variant merges and the 'col'
    exchange (§4.7, direction-optimized BFS's visited pushdown). The mask
    pieces are all-gathered along 'col' (one O(mb) boolean per device,
    the same volume as the output reduction itself).
    """
    assert x.layout == "col"
    # the frontier is about to be all-gathered along 'row' — the wire
    # boundary the audit checksums bracket (robust/audit.guard_exchange)
    x = _audit.guard_exchange("spmspv.comm_x", x)
    pr, pc = a.grid
    local_fn = L.SPMSPV_VARIANTS[variant]
    vb_out = -(-a.shape[0] // (pr * pc))
    mb = a.mb
    mv = mask.vec if mask is not None else None
    if mask is not None:
        if mv is None:
            raise ValueError("spmspv masks are dense-vector masks "
                             "(mask.vector_mask)")
        assert mv.layout == "row" and mv.grid == a.grid \
            and mv.n == a.shape[0], "mask must be piece-aligned with y"

    def body(at, xi, xv, xn, *md):
        tile = at.tile()
        allow = None
        if md:
            member = jnp.asarray(mask.pred(md[0].reshape(-1)))  # (vb,)
            if mask.complement:
                member = ~member
            # process row i's pieces j=0..pc-1 are exactly the tile's row
            # range [i*mb, (i+1)*mb) in j order (layout 'row')
            with _obs.span("exchange"):
                allow = jax.lax.all_gather(member, "col", tiled=True)
        # gather the sparse pieces of column block j (localize to block)
        xi_l = xi.reshape(-1)
        xv_l = xv.reshape(-1)
        xn_l = xn.reshape(())
        cap_x = xi_l.shape[0]
        i_in_blk = jax.lax.axis_index("row")
        vb_in = a.nb // pr
        xi_blk = jnp.where(xi_l != SENTINEL, xi_l + i_in_blk * vb_in, SENTINEL)
        with _obs.span("exchange"):
            gi = jax.lax.all_gather(xi_blk, "row", tiled=True)  # (pr*cap_x,)
            gv = jax.lax.all_gather(xv_l, "row", tiled=True)
            gn = jax.lax.psum(xn_l, "row")
        # compact: local spmspv handles interleaved padding via mask->cnt=0
        # trick: treat gathered arrays as a sparse vector with nnz=total but
        # padding interleaved — _expand masks by index<nnz, so compact first
        order = jnp.argsort(gi == SENTINEL, stable=True)
        gi, gv = gi[order], gv[order]
        with _obs.span("spmspv.local"):
            (yi, yv, yn), ok = local_fn(tile, gi, gv, gn, sr,
                                        prod_cap=prod_cap, out_cap=out_cap,
                                        allow=allow)
        if merge == "dense" and sr.add.tag == "sum":
            dense = L.spvec_to_dense(yi, yv, mb, zero=0)
            with _obs.span("exchange"):
                piece = jax.lax.psum_scatter(dense, "col",
                                             scatter_dimension=0, tiled=True)
            # spvec_from_dense clamps nnz to out_cap — detect the overflow
            # before re-sparsifying or truncation would be silent
            ok = ok & (jnp.sum(piece != 0) <= out_cap)
            pi, pv, pn = L.spvec_from_dense(piece, out_cap, zero=0)
            return pi[None, None], pv[None, None], pn[None, None], \
                ok[None, None]
        # ---- sparse merge: bucket partial entries by destination piece ----
        dest = jnp.where(yi != SENTINEL, yi // vb_out, pc)
        cap_d = max(out_cap // pc, 8)
        order2 = jnp.argsort(dest, stable=True)
        d_s = dest[order2]
        seg = jnp.searchsorted(d_s, jnp.arange(pc + 1)).astype(jnp.int32)
        counts = seg[1:] - seg[:-1]
        ok = ok & jnp.all(counts <= cap_d)
        within = jnp.arange(yi.shape[0], dtype=jnp.int32) - \
            seg[jnp.clip(d_s, 0, pc - 1)]
        keep = (d_s < pc) & (within < cap_d)
        # dropped entries write out-of-bounds (mode='drop')
        slot = jnp.where(keep, d_s * cap_d + jnp.minimum(within, cap_d - 1),
                         pc * cap_d)
        bi = jnp.full((pc * cap_d,), SENTINEL, jnp.int32)
        bv = jnp.full((pc * cap_d,), sr.add.identity, yv.dtype)
        yi_s, yv_s = yi[order2], yv[order2]
        bi = bi.at[slot].set(yi_s, mode="drop")
        bv = bv.at[slot].set(yv_s, mode="drop")
        with _obs.span("exchange"):
            bi = jax.lax.all_to_all(bi.reshape(pc, cap_d), "col", 0, 0) \
                .reshape(pc * cap_d)
            bv = jax.lax.all_to_all(bv.reshape(pc, cap_d), "col", 0, 0) \
                .reshape(pc * cap_d)
        # localize to my piece and merge duplicates from the pc sources
        j = jax.lax.axis_index("col")
        valid = bi != SENTINEL
        li = jnp.where(valid, bi - j * vb_out, SENTINEL)
        d = COO(li, jnp.where(valid, 0, SENTINEL), bv,
                jnp.sum(valid).astype(jnp.int32), (vb_out, 1),
                "none").dedup(sr.add)
        ok = ok & (d.nnz <= out_cap)             # pre-clamp nnz
        merged = d.with_cap(out_cap, sr.add.identity)
        return merged.row[None, None], merged.val[None, None], \
            merged.nnz[None, None], ok[None, None]

    out_specs = (P("row", "col", None), P("row", "col", None),
                 P("row", "col"), P("row", "col"))
    in_specs = (specs_of(a), P("row", "col", None), P("row", "col", None),
                P("row", "col"))
    args = (a, x.idx, x.val, x.nnz)
    if mv is not None:
        in_specs = in_specs + (P("row", "col", None),)
        args = args + (mv.data,)
    with _obs.span("spmspv.execute", variant=variant, merge=merge):
        yi, yv, yn, ok = shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)(*args)
        _obs.sync((yi, yv, yn, ok))
    y = DistSpVec(yi, yv, yn, a.shape[0], a.grid, "row")
    _audit.audit_obj(y, "spmspv.out", min_level=_audit.FULL)
    return y, ok


def transpose_spvec_layout(v: DistSpVec, *, mesh: Mesh) -> DistSpVec:
    pr, pc = v.grid
    assert pr == pc
    q = pr
    perm = [(i * q + j, j * q + i) for i in range(q) for j in range(q)]

    def body(xi, xv, xn):
        f = lambda t: jax.lax.ppermute(t, ("row", "col"), perm)
        with _obs.span("exchange"):
            return f(xi), f(xv), f(xn)

    yi, yv, yn = shard_map(
        body, mesh=mesh,
        in_specs=(P("row", "col", None), P("row", "col", None),
                  P("row", "col")),
        out_specs=(P("row", "col", None), P("row", "col", None),
                   P("row", "col")))(v.idx, v.val, v.nnz)
    return DistSpVec(yi, yv, yn, v.n, v.grid,
                     "row" if v.layout == "col" else "col")
