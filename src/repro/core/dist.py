"""Distributed sparse/dense containers (SpParMat / FullyDist[Sp]Vec analogues).

Data model (DESIGN.md §3): a distributed object stores each field as ONE
jax.Array whose leading dims are the process-grid dims, sharded so each
device owns exactly its tile:

  DistSpMat  : row/col/val/nnz with shapes (pr, pc, cap …), P('row','col')
  DistSpMat3D: (L, pr, pc, cap …), P('layer','row','col')
  DistVec    : (pr, pc, vb), P('row','col')  — CombBLAS's superimposed 2D
               vector distribution, NO replication (paper §2.2): piece
               (i, j) holds global block k*vb .. (k+1)*vb where the linear
               piece id k depends on the layout:
                 layout='col': k = j*pr + i  (block j of the matrix column
                                dimension is owned collectively by process
                                column j — what SpMV input needs)
                 layout='row': k = i*pc + j  (block i owned by process row
                                i — what reduce-scattered SpMV output is)
  DistSpVec  : sparse pieces (pr, pc, cap) idx/val/nnz, same piece layout.

Index discipline (paper §1, two index types): global indices are int64 and
live ONLY on the host (numpy) during assembly/extraction; device-resident
indices are tile-local int32.

Load balance (paper §2.3/§6): ``random_permute=True`` at assembly applies a
seeded random row+column permutation — CombBLAS's standard trick, also the
free side effect of ReadGeneralizedTuples.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import recorder as _obs
from ..robust import audit as _audit, faults as _faults
from . import compat
from .coo import COO, SENTINEL

Array = jax.Array


def _ceil(a, b):
    return -(-a // b)


def make_grid(pr: int, pc: int, layers: int = 1,
              devices=None) -> Mesh:
    """Process grid for sparse ops: ('row','col') or ('layer','row','col')."""
    devices = devices if devices is not None else jax.devices()
    n = layers * pr * pc
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    if layers == 1:
        return compat.make_mesh((pr, pc), ("row", "col"), devices=devices[:n])
    return compat.make_mesh((layers, pr, pc), ("layer", "row", "col"),
                            devices=devices[:n])


# --------------------------------------------------------------------------
# 2D distributed sparse matrix
# --------------------------------------------------------------------------

def _fetch(*arrays) -> list[np.ndarray]:
    """The arrays on the host: one blocking device→host read each, counted
    as ``host.sync.fetch``, and their bytes, from shapes and dtypes, as
    ``fetch.bytes``."""
    if _obs.enabled():
        _obs.counter_add("host.sync.fetch", len(arrays))
        _obs.counter_add("fetch.bytes", sum(
            math.prod(x.shape) * np.dtype(x.dtype).itemsize for x in arrays))
    return [np.asarray(x) for x in arrays]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DistSpMat:
    """2D-distributed sparse matrix on a (pr, pc) grid.

    Tile (i, j) covers global rows [i*mb, (i+1)*mb) × cols [j*nb, (j+1)*nb)
    with mb = vbm*pc and nb = vbn*pr (padded so the superimposed vector
    pieces align — see DistVec).
    """

    row: Array   # (pr, pc, cap) int32, tile-local row index
    col: Array   # (pr, pc, cap) int32, tile-local col index
    val: Array   # (pr, pc, cap, *vdims)
    nnz: Array   # (pr, pc) int32
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    grid: tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    # per-tile entry order, same vocabulary as COO.order. 'row' is the
    # maintained invariant: assembly sorts tiles row-major and every core op
    # either preserves it or re-establishes it via dedup (DESIGN.md §4.3),
    # so local kernels hit their sort-free fast paths.
    order: str = dataclasses.field(default="none", metadata=dict(static=True))

    @property
    def pr(self):
        return self.grid[0]

    @property
    def pc(self):
        return self.grid[1]

    @property
    def cap(self):
        return self.row.shape[-1]

    @property
    def mb(self):
        return _ceil(self.shape[0], self.pr * self.pc) * self.pc

    @property
    def nb(self):
        return _ceil(self.shape[1], self.pr * self.pc) * self.pr

    @property
    def total_nnz(self):
        return jnp.sum(self.nnz)

    def tile(self, squeeze3=True) -> COO:
        """Local COO view — call inside shard_map only."""
        r = self.row.reshape(self.cap)
        c = self.col.reshape(self.cap)
        v = self.val.reshape((self.cap,) + self.val.shape[3:])
        n = self.nnz.reshape(())
        return COO(r, c, v, n, (self.mb, self.nb), self.order)

    # ---------------- host-side assembly / extraction ----------------
    @staticmethod
    @_obs.timed("dist.assemble")
    def from_global_coo(shape, rows, cols, vals, grid, *, mesh: Mesh = None,
                        cap: int | None = None, pad: float = 1.25,
                        random_permute: bool = False, seed: int = 0,
                        vdims=(), order: str = "row"):
        """Assemble from global int64 COO (host-side numpy).

        ``order`` picks the per-tile entry sort — ``'row'`` (the maintained
        invariant) or ``'col'`` (so :meth:`regrid` can preserve a
        col-ordered matrix's tag through re-assembly).
        """
        M, N = shape
        pr, pc = grid
        if order not in ("row", "col"):
            raise ValueError(f"order must be 'row' or 'col', got {order!r}")
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        vals = np.asarray(vals)
        if random_permute:
            rng = np.random.default_rng(seed)
            rp = rng.permutation(M).astype(np.int64)
            cp = rp if M == N else rng.permutation(N).astype(np.int64)
            rows, cols = rp[rows], cp[cols]
        mb = _ceil(M, pr * pc) * pc
        nb = _ceil(N, pr * pc) * pr
        ti, tj = rows // mb, cols // nb
        lr = (rows % mb).astype(np.int32)
        lc = (cols % nb).astype(np.int32)
        tid = ti * pc + tj
        within_keys = (lc, lr) if order == "row" else (lr, lc)
        perm = np.lexsort(within_keys + (tid,))
        tid, lr, lc, vals_s = tid[perm], lr[perm], lc[perm], vals[perm]
        counts = np.bincount(tid, minlength=pr * pc)
        if cap is None:
            cap = max(8, int(math.ceil(counts.max() * pad / 8) * 8)) \
                if len(rows) else 8
        if counts.max() > cap:
            raise ValueError(f"tile overflow: max nnz {counts.max()} > cap {cap}")
        starts = np.zeros(pr * pc, np.int64)
        starts[1:] = np.cumsum(counts)[:-1]
        R = np.full((pr * pc, cap), SENTINEL, np.int32)
        Cc = np.full((pr * pc, cap), SENTINEL, np.int32)
        V = np.zeros((pr * pc, cap) + tuple(vdims), vals.dtype)
        within = np.arange(len(rows)) - starts[tid]
        R[tid, within] = lr
        Cc[tid, within] = lc
        V[tid, within] = vals_s
        # with a mesh the host arrays go straight to their tiles' devices
        # (shard_put below); a default-device copy first would hold the
        # whole matrix on jax.devices()[0]
        put = jnp.asarray if mesh is None else np.asarray
        out = DistSpMat(
            row=put(R.reshape(pr, pc, cap)),
            col=put(Cc.reshape(pr, pc, cap)),
            val=put(V.reshape((pr, pc, cap) + tuple(vdims))),
            nnz=put(counts.reshape(pr, pc).astype(np.int32)),
            shape=(int(M), int(N)), grid=(pr, pc),
            # the lexsort above sorted each tile by the requested key
            order=order)
        out = _faults.corrupt_spmat("dist.assemble", out)
        _audit.audit_obj(out, "dist.assemble", min_level=_audit.FULL)
        if mesh is not None:
            out = shard_put(out, mesh)
        return out

    @_obs.timed("dist.fetch")
    def to_global_coo(self):
        """Gather to host as (rows, cols, vals) in global int64 coords."""
        pr, pc, cap = self.pr, self.pc, self.cap
        R, C, V, Nz = _fetch(self.row, self.col, self.val, self.nnz)
        R = R.reshape(pr, pc, cap)
        C = C.reshape(pr, pc, cap)
        V = V.reshape((pr, pc, cap) + self.val.shape[3:])
        Nz = Nz.reshape(pr, pc)
        rows, cols, vals = [], [], []
        for i in range(pr):
            for j in range(pc):
                k = int(Nz[i, j])
                rows.append(R[i, j, :k].astype(np.int64) + i * self.mb)
                cols.append(C[i, j, :k].astype(np.int64) + j * self.nb)
                vals.append(V[i, j, :k])
        return (np.concatenate(rows), np.concatenate(cols),
                np.concatenate(vals))

    @_obs.timed("dist.regrid")
    def regrid(self, grid, *, mesh: Mesh = None, cap: int | None = None,
               pad: float = 1.25) -> "DistSpMat":
        """Re-distribute onto a new process grid (elastic shrink/grow).

        Round-trips through global COO and the normal assembly path, so
        entry values are bit-identical, the ``order`` tag is preserved
        ('none' tightens to 'row' — assembly sorts anyway), and the tile
        capacity is re-planned for the new tiling unless ``cap`` is given.
        This is the topology-recovery primitive: a 4×4 grid that lost
        devices regrids to 2×2 and every downstream op just works.
        """
        rows, cols, vals = self.to_global_coo()
        tag = self.order if self.order in ("row", "col") else "row"
        return DistSpMat.from_global_coo(
            self.shape, rows, cols, vals, tuple(grid), mesh=mesh, cap=cap,
            pad=pad, vdims=self.val.shape[3:], order=tag)

    def to_dense(self, zero=0.0) -> np.ndarray:
        r, c, v = self.to_global_coo()
        out = np.full(self.shape + self.val.shape[3:], zero,
                      np.asarray(self.val).dtype)
        out[r, c] = v
        return out


# --------------------------------------------------------------------------
# 3D (communication-avoiding) distributed sparse matrix
# --------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DistSpMat3D:
    """Sparse matrix on a (L, q, q) grid (paper §3.2, Fig 1).

    dist='acol': input-A style — columns sliced into L outer slabs; layer l
                 holds slab l as a 2D (q×q) matrix.
    dist='brow': input-B style — rows sliced into L outer slabs.
    dist='csub': output style (Fig 2) — within each column block j, columns
                 are sub-sliced into L pieces; layer l holds sub-piece l.
    """

    row: Array   # (L, q, q, cap) int32
    col: Array
    val: Array
    nnz: Array   # (L, q, q)
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    grid: tuple[int, int, int] = dataclasses.field(metadata=dict(static=True))
    dist: str = dataclasses.field(metadata=dict(static=True))
    order: str = dataclasses.field(default="none", metadata=dict(static=True))

    @property
    def L(self):
        return self.grid[0]

    @property
    def q(self):
        return self.grid[1]

    @property
    def cap(self):
        return self.row.shape[-1]

    def block_sizes(self):
        """(tile_rows, tile_cols) of each local tile.

        Every dimension is padded to a multiple of L*q*q so that (a) the
        contraction dims of acol-A and brow-B tiles agree and (b) partial-C
        column blocks subdivide exactly L ways for the inter-layer all-to-all.
        """
        M, N = self.shape
        L, q = self.L, self.q
        if self.dist == "acol":
            return _pad_to(M, L * q * q) // q, _pad_to(N, L * q * q) // (L * q)
        if self.dist == "brow":
            return _pad_to(M, L * q * q) // (L * q), _pad_to(N, L * q * q) // q
        if self.dist == "csub":
            return _pad_to(M, L * q * q) // q, _pad_to(N, L * q * q) // (L * q)
        raise ValueError(self.dist)

    def tile(self) -> COO:
        cap = self.cap
        tr, tc = self.block_sizes()
        return COO(self.row.reshape(cap), self.col.reshape(cap),
                   self.val.reshape((cap,) + self.val.shape[4:]),
                   self.nnz.reshape(()), (tr, tc), self.order)

    def _global_offsets(self, l, i, j):
        tr, tc = self.block_sizes()
        M, N = self.shape
        L, q = self.L, self.q
        if self.dist == "acol":
            return i * tr, l * (tc * q) + j * tc
        if self.dist == "brow":
            return l * (tr * q) + i * tr, j * tc
        if self.dist == "csub":
            return i * tr, j * (tc * L) + l * tc
        raise ValueError(self.dist)

    @staticmethod
    @_obs.timed("dist.assemble3d")
    def from_global_coo(shape, rows, cols, vals, grid, dist, *,
                        mesh: Mesh = None, cap=None, pad=1.25,
                        random_permute=False, seed=0):
        L, q, _ = grid
        M, N = shape
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        vals = np.asarray(vals)
        if random_permute:
            rng = np.random.default_rng(seed)
            rp = rng.permutation(M).astype(np.int64)
            cp = rp if M == N else rng.permutation(N).astype(np.int64)
            rows, cols = rp[rows], cp[cols]
        proto = DistSpMat3D(None, None, None, None, (int(M), int(N)),
                            (L, q, q), dist)
        tr, tc = proto.block_sizes()
        if dist == "acol":
            l = cols // (tc * q)
            i, j = rows // tr, (cols % (tc * q)) // tc
            lr, lc = rows % tr, cols % tc
        elif dist == "brow":
            l = rows // (tr * q)
            i, j = (rows % (tr * q)) // tr, cols // tc
            lr, lc = rows % tr, cols % tc
        else:  # csub
            jblk = cols // (tc * L)
            rem = cols % (tc * L)
            l, j = rem // tc, jblk
            i = rows // tr
            lr, lc = rows % tr, rem % tc
        tid = (l * q + i) * q + j
        order = np.lexsort((lc.astype(np.int32), lr.astype(np.int32), tid))
        tid = tid[order]
        lr, lc, vals_s = lr[order].astype(np.int32), lc[order].astype(np.int32), vals[order]
        ntile = L * q * q
        counts = np.bincount(tid, minlength=ntile)
        if cap is None:
            cap = max(8, int(math.ceil((counts.max() if len(rows) else 1)
                                       * pad / 8) * 8))
        if len(rows) and counts.max() > cap:
            raise ValueError(f"tile overflow: {counts.max()} > {cap}")
        starts = np.zeros(ntile, np.int64)
        starts[1:] = np.cumsum(counts)[:-1]
        R = np.full((ntile, cap), SENTINEL, np.int32)
        Cc = np.full((ntile, cap), SENTINEL, np.int32)
        V = np.zeros((ntile, cap), vals.dtype)
        within = np.arange(len(rows)) - starts[tid]
        R[tid, within] = lr
        Cc[tid, within] = lc
        V[tid, within] = vals_s
        put = jnp.asarray if mesh is None else np.asarray  # as in DistSpMat
        out = DistSpMat3D(
            row=put(R.reshape(L, q, q, cap)),
            col=put(Cc.reshape(L, q, q, cap)),
            val=put(V.reshape(L, q, q, cap)),
            nnz=put(counts.reshape(L, q, q).astype(np.int32)),
            shape=(int(M), int(N)), grid=(L, q, q), dist=dist,
            order="row")  # lexsort above is (lr, lc) within tile
        if mesh is not None:
            out = shard_put(out, mesh)
        return out

    def to_global_coo(self):
        L, q, cap = self.L, self.q, self.cap
        R = np.asarray(self.row)
        C = np.asarray(self.col)
        V = np.asarray(self.val)
        Nz = np.asarray(self.nnz)
        rows, cols, vals = [], [], []
        for l in range(L):
            for i in range(q):
                for j in range(q):
                    k = int(Nz[l, i, j])
                    ro, co = self._global_offsets(l, i, j)
                    rows.append(R[l, i, j, :k].astype(np.int64) + ro)
                    cols.append(C[l, i, j, :k].astype(np.int64) + co)
                    vals.append(V[l, i, j, :k])
        return (np.concatenate(rows), np.concatenate(cols),
                np.concatenate(vals))

    @_obs.timed("dist.regrid3d")
    def regrid(self, grid, *, mesh: Mesh = None, cap: int | None = None,
               pad: float = 1.25, dist: str | None = None) -> "DistSpMat3D":
        """Re-distribute onto a new (L, q, q) grid (elastic shrink/grow).

        The 3D analogue of :meth:`DistSpMat.regrid` — a replication-layer
        loss regrids (4, q, q) → (2, q, q) through global COO and the
        normal assembly path. ``dist`` defaults to the current
        distribution style; capacity is re-planned unless ``cap`` is given.
        """
        rows, cols, vals = self.to_global_coo()
        return DistSpMat3D.from_global_coo(
            self.shape, rows, cols, vals, tuple(grid), dist or self.dist,
            mesh=mesh, cap=cap, pad=pad)

    def to_dense(self, zero=0.0) -> np.ndarray:
        r, c, v = self.to_global_coo()
        out = np.full(self.shape, zero, np.asarray(self.val).dtype)
        out[r, c] = v
        return out


def _pad_to(n, mult):
    return _ceil(n, mult) * mult


# --------------------------------------------------------------------------
# distributed dense / sparse vectors
# --------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DistVec:
    """Fully distributed dense vector, no replication (paper §2.2)."""

    data: Array  # (pr, pc, vb)
    n: int = dataclasses.field(metadata=dict(static=True))
    grid: tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    layout: str = dataclasses.field(default="col", metadata=dict(static=True))

    @property
    def vb(self):
        return self.data.shape[2]

    def piece_id(self, i, j):
        return j * self.grid[0] + i if self.layout == "col" \
            else i * self.grid[1] + j

    @staticmethod
    def from_global(x, grid, layout="col", mesh: Mesh = None):
        pr, pc = grid
        x = np.asarray(x)
        n = x.shape[0]
        vb = _ceil(n, pr * pc)
        xp = np.zeros((pr * pc * vb,) + x.shape[1:], x.dtype)
        xp[:n] = x
        pieces = xp.reshape((pr * pc, vb) + x.shape[1:])
        out = np.empty((pr, pc, vb) + x.shape[1:], x.dtype)
        for i in range(pr):
            for j in range(pc):
                k = j * pr + i if layout == "col" else i * pc + j
                out[i, j] = pieces[k]
        v = DistVec(jnp.asarray(out), int(n), (pr, pc), layout)
        if mesh is not None:
            v = shard_put(v, mesh)
        return v

    @_obs.timed("dist.fetch")
    def to_global(self) -> np.ndarray:
        pr, pc = self.grid
        (d,) = _fetch(self.data)
        vb = self.vb
        xp = np.empty((pr * pc, vb) + d.shape[3:], d.dtype)
        for i in range(pr):
            for j in range(pc):
                k = j * pr + i if self.layout == "col" else i * pc + j
                xp[k] = d[i, j]
        return xp.reshape((pr * pc * vb,) + d.shape[3:])[:self.n]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DistSpVec:
    """Fully distributed sparse vector (FullyDistSpVec)."""

    idx: Array   # (pr, pc, cap) int32, piece-local indices
    val: Array   # (pr, pc, cap)
    nnz: Array   # (pr, pc) int32
    n: int = dataclasses.field(metadata=dict(static=True))
    grid: tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    layout: str = dataclasses.field(default="col", metadata=dict(static=True))

    @property
    def cap(self):
        return self.idx.shape[-1]

    @property
    def vb(self):
        pr, pc = self.grid
        return _ceil(self.n, pr * pc)

    @staticmethod
    def from_global(idx, val, n, grid, cap=None, layout="col",
                    mesh: Mesh = None, pad=1.5):
        pr, pc = grid
        idx = np.asarray(idx, np.int64)
        val = np.asarray(val)
        vb = _ceil(n, pr * pc)
        piece = idx // vb
        local = (idx % vb).astype(np.int32)
        counts = np.bincount(piece, minlength=pr * pc)
        if cap is None:
            cap = max(8, int(math.ceil((counts.max() if len(idx) else 1)
                                       * pad / 8) * 8))
        if len(idx) and counts.max() > cap:
            raise ValueError("piece overflow")
        order = np.lexsort((local, piece))
        piece, local, val_s = piece[order], local[order], val[order]
        starts = np.zeros(pr * pc, np.int64)
        starts[1:] = np.cumsum(counts)[:-1]
        I = np.full((pr * pc, cap), SENTINEL, np.int32)
        V = np.zeros((pr * pc, cap), val.dtype)
        within = np.arange(len(idx)) - starts[piece]
        I[piece, within] = local
        V[piece, within] = val_s
        Ii = np.empty((pr, pc, cap), np.int32)
        Vv = np.empty((pr, pc, cap), val.dtype)
        Nz = np.empty((pr, pc), np.int32)
        for i in range(pr):
            for j in range(pc):
                k = j * pr + i if layout == "col" else i * pc + j
                Ii[i, j], Vv[i, j], Nz[i, j] = I[k], V[k], counts[k]
        v = DistSpVec(jnp.asarray(Ii), jnp.asarray(Vv), jnp.asarray(Nz),
                      int(n), (pr, pc), layout)
        if mesh is not None:
            v = shard_put(v, mesh)
        return v

    @_obs.timed("dist.fetch")
    def to_global(self):
        pr, pc = self.grid
        I, V, Nz = _fetch(self.idx, self.val, self.nnz)
        idxs, vals = [], []
        for i in range(pr):
            for j in range(pc):
                k = j * pr + i if self.layout == "col" else i * pc + j
                c = int(Nz[i, j])
                idxs.append(I[i, j, :c].astype(np.int64) + k * self.vb)
                vals.append(V[i, j, :c])
        idx = np.concatenate(idxs)
        val = np.concatenate(vals)
        keep = idx < self.n
        return idx[keep], val[keep]

    def to_global_dense(self, zero=0.0):
        idx, val = self.to_global()
        out = np.full((self.n,), zero, np.asarray(self.val).dtype)
        out[idx] = val
        return out


# --------------------------------------------------------------------------
# sharding helpers
# --------------------------------------------------------------------------

_SPEC2 = {"DistSpMat": dict(row=P("row", "col", None),
                            col=P("row", "col", None),
                            val=P("row", "col", None),
                            nnz=P("row", "col")),
          "DistSpMat3D": dict(row=P("layer", "row", "col", None),
                              col=P("layer", "row", "col", None),
                              val=P("layer", "row", "col", None),
                              nnz=P("layer", "row", "col")),
          "DistVec": dict(data=P("row", "col", None)),
          "DistSpVec": dict(idx=P("row", "col", None),
                            val=P("row", "col", None),
                            nnz=P("row", "col"))}


def specs_of(obj):
    """Matching pytree of PartitionSpecs for a distributed object."""
    table = _SPEC2[type(obj).__name__]

    def fix(name, arr):
        spec = table[name]
        extra = arr.ndim - len(spec)
        return P(*(tuple(spec) + (None,) * extra))

    kw = {f.name: fix(f.name, getattr(obj, f.name))
          for f in dataclasses.fields(obj)
          if f.name in table}
    rest = {f.name: getattr(obj, f.name) for f in dataclasses.fields(obj)
            if f.name not in table}
    return dataclasses.replace(obj, **{**kw, **rest})


def shard_put(obj, mesh: Mesh):
    """Place a distributed object onto its mesh with the canonical sharding."""
    spec_tree = specs_of(obj)
    table = _SPEC2[type(obj).__name__]
    kw = {}
    for f in dataclasses.fields(obj):
        if f.name in table:
            kw[f.name] = jax.device_put(
                getattr(obj, f.name),
                NamedSharding(mesh, getattr(spec_tree, f.name)))
    return dataclasses.replace(obj, **kw)


# --------------------------------------------------------------------------
# mesh-independent sparse checkpoints (elastic topology recovery)
# --------------------------------------------------------------------------

@_obs.timed("dist.ckpt_save")
def save_spmat(ckpt_dir: str, step: int, m, *, keep: int = 3) -> str:
    """Checkpoint a DistSpMat/DistSpMat3D through the CRC-manifest path.

    The matrix is saved as *global COO* plus metadata — not as grid-shaped
    tiles — so :func:`restore_spmat` can re-assemble it onto ANY grid
    (including a smaller one after device loss). Rides the atomic-rename +
    per-leaf CRC32 + fallback-to-previous-step machinery of
    ``train/checkpoint.py`` unchanged.
    """
    from ..train.checkpoint import save_checkpoint   # lazy: train is heavy
    rows, cols, vals = m.to_global_coo()
    tree = {"rows": rows, "cols": cols, "vals": vals,
            "shape": np.asarray(m.shape, np.int64),
            "order": np.frombuffer(m.order.encode(), np.uint8),
            "kind": np.frombuffer(type(m).__name__.encode(), np.uint8)}
    if isinstance(m, DistSpMat3D):
        tree["dist"] = np.frombuffer(m.dist.encode(), np.uint8)
    return save_checkpoint(ckpt_dir, step, tree, keep=keep)


@_obs.timed("dist.ckpt_restore")
def restore_spmat(ckpt_dir: str, grid, *, mesh: Mesh = None,
                  step: int | None = None, cap: int | None = None,
                  pad: float = 1.25, dist: str | None = None):
    """Restore a sparse checkpoint onto ``grid`` (any shape); returns
    ``(matrix, step)``.

    The target ``grid`` chooses the container family: a 2-tuple rebuilds a
    :class:`DistSpMat`, a 3-tuple a :class:`DistSpMat3D` (``dist`` defaults
    to the saved distribution style). Capacity is re-planned for the target
    tiling and the saved ``order`` tag is preserved — the regrid-on-resume
    half of elastic recovery.
    """
    from ..train.checkpoint import restore_flat      # lazy: train is heavy
    state, step = restore_flat(ckpt_dir, step)
    shape = tuple(int(x) for x in np.asarray(state["shape"]))
    saved_order = bytes(np.asarray(state["order"])).decode()
    tag = saved_order if saved_order in ("row", "col") else "row"
    grid = tuple(int(g) for g in grid)
    if len(grid) == 3:
        d = dist or bytes(np.asarray(state["dist"])).decode()
        m = DistSpMat3D.from_global_coo(
            shape, state["rows"], state["cols"], state["vals"], grid, d,
            mesh=mesh, cap=cap, pad=pad)
    else:
        vals = np.asarray(state["vals"])
        m = DistSpMat.from_global_coo(
            shape, state["rows"], state["cols"], vals, grid, mesh=mesh,
            cap=cap, pad=pad, vdims=vals.shape[1:], order=tag)
    return m, step
