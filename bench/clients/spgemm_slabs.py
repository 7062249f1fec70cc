"""Client of the ``spgemm_slabs`` traffic: planned unmasked A·A[:, slab].

Each call multiplies A by one column slab of A through the planner
(``repro.core.plan.spgemm``, arithmetic semiring) and fetches the product
to the host. The slabs hold equally many columns and near-equal work:
the columns, heaviest first, are dealt to the slabs in snake order. A
column's weight is the multiplications it needs, which do not depend on
the labels, so every seed multiplies the same slabs, in an order and
under labels drawn from the seed.
"""
from __future__ import annotations

import numpy as np

from bench.graph import Graph, rng, tile_cap
from bench.reference import graphs as ref


def _slab_columns(g: Graph, slabs: int) -> list[np.ndarray]:
    deg = np.bincount(g.rows, minlength=g.n)          # = nnz(A[:, k])
    # multiplications column j of B needs: sum of nnz(A[:, k]) over its k
    weight = np.bincount(g.cols, weights=deg[g.rows], minlength=g.n)
    order = np.lexsort((g.canonical(np.arange(g.n)), -weight))
    snake = np.concatenate([np.arange(slabs), np.arange(slabs)[::-1]])
    slab = np.empty(g.n, np.int64)
    slab[order] = snake[np.arange(g.n) % (2 * slabs)]
    return [np.flatnonzero(slab == k) for k in range(slabs)]


def entries_wrong(got, want, cols: np.ndarray) -> int:
    """Entries of ``got`` = (rows, cols, vals) that ``want`` (the product's
    columns ``cols``, in order) lacks, holds with another value, or holds
    once where ``got`` repeats them; plus those ``want`` holds and ``got``
    lacks."""
    gr, gc, gv = got
    m = len(cols)
    pos = np.searchsorted(cols, gc)
    inside = (pos < m) & (cols[np.minimum(pos, m - 1)] == gc)
    key = gr[inside] * m + pos[inside]
    val = np.asarray(gv, np.float64)[inside]
    w = want.tocoo()
    wkey = w.row.astype(np.int64) * m + w.col
    ukey, first = np.unique(key, return_index=True)
    common, ig, iw = np.intersect1d(ukey, wkey, assume_unique=True,
                                    return_indices=True)
    return int(np.count_nonzero(~inside) + (len(key) - len(ukey))
               + (len(ukey) - len(common)) + (len(wkey) - len(common))
               + np.count_nonzero(val[first][ig] != w.data[iw]))


class Traffic:
    def __init__(self, g: Graph, config: dict, traffic: dict, seed: int):
        self.g, self.config, self.traffic = g, config, traffic
        slabs = traffic["slabs"]
        self.cols = _slab_columns(g, slabs)
        self.slab_of = np.empty(g.n, np.int64)
        for k, c in enumerate(self.cols):
            self.slab_of[c] = k
        entry_slab = self.slab_of[g.cols]
        a_col = np.bincount(g.cols, minlength=g.n)      # nnz(A[:, k])
        self.mults = [int(np.dot(a_col, np.bincount(
            g.rows[entry_slab == k], minlength=g.n))) for k in range(slabs)]
        pr, pc = config["grid"]
        per_slab = np.bincount(entry_slab, minlength=slabs).max()
        self.slab_cap = tile_cap(per_slab, pr * pc, traffic["slab_cap_pad"])
        self.items = [int(k) for k in rng(seed, 1).permutation(slabs)]
        self.warmup = self.items[:1]
        self.limits = traffic["limits"]
        self.b = None

    def place(self, a, mesh) -> None:
        """Put A and every slab B = A[:, slab] in tiles on the mesh."""
        from repro.core import DistSpMat
        g = self.g
        self.a, self.mesh = a, mesh
        entry_slab = self.slab_of[g.cols]
        self.b = []
        for k in range(len(self.cols)):
            sel = entry_slab == k
            self.b.append(DistSpMat.from_global_coo(
                (g.n, g.n), g.rows[sel], g.cols[sel],
                np.ones(int(sel.sum()), np.float32), tuple(a.grid),
                mesh=mesh, cap=self.slab_cap))

    def release(self) -> None:
        self.a = self.b = self.mesh = None

    def call(self, k: int):
        """One timed call: C = A·A[:, slab k], fetched to the host."""
        from repro.core import ARITHMETIC
        from repro.core import plan
        c, p = plan.spgemm(self.a, self.b[k], ARITHMETIC, mesh=self.mesh)
        return c.to_global_coo(), {"degraded": p.degraded}

    def work(self, k: int) -> float:
        """Floating-point operations the product needs: 2 per useful
        multiplication."""
        return 2.0 * self.mults[k]

    def counts(self, k: int, out) -> dict:
        return {"mults": self.mults[k]}

    def check(self, done: list) -> dict:
        """{number: value} over the completed calls ``(item, output)``."""
        wrong = 0
        for k, out in done:
            wrong += entries_wrong(out, ref.spgemm_columns(
                self.g.host, self.cols[k]), self.cols[k])
        return {"entries_wrong": wrong}

    def control(self, k: int):
        """The reference in the program's place, values in bfloat16."""
        c = ref.spgemm_columns_bf16(self.g.host, self.cols[k]).tocoo()
        return (c.row.astype(np.int64), self.cols[k][c.col],
                c.data.astype(np.float32))
