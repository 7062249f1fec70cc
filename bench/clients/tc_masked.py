"""Client of the ``tc_masked`` traffic: GraphChallenge triangle counting.

Each call is one whole ``repro.apps.triangle_count`` of the run's graph:
L = strict lower triangle, then the sum of (L·L) masked by L through the
planner.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sps

from bench.graph import Graph
from bench.reference import graphs as ref


class Traffic:
    def __init__(self, g: Graph, config: dict, traffic: dict, seed: int):
        self.g, self.config = g, config
        low = sps.tril(g.host, -1).tocsr()
        # products the masked L·L expands: sum over k of nnz(L[:, k]) *
        # nnz(L[k, :]), before the mask screens them
        self.mults = int(np.dot(np.diff(low.tocsc().indptr),
                                np.diff(low.indptr)))
        self.edges = len(g.rows) / 2.0
        self.items = [0]
        self.warmup = [0]
        self.limits = traffic["limits"]
        self._want = None

    def place(self, a, mesh) -> None:
        self.a, self.mesh = a, mesh

    def release(self) -> None:
        self.a = self.mesh = None

    def call(self, _item: int):
        """One timed call: the whole count, on the host."""
        from repro.apps import triangle_count
        return triangle_count(self.a, mesh=self.mesh), {}

    def work(self, _item: int) -> float:
        """Undirected edges of the graph (GraphChallenge's edges/s)."""
        return self.edges

    def counts(self, _item: int, _out) -> dict:
        return {"mults": self.mults}

    def check(self, done: list) -> dict:
        if self._want is None:
            self._want = ref.triangles(self.g.host)
        return {"count_gap": max((abs(int(got) - self._want)
                                  for _, got in done), default=0)}

    def control(self, _item: int):
        """The reference in the program's place, summed in bfloat16."""
        return ref.triangles_bf16(self.g.host)
