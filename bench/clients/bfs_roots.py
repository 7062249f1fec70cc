"""Client of the ``bfs_roots`` traffic: Graph500 kernel 2 searches.

A call is one round of ``repro.apps.bfs_levels`` searches, one from each
search key, back to back. The keys are sampled once from the
configuration's graph among vertices of degree >= ``min_degree``, as
Graph500 samples them, so every seed searches from the same vertices
(under its own labels, in its own order) and does the same work. Every
level runs at fixed worst-case SpMSpV caps (the tile capacity and the
vertex count), so every level of every search runs the same programs.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import csgraph

from bench.graph import Graph, rng
from bench.reference import graphs as ref


class Traffic:
    def __init__(self, g: Graph, config: dict, traffic: dict, seed: int):
        self.g, self.config = g, config
        deg = np.bincount(g.rows, minlength=g.n)[g.label]   # generator labels
        keys = rng(config["graph_seed"], 2).choice(
            np.flatnonzero(deg >= traffic["min_degree"]),
            traffic["search_keys"], replace=False)
        batch = g.label[keys][rng(seed, 1).permutation(len(keys))]
        self.items = [tuple(int(v) for v in batch)]
        self.warmup = [self.items[0][:1]]
        _, comp = csgraph.connected_components(g.host, directed=False)
        # undirected input edges of each component: stored entries / 2
        self.comp, self.comp_edges = comp, np.bincount(
            comp[g.rows], minlength=comp.max() + 1) / 2.0
        self.caps = dict(prod_cap=g.tile_cap, out_cap=g.n)
        self.limits = traffic["limits"]

    def place(self, a, mesh) -> None:
        self.a, self.mesh = a, mesh

    def release(self) -> None:
        self.a = self.mesh = None

    def call(self, roots: tuple):
        """One timed call: the levels of each search, on the host."""
        from repro.apps import bfs_levels
        return np.stack([bfs_levels(self.a, source=r, mesh=self.mesh,
                                    **self.caps) for r in roots]), {}

    def work(self, roots: tuple) -> float:
        """Undirected input edges in each root's component (Graph500 TEPS)."""
        return float(sum(self.comp_edges[self.comp[r]] for r in roots))

    def counts(self, roots: tuple, levels) -> dict:
        """Levels the searches ran: each one's deepest level, and one more
        that finds the frontier empty."""
        return {"levels": int(np.sum(np.max(levels, axis=1) + 1))}

    def check(self, done: list) -> dict:
        wrong = 0
        for roots, got in done:
            want = ref.bfs_levels(self.g.host, roots)
            got = np.asarray(got)
            wrong += (int(np.count_nonzero(got != want))
                      if got.shape == want.shape else want.size)
        return {"levels_wrong": wrong}

    def control(self, roots: tuple):
        """The reference in the program's place, cut one level short."""
        return ref.bfs_levels_stopped_early(self.g.host, roots)
