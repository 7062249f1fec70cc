"""Quickstart: the sparse library in 60 lines.

Builds an R-MAT graph, runs BFS / PageRank / connected components /
triangle counting, and shows a user-defined semiring (min-plus shortest
paths via SpGEMM powers) — the CombBLAS 2.0 tour.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import (ARITHMETIC, MIN_PLUS, DistSpMat, make_grid,
                        make_semiring, spgemm_2d)
from repro.apps import bfs_levels, fastsv, pagerank, triangle_count
from repro.io import rmat_coo
from repro.launch.cache import use_compile_cache


def main():
    use_compile_cache()
    mesh = make_grid(1, 1)           # same code runs on any (pr, pc) grid
    shape, rows, cols, vals = rmat_coo(9, 8, seed=0, symmetrize=True,
                                       drop_self_loops=True)
    A = DistSpMat.from_global_coo(shape, rows, cols, vals, (1, 1),
                                  mesh=mesh, random_permute=True)
    print(f"graph: {shape[0]} vertices, {len(rows)} edges")

    lv = bfs_levels(A, source=0, mesh=mesh)
    print(f"BFS: reached {(lv >= 0).sum()} vertices, "
          f"eccentricity {lv.max()}")

    pr = pagerank(A, mesh=mesh, max_iters=30)
    print(f"PageRank: top vertex {int(np.argmax(pr))} "
          f"score {pr.max():.5f}")

    cc = fastsv(A, mesh=mesh)
    print(f"Connected components: {len(set(cc))}")

    tri = triangle_count(A, mesh=mesh, prod_cap=1 << 18, out_cap=1 << 17)
    print(f"Triangles: {tri}")

    # --- user-defined semiring: 2-hop shortest paths via min-plus SpGEMM
    W = DistSpMat.from_global_coo(
        shape, rows, cols,
        np.random.default_rng(0).random(len(rows)).astype(np.float32) + 0.1,
        (1, 1), mesh=mesh)
    P2, ok = spgemm_2d(W, W, MIN_PLUS, mesh=mesh, prod_cap=1 << 20,
                       out_cap=1 << 17)
    print(f"min-plus A^2: {int(P2.total_nnz)} 2-hop paths, ok={bool(ok.all())}")

    # --- heterogeneous user algebra: count common neighbors (plus_pair)
    plus_pair = make_semiring(jnp.add, 0, lambda a, b: jnp.ones((), jnp.float32),
                         tag="sum", name="plus_pair")
    CN, ok = spgemm_2d(A, A, plus_pair, mesh=mesh, prod_cap=1 << 20,
                       out_cap=1 << 17)
    print(f"common-neighbor counts: nnz={int(CN.total_nnz)}")


if __name__ == "__main__":
    main()
