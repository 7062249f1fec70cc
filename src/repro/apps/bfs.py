"""Breadth-first search via masked SpMSpV (the original CombBLAS demo app).

Level-synchronous BFS: the frontier is a FullyDistSpVec, each step is one
SpMSpV over the boolean semiring with the visited (levels) vector pushed in
as a COMPLEMENT mask (§4.7): already-visited vertices are discarded inside
the local expansion — before the variant merges and the 'col' exchange —
instead of being generated, shipped, and thrown away by a post-hoc
piece-aligned filter. The planner additionally caps the output at the
unvisited count, so sort/merge volumes shrink as the search saturates (the
direction-optimizing payoff without the pull-side kernel).

Capacities are chosen by the planner (core/plan.py) from the *current*
frontier size each level — the local SpMSpV data structure follows the
Fig-3 density rule, and an overflowing level retries with grown caps
instead of asserting. Pass ``prod_cap``/``out_cap`` only to override.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh

from ..core import (BOOLEAN, DistSpMat, DistSpVec, DistVec,
                    transpose_spvec_layout)
from ..obs import recorder as _obs
from ..core.mask import vector_mask
from ..core.matops import spvec_nnz, vec_scatter_spvec
from ..core.plan import plan_spmspv, spmspv as spmspv_planned


def bfs_levels(a: DistSpMat, source: int, *, mesh: Mesh,
               prod_cap: int | None = None, out_cap: int | None = None,
               max_iters: int | None = None) -> np.ndarray:
    """Return per-vertex BFS levels (-1 = unreachable) from ``source``.

    ``a`` is interpreted as directed adjacency with edges u→v for entry
    (v, u) — i.e. we multiply y = A x so neighbors of the frontier x appear
    in y (CombBLAS convention: use A^T for the usual orientation).
    """
    n = a.shape[0]
    grid = a.grid
    levels = DistVec.from_global(np.full(n, -1, np.int32), grid,
                                 layout="row", mesh=mesh)
    # frontier capacity: the planner's output cap for a worst-case frontier
    # (so pieces never truncate); explicit out_cap still wins
    fcap = out_cap or plan_spmspv(a, n, out_cap=out_cap).out_cap
    frontier = DistSpVec.from_global(np.array([source], np.int64),
                                     np.ones(1, np.bool_), n, grid,
                                     cap=fcap, layout="row", mesh=mesh)
    levels = vec_scatter_spvec(levels, frontier,
                               lambda cur, xv: jnp.zeros_like(cur))
    level = 0
    max_iters = max_iters or n
    # one device→host read of the frontier's size a level: the loop
    # condition, and the level span's attribute
    frontier_nnz = _frontier_nnz(frontier)
    while frontier_nnz > 0 and level < max_iters:
        level += 1
        with _obs.span("bfs.level", level=level, frontier_nnz=frontier_nnz):
            fcol = transpose_spvec_layout(frontier, mesh=mesh)
            # visited vertices (level >= 0) as a complement mask: the fused
            # kernel emits ONLY unvisited neighbors — no post-filter pass
            visited = vector_mask(levels, pred=lambda lv: lv >= 0,
                                  complement=True)
            nxt, _plan = spmspv_planned(a, fcol, BOOLEAN, mesh=mesh,
                                        mask=visited,
                                        prod_cap=prod_cap, out_cap=out_cap)
            levels = vec_scatter_spvec(
                levels, nxt, lambda cur, xv: jnp.full_like(cur, level))
            frontier = nxt
        frontier_nnz = _frontier_nnz(frontier)
    return levels.to_global().astype(np.int32)


def _frontier_nnz(frontier: DistSpVec) -> int:
    _obs.counter_add("host.sync.bfs.frontier")
    return int(spvec_nnz(frontier))
