"""Plain references of the benchmark's entry points, and their controls.

The references are ``chip_smoke.py``'s SciPy references, copied so that a
change to the program cannot change what ``correct`` compares against.
They import nothing of the program and take nothing that it made: they
read only the host edge list that the benchmark generated.

Convention of the program: entry (v, u) of A is the edge u -> v.

The controls are what each cell's comparison has to reject: the
reference put in the program's place, computed one step below the
precision or guarantee that the configuration states.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sps
from scipy.sparse import csgraph


def adjacency(n: int, rows, cols) -> sps.csr_matrix:
    """The float64 host matrix of the edge list (unit values)."""
    return sps.csr_matrix((np.ones(len(rows)), (rows, cols)), shape=(n, n))


def bfs_levels(a: sps.csr_matrix, roots) -> np.ndarray:
    """Hop counts from each root, -1 where unreached: (len(roots), n)."""
    dist = csgraph.shortest_path(a.T.tocsr(), unweighted=True,
                                 indices=np.asarray(roots))
    return np.where(np.isinf(dist), -1, dist).astype(np.int32).reshape(
        len(roots), a.shape[0])


def spgemm_columns(a: sps.csr_matrix, cols) -> sps.csr_matrix:
    """A @ A[:, cols], with sorted indices."""
    c = (a @ a[:, np.asarray(cols)]).tocsr()
    c.sort_indices()
    return c


def triangles(a: sps.csr_matrix) -> int:
    """Triangles of the symmetric graph: sum of (L @ L) masked by L."""
    low = sps.tril(a, -1).tocsr()
    return int(round((low @ low).multiply(low).sum()))


# --------------------------------------------------------------------------
# controls
# --------------------------------------------------------------------------

def bfs_levels_stopped_early(a: sps.csr_matrix, roots) -> np.ndarray:
    """The search cut one level short: the guarantee that every reachable
    vertex gets its hop count is broken for the deepest level (a BFS has
    no precision to lower; this is the early stop that would tempt)."""
    lv = bfs_levels(a, roots)
    deepest = lv.max(axis=1, keepdims=True)
    return np.where((lv == deepest) & (deepest > 0), -1, lv)


def spgemm_columns_bf16(a: sps.csr_matrix, cols) -> sps.csr_matrix:
    """A @ A[:, cols] with every value held in bfloat16, one step below
    the configuration's float32 (sums above 256 lose their last bits)."""
    c = spgemm_columns(a, cols)
    c.data = c.data.astype(ml_dtypes.bfloat16).astype(np.float64)
    return c


def triangles_bf16(a: sps.csr_matrix) -> int:
    """The count with every partial sum held in bfloat16, one step below
    the configuration's float32: each row's count, then their total."""
    low = sps.tril(a, -1).tocsr()
    per_row = np.asarray((low @ low).multiply(low).sum(axis=1)).ravel()
    return int(per_row.astype(ml_dtypes.bfloat16).sum(
        dtype=ml_dtypes.bfloat16))
