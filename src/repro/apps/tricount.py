"""Triangle counting via masked SpGEMM: tri = Σ (L·L)⟨L⟩.

L is the strict lower triangle; (L·L)[i,j] counts k with j<k<i adjacent to
both, and the structural mask L keeps (i,j) edges — each triangle counted
exactly once. This is a TRUE masked multiply (§4.7): L is passed as the
output mask of the SpGEMM itself, so non-edge products are discarded before
any merge stage and the planner sizes out/stage capacities from nnz(L)
instead of nnz(L·L) — no post-hoc ewise intersection ever materializes the
unmasked product.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.sharding import Mesh

from ..core import ARITHMETIC, DistSpMat
from ..core.mask import structural
from ..core.matops import mat_apply_local, mat_select_lower, mat_sum
from ..core.plan import spgemm as spgemm_planned
from ..obs import recorder as _obs


def triangle_count(a: DistSpMat, *, mesh: Mesh, prod_cap: int | None = None,
                   out_cap: int | None = None) -> int:
    """Count triangles of the symmetric graph ``a`` (values ignored)."""
    with _obs.span("tricount"):
        ones = lambda t: t.apply(lambda v: jnp.ones_like(v))
        l = mat_select_lower(mat_apply_local(a, ones, mesh=mesh), mesh=mesh)
        b, _plan = spgemm_planned(l, l, ARITHMETIC, mesh=mesh,
                                  mask=structural(l),
                                  prod_cap=prod_cap, out_cap=out_cap)
        _obs.counter_add("host.sync.mat_sum")
        return int(mat_sum(b))
