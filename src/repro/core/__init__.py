"""repro.core — CombBLAS 2.0 primitives in JAX (the paper's contribution).

Layering:
  semiring      generalized (add, mul) algebra + segment reductions
  coo           capacity-padded local sparse tiles (SpMat analogue)
  merge         sort-free merge engine: packed-key dedup, rank-placement
                merging, kv stage pipeline (§4.4)
  local_spgemm  ESC / dense-accumulator / hybrid local multiply (§4.1)
  spmv_local    SpMV + SpMSpV variant families (§4.2–4.3)
  dist          SpParMat / FullyDist[Sp]Vec containers (§2.1–2.2)
  mask          output masks (GraphBLAS C⟨M⟩) + membership probes (§4.7)
  spgemm        2D SUMMA (rotation/allgather) + 3D CA SpGEMM (§3.2)
  spmv          distributed SpMV / SpMSpV (§3.1)
  spmm          1.5D + true-2D SpMM
  assign        skew-aware vector assign / extract (§3.3)
  plan          capacity planner + variant rules of thumb (§5, §7)
  compat        mesh axis types + the engine's shard_map (jax 0.9.0)
"""
from . import compat, merge, semiring
from .coo import COO, SENTINEL, column_range, ewise_intersect, ewise_union
from .merge import (dedup_sorted, merge_capped, merge_sorted, merge_tree,
                    pack_keys)
from .dist import (DistSpMat, DistSpMat3D, DistSpVec, DistVec, make_grid,
                   shard_put, specs_of)
from .local_spgemm import (compression_ratio, spgemm_auto, spgemm_dense,
                           spgemm_esc, spgemm_flops)
from .mask import (LocalMask, MaskSpec, complement_of, local_mask,
                   mask_member, structural, value_mask, vector_mask)
from .semiring import (ARITHMETIC, BOOLEAN, MAX_MIN, MAX_PLUS, MIN_PLUS,
                       MIN_SELECT2ND, Monoid, Semiring, segment_reduce,
                       semiring as make_semiring)
from .spgemm import spgemm_2d, spgemm_2d_batched, spgemm_3d
from .spmm import local_spmm, spmm_15d, spmm_2d
from .spmv import (spmspv, spmv, spmv_iter, transpose_layout,
                   transpose_spvec_layout)
from .spmv_local import (SPMSPV_VARIANTS, spmspv_auto, spmv_col, spmv_row,
                         spvec_from_dense, spvec_to_dense)
from .assign import assign, extract
from .plan import (LocalSpGEMMPlan, LocalSpMSpVPlan, SpGEMMPlan, SpMSpVPlan,
                   plan_local_spgemm, plan_local_spmspv, plan_spgemm,
                   plan_spmspv, spmspv_variant_for_density, spmv_variant)
from .plan import spgemm as spgemm_planned
from .plan import spmspv as spmspv_planned
