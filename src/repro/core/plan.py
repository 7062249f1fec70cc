"""Capacity planner — the paper's "rules of thumb", made executable.

CombBLAS 2.0 sizes SpGEMM outputs with a symbolic phase before the numeric
phase (§4.1) and gives scenario rules for picking data structures and
algorithm variants (§5, §7). JAX/XLA adds a twist: every buffer is a static
*capacity*, so a wrong guess either truncates (too small) or wastes memory
and compile cache (too large). This module centralizes the guessing:

  1. **Estimate** flops and nnz(C) from tile nnz statistics — a cheap
     symbolic pass over the host-resident ``DistSpMat.nnz`` array (p numbers
     per operand, no device work), or the exact ``spgemm_flops`` count for
     single tiles.
  2. **Derive** ``prod_cap`` / ``out_cap`` with a safety factor, quantized
     to powers of two so repeated planning reuses compiled executables.
  3. **Bound** every cap by a true worst case (products can never exceed
     nnz(A-tile)·nnz(B-tile) per stage; outputs never exceed the dense
     tile), so overflow-retry terminates.
  4. **Retry on overflow**: the kernels' ``ok`` flags are checked on the
     host; a failed attempt re-runs with grown caps instead of returning
     truncated results.
  5. **Pick variants** by the paper's rules of thumb (DESIGN.md §4.6):
     deferred vs incremental merge by product-buffer memory, rotation vs
     allgather by gathered-operand memory, SpMV vs SpMSpV (and the local
     SpMSpV data structure) by frontier density (§4.5, Fig 3).

Apps call ``spgemm`` / ``spmspv`` below with NO capacity arguments; explicit
caps remain available as overrides and short-circuit the estimator.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import recorder as _obs
from ..robust import (audit as _audit, deadline as _deadline,
                      faults as _faults, recover as _recover)
from .coo import COO
from .dist import DistSpMat, DistSpVec
from .local_spgemm import compression_ratio, spgemm_flops
from .semiring import ARITHMETIC, Semiring
from .spgemm import spgemm_2d as _spgemm_2d
from .spmv import spmspv as _spmspv_2d

# Per-device scratch budget for planner decisions, in *entries* (a COO entry
# is ~16 bytes with indices): ~64 MB. Crossing it flips the memory-saving
# variant choices; it never bounds correctness (caps still grow on retry).
MEM_BUDGET_ENTRIES = 1 << 22

# Below this many total product slots (q·prod_cap) the legacy single
# concat-and-sort merge beats the merge tree: per-stage compaction and the
# pairwise rank-placement merges carry fixed overheads that a few thousand
# entries never amortize (DESIGN.md §4.4/§4.6).
SORT_MERGE_ENTRIES = 1 << 13

# Hybrid-schedule rule of thumb (DESIGN.md §4.6/§4.8, after McFarland et
# al. arXiv 2504.06408): when the per-stage wire volumes are skewed
# (coefficient of variation of the stage operand sizes above this), batch
# the sparsest stages into one fused eager exchange (the all-to-all leg)
# and stream the dense stages as per-stage broadcasts. Uniform stages gain
# nothing from splitting the sweep, so they keep the rotate schedule.
HYBRID_STAGE_SKEW = 0.5

# Mask pushdown rule of thumb (DESIGN.md §4.6/§4.7): fused masking beats
# unmasked-then-filter when the mask admits at most this fraction of the
# unmasked output estimate — below it the mask-sized out/stage caps drop a
# pow2 tier and every merge stage shrinks; above it only the membership
# probe (O(log nnz(M)) per product) and the skipped post-filter pass remain,
# which is ~parity. Capacity shrinking applies the bound unconditionally
# (it is exact, not a heuristic); this constant documents where the *win*
# starts (the BENCH_spgemm.json masked rows track it).
MASK_PUSHDOWN_RATIO = 0.5


def _pow2(x: float, lo: int = 64) -> int:
    """Round up to a power of two (compile-cache-friendly cap quantization)."""
    return max(lo, 1 << math.ceil(math.log2(max(float(x), 1.0))))


def _host_read(site: str, x):
    """``jax.device_get(x)``: a blocking device→host read of the planner,
    counted as ``host.sync.plan.<site>``."""
    _obs.counter_add("host.sync.plan." + site)
    return jax.device_get(x)


def _host_nnz(a) -> np.ndarray:
    return np.asarray(_host_read("nnz", a.nnz), np.float64)


# --------------------------------------------------------------------------
# distributed SpGEMM
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SpGEMMPlan:
    prod_cap: int          # per-stage expansion slots per device
    out_cap: int           # merged output entries per device
    variant: str           # 'rotation' | 'allgather'
    merge: str             # 'sort' | 'deferred' | 'incremental' (§4.4)
    prod_ceiling: int      # worst-case bound — retry growth stops here
    out_ceiling: int
    est_flops: float       # estimated peak per-device per-stage products
    est_out: float         # estimated peak per-device nnz(C)
    attempts: int = 1      # how many numeric attempts the retry loop used
    degraded: tuple = ()   # ladder rungs taken (robust/recover.py), in order
    # exchange schedule (§4.8): None derives from the variant; 'rotate' |
    # 'alltoall' | 'bcast' | a length-q tuple of 'bcast'|'gather' entries
    schedule: object = None
    overlap: bool = True   # double-buffered stage loops (False = bulk-sync)
    compress: Optional[str] = None   # 'int8' wire compression of values

    def at_ceiling(self) -> bool:
        return (self.prod_cap >= self.prod_ceiling
                and self.out_cap >= self.out_ceiling)

    def grown(self, factor: int = 4) -> "SpGEMMPlan":
        if (self.prod_cap >= self.prod_ceiling
                and self.out_cap >= self.out_ceiling):
            raise RuntimeError(
                "SpGEMM overflow at worst-case capacities "
                f"(prod_cap={self.prod_cap}, out_cap={self.out_cap}) — "
                "the ok flags disagree with the symbolic bound")
        return dataclasses.replace(
            self,
            prod_cap=min(self.prod_cap * factor, self.prod_ceiling),
            out_cap=min(self.out_cap * factor, self.out_ceiling),
            attempts=self.attempts + 1)


def plan_spgemm(a: DistSpMat, b: DistSpMat | None = None, *,
                safety: float = 4.0,
                prod_cap: int | None = None, out_cap: int | None = None,
                variant: str | None = None, merge: str | None = None,
                mask=None, schedule=None, overlap: bool = True,
                compress: str | None = None,
                mem_budget: int = MEM_BUDGET_ENTRIES) -> SpGEMMPlan:
    """Size and configure a 2D SpGEMM from tile nnz statistics.

    The estimate assumes entries spread uniformly over tile columns (the
    random-permutation load-balance story of §2.3); skewed inputs are caught
    by the overflow flags and absorbed by the safety factor + retry growth.

    ``schedule`` (§4.8): when neither variant nor schedule is forced, the
    planner inspects the per-stage wire volumes (stage k moves A(·,k) and
    B(k,·)); skewed stages (cv > ``HYBRID_STAGE_SKEW``) pick a hybrid
    per-stage tuple — the sparsest stages batched into one fused eager
    exchange ('gather'), the rest per-stage broadcasts ('bcast') — while
    uniform stages keep the variant-derived whole-sweep schedule.
    ``overlap`` and ``compress`` ride on the plan so the retry loop and the
    degradation ladder ('serial-schedule' rung) can flip them.

    ``mask`` (a ``mask.MaskSpec``): a pattern mask bounds the per-tile
    output EXACTLY — a structural mask's C tile holds at most its mask
    tile's nnz, a complement mask's at most dense-tile − nnz — so both the
    out estimate and the retry ceiling intersect with the mask stats and
    every mask-sized sort/merge stage shrinks with them (§4.7). Value-only
    masks have unknown selectivity and change nothing here.
    """
    b = a if b is None else b
    q = a.pr
    na = _host_nnz(a).reshape(q, q)
    nb_ = _host_nnz(b).reshape(q, q)
    inner = float(max(a.nb, 1))            # contraction dim of one tile pair

    # stage (i, j, k) multiplies A(i,k) · B(k,j): expected products under
    # uniform column occupancy, exact upper bound nnz_a * nnz_b
    pair = na[:, :, None] * nb_[None, :, :]          # [i, k, j] -> products
    stage_est = float(pair.max()) / inner
    stage_bound = float(pair.max())
    # per-device output: flops estimate summed over stages, capped by the
    # dense C tile (distinct (row, col) pairs cannot exceed it)
    flops_dev = np.einsum("ik,kj->ij", na, nb_) / inner
    dense_tile = float(a.mb) * float(b.nb)
    out_bound = min(stage_bound * q, dense_tile)
    if mask is not None and mask.mat is not None:
        mn = _host_nnz(mask.mat).reshape(q, q)
        if not mask.complement:
            # structural (pred or not): members ⊆ stored entries
            mask_bound = float(mn.max())
        elif mask.pred is None:
            # complement: admissible slots = dense tile − stored entries
            mask_bound = float(dense_tile - mn.min())
        else:
            # complement of a pred-subselected mask admits UP TO the dense
            # tile (pred may reject every stored entry) — no valid shrink
            mask_bound = dense_tile
        out_bound = min(out_bound, max(mask_bound, 1.0))
    out_est = float(min(flops_dev.max(), out_bound))

    p_ceil = _pow2(stage_bound)
    o_ceil = _pow2(out_bound)
    p_cap = min(_pow2(prod_cap or safety * stage_est), p_ceil)
    o_cap = min(_pow2(out_cap or safety * out_est), o_ceil)
    if prod_cap:
        p_cap = max(p_cap, _pow2(prod_cap))   # explicit override wins
        p_ceil = max(p_ceil, p_cap)
    if out_cap:
        o_cap = max(o_cap, _pow2(out_cap))
        o_ceil = max(o_ceil, o_cap)

    # rules of thumb (DESIGN.md §4.6): allgather materializes q stage
    # operands at once — fine on small grids, memory-hostile at scale.
    # Merge strategy (§4.4), from stage count and nnz stats:
    #   - tiny total product volume: the legacy single concat-and-sort has
    #     no per-stage fixed costs to amortize -> 'sort';
    #   - q·prod_cap beyond the scratch budget: 'incremental' (O(out_cap)
    #     accumulator, one stage buffer live at a time);
    #   - otherwise 'deferred' (per-stage compaction + merge tree) — but
    #     only where it wins: the engine's sorts track live products, so it
    #     needs real cap slack to skip (prod_cap ≥ 4·expected products) and
    #     its tree work (≈ out_cap·log2 q rank-placement slots) must stay
    #     well under the q·prod_cap sort volume it avoids.
    if variant is None and schedule is not None:
        # explicit schedule, free variant: keep the pair consistent
        variant = ("rotation" if schedule == "rotate" else
                   "allgather" if schedule == "alltoall" else "hybrid")
    auto_sched = variant is None and schedule is None
    if variant is None:
        variant = "allgather" if q * (a.cap + b.cap) <= mem_budget \
            else "rotation"
    if merge is None:
        tree_slots = o_cap * max(math.log2(max(q, 2)), 1.0)
        if q * p_cap <= SORT_MERGE_ENTRIES:
            merge = "sort"
        elif q * p_cap > mem_budget:
            merge = "incremental"
        elif p_cap >= 4 * stage_est and tree_slots <= q * p_cap / 4:
            merge = "deferred"
        else:
            merge = "sort"
    if schedule is None and auto_sched and variant == "rotation" and q >= 2:
        # per-stage schedule pick (§4.8): stage k moves A(·,k)/B(k,·); when
        # the stage volumes are skewed, eagerly batch the sparsest stages
        # (one fused exchange — the all-to-all leg) and broadcast the rest
        # per stage. The gather count is memory-bounded: each batched stage
        # keeps one extra operand pair live.
        sk = na.max(axis=0) + nb_.max(axis=1)
        cv = float(sk.std() / max(sk.mean(), 1.0))
        g = int(min(q - 1, mem_budget // max(a.cap + b.cap, 1)))
        if cv > HYBRID_STAGE_SKEW and g >= 1:
            sparsest = set(int(k) for k in np.argsort(sk)[:g])
            schedule = tuple("gather" if k in sparsest else "bcast"
                             for k in range(q))
            variant = "hybrid"
    return SpGEMMPlan(p_cap, o_cap, variant, merge, p_ceil, o_ceil,
                      stage_est, out_est, schedule=schedule, overlap=overlap,
                      compress=compress)


@_obs.timed("plan.spgemm")
def spgemm(a: DistSpMat, b: DistSpMat | None = None,
           sr: Semiring = ARITHMETIC, *, mesh,
           plan: SpGEMMPlan | None = None,
           prod_cap: int | None = None, out_cap: int | None = None,
           variant: str | None = None, merge: str | None = None,
           mask=None, schedule=None, overlap: bool = True,
           compress: str | None = None,
           safety: float = 4.0, max_attempts: int = 6, growth: int = 4):
    """Planned C = A ⊕.⊗ B (optionally C⟨M⟩ via ``mask``). Returns
    (C, plan-with-attempt-count).

    An overflowing attempt (any device's ``ok`` flag false) is retried with
    caps grown ×``growth`` toward the worst-case ceiling — never a silently
    truncated result. Caps quantize to powers of two, so steady-state
    iterative callers (HipMCL) reuse the compiled executable. Pattern masks
    shrink the planned out/stage capacities to the mask-intersected
    estimate (§4.7), with the same retry loop as the safety net.

    Robustness (robust/): a failed audit (checksum mismatch across a comm
    boundary, invariant violation) counts as a failed attempt and re-runs
    from the pristine host-side inputs; when plain retries keep failing —
    caps at the worst-case ceiling with ok still false, attempts exhausted,
    or persistent audit failures — the degradation ladder
    (``recover.next_rung``) swaps in progressively more conservative
    pipeline pieces, one loud warning each, recorded in ``plan.degraded``.
    Only when the ladder is exhausted does this raise.
    """
    b = a if b is None else b
    p = plan if plan is not None else plan_spgemm(
        a, b, safety=safety, prod_cap=prod_cap, out_cap=out_cap,
        variant=variant, merge=merge, mask=mask, schedule=schedule,
        overlap=overlap, compress=compress)
    _plan_event("plan.spgemm", p)
    cur_mask = mask
    post_mask = None       # set when the 'postfilter' rung strips the mask
    audit_fails = 0
    while True:
        try:
            c, ok = _spgemm_2d(a, b, sr, mesh=mesh, prod_cap=p.prod_cap,
                               out_cap=p.out_cap, variant=p.variant,
                               merge=p.merge, mask=cur_mask,
                               schedule=p.schedule, overlap=p.overlap,
                               compress=p.compress)
        except _audit.AuditError as err:
            audit_fails += 1
            timeout = isinstance(err, _deadline.ExchangeTimeout)
            _obs.event("plan.audit_retry", op="spgemm", site=err.site,
                       attempt=p.attempts, fails=audit_fails,
                       timeout=timeout)
            _obs.counter_add("plan.audit_retries")
            if audit_fails <= MAX_AUDIT_RETRIES:
                warnings.warn(
                    f"SpGEMM attempt {p.attempts} failed audit at "
                    f"{err.site}: {err} — retrying from pristine inputs "
                    f"({audit_fails}/{MAX_AUDIT_RETRIES})",
                    RuntimeWarning, stacklevel=3)
                if timeout:
                    # a deadline trip means a straggling peer, not a flipped
                    # bit — give the topology time to heal before hammering
                    # the same exchange (deterministic seeded backoff)
                    _deadline.backoff_sleep(err.site, audit_fails)
                p = dataclasses.replace(p, attempts=p.attempts + 1)
                continue
            rung = _recover.next_rung(p, cur_mask, kind="spgemm")
            if rung is None:
                if timeout:
                    raise _deadline.TopologyError(
                        f"SpGEMM exchange at {err.site} still over deadline "
                        f"after {p.attempts} attempts with the degradation "
                        f"ladder exhausted (degraded={p.degraded}) — the "
                        "topology, not the data, is at fault", err.site) \
                        from err
                raise
            p = _recover.apply_rung(rung, p)
            if timeout:
                # the shed schedule's exchanges have different timing: the
                # old trailing-median budget would trip spuriously
                _deadline.reset(err.site)
            p, cur_mask, post_mask = _spgemm_take_rung(
                rung, p, a, b, safety, cur_mask, post_mask)
            continue
        ok = _faults.flip_ok("plan.spgemm.ok", ok)
        _obs.counter_add("host.sync.plan.ok")
        if bool(jnp.all(ok)):
            if post_mask is not None:
                c = _recover.postfilter_2d(c, post_mask, sr, mesh=mesh)
            if p.attempts > 1 or p.degraded:
                _plan_event("plan.spgemm.done", p)
            return c, p
        if p.attempts < max_attempts and not p.at_ceiling():
            _obs.event("plan.overflow_retry", op="spgemm",
                       attempt=p.attempts, prod_cap=p.prod_cap,
                       out_cap=p.out_cap)
            _obs.counter_add("plan.overflow_retries")
            p = p.grown(growth)
            continue
        rung = _recover.next_rung(p, cur_mask, kind="spgemm")
        if rung is None:
            raise RuntimeError(
                f"SpGEMM still overflowing after {p.attempts} attempts "
                f"(prod_cap={p.prod_cap}, out_cap={p.out_cap}) — "
                f"degradation ladder exhausted (degraded={p.degraded})")
        p = _recover.apply_rung(rung, p)
        p, cur_mask, post_mask = _spgemm_take_rung(
            rung, p, a, b, safety, cur_mask, post_mask)


# Audit failures are retried from pristine inputs this many times before
# the retry loop escalates to the degradation ladder (transient wire faults
# vs. a persistently-implicated pipeline stage).
MAX_AUDIT_RETRIES = 3


def _plan_event(kind: str, p):
    """One structured obs event carrying a plan's full decision record.

    Emitted when a plan is adopted (``plan.spgemm`` / ``plan.spmspv``) and
    again at return when the retry loop changed it (``*.done``) — the
    flight-recorder view of the paper's rules of thumb in action. Free
    when obs is disabled (event() is one boolean read).
    """
    if not _obs.enabled():
        return
    s = getattr(p, "schedule", None)
    _obs.event(kind,
               variant=getattr(p, "variant", None),
               merge=getattr(p, "merge", None),
               schedule=s if (s is None or isinstance(s, str)) else "hybrid",
               overlap=getattr(p, "overlap", None),
               compress=getattr(p, "compress", None),
               prod_cap=p.prod_cap, out_cap=p.out_cap,
               attempts=p.attempts, degraded=",".join(p.degraded))


def _spgemm_take_rung(rung, p, a, b, safety, cur_mask, post_mask):
    """Post-``apply_rung`` bookkeeping the planner owns: the 'postfilter'
    rung strips the mask from the multiply (applied post-hoc on success),
    which invalidates the mask-shrunk capacities — re-plan for the unmasked
    output, keeping the grown caps as floors."""
    p = dataclasses.replace(p, attempts=p.attempts + 1)
    if rung != "postfilter":
        return p, cur_mask, post_mask
    fresh = plan_spgemm(a, b, safety=safety, variant=p.variant, merge=p.merge)
    p = dataclasses.replace(
        p,
        prod_cap=max(p.prod_cap, fresh.prod_cap),
        out_cap=max(p.out_cap, fresh.out_cap),
        prod_ceiling=max(p.prod_ceiling, fresh.prod_ceiling),
        out_ceiling=max(p.out_ceiling, fresh.out_ceiling))
    return p, None, cur_mask


def demote_stage(plan: SpGEMMPlan, stage: int, q: int) -> SpGEMMPlan:
    """Re-plan the hybrid schedule away from a persistently slow stage.

    The watchdog's straggler signal names an iteration, and the caller maps
    it to the exchange stage whose peer keeps lagging; demoting that stage
    from the per-stage broadcast to the batched ``'gather'`` leg takes its
    broadcast off the critical path (the gather stages exchange eagerly in
    one fused all-to-all up front — §4.8 hybrid schedule). The elastic
    ``CheckpointedLoop``'s ``on_straggler`` hook is the intended caller.

    Whole-sweep schedules (``rotate``/``alltoall``/None) first expand to the
    per-stage ``('bcast',) * q`` form; the result is always a length-``q``
    tuple schedule with ``variant='hybrid'``, recorded in ``plan.degraded``
    as ``demote-stage:<k>`` so degraded runs stay diagnosable.
    """
    if not 0 <= stage < q:
        raise ValueError(f"stage {stage} outside [0, q={q})")
    s = plan.schedule
    base = tuple(s) if isinstance(s, (tuple, list)) else ("bcast",) * q
    if len(base) != q:
        raise ValueError(
            f"plan schedule has {len(base)} stages, expected q={q}")
    if base[stage] == "gather":
        return plan                       # already off the broadcast path
    warnings.warn(
        f"robust: demoting exchange stage {stage} to the batched 'gather' "
        f"leg (persistent straggler; schedule was {s!r})",
        RuntimeWarning, stacklevel=2)
    _obs.event("ladder.demote_stage", stage=stage,
               schedule=s if (s is None or isinstance(s, str)) else "hybrid")
    _obs.counter_add("ladder.demotions")
    sched = base[:stage] + ("gather",) + base[stage + 1:]
    return dataclasses.replace(
        plan, schedule=sched, variant="hybrid",
        degraded=tuple(plan.degraded) + (f"demote-stage:{stage}",))


# --------------------------------------------------------------------------
# distributed SpMSpV / SpMV
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SpMSpVPlan:
    prod_cap: int
    out_cap: int
    variant: str           # local kernel: 'sort' | 'bucket' | 'spa'
    merge: str             # 'sparse' | 'dense'
    use_spmv: bool         # rule of thumb: dense SpMV beats SpMSpV here
    prod_ceiling: int
    out_ceiling: int
    density: float
    attempts: int = 1
    degraded: tuple = ()   # ladder rungs taken (robust/recover.py), in order

    def at_ceiling(self) -> bool:
        return (self.prod_cap >= self.prod_ceiling
                and self.out_cap >= self.out_ceiling)

    def grown(self, factor: int = 4) -> "SpMSpVPlan":
        if (self.prod_cap >= self.prod_ceiling
                and self.out_cap >= self.out_ceiling):
            raise RuntimeError(
                "SpMSpV overflow at worst-case capacities "
                f"(prod_cap={self.prod_cap}, out_cap={self.out_cap})")
        return dataclasses.replace(
            self,
            prod_cap=min(self.prod_cap * factor, self.prod_ceiling),
            out_cap=min(self.out_cap * factor, self.out_ceiling),
            attempts=self.attempts + 1)


def spmspv_variant_for_density(density: float) -> str:
    """Fig-3 rule of thumb (§4.5): sort ≲0.5%, bucket ≲10%, SPA above."""
    if density < 0.005:
        return "sort"
    if density < 0.10:
        return "bucket"
    return "spa"


def plan_spmspv(a: DistSpMat, frontier_nnz: int, *, safety: float = 4.0,
                prod_cap: int | None = None, out_cap: int | None = None,
                variant: str | None = None, merge: str | None = None,
                add_tag: str | None = None,
                mask_allowed: int | None = None) -> SpMSpVPlan:
    """Size y = A·x for a sparse x with ``frontier_nnz`` stored entries.

    Expected per-device products = nnz(A_tile) · frontier density (each
    frontier column activates its share of tile entries); the exact worst
    case is the full tile, which bounds retry growth. ``add_tag`` (the
    semiring's add-monoid tag, if the caller knows it) lets the dense-merge
    rule of thumb apply — psum_scatter merging needs a 'sum' monoid.

    ``mask_allowed`` (mask-admissible output rows, ``mask_allowed_count``)
    bounds y's stored entries exactly — masked products are dropped inside
    the expansion (§4.7), so out caps (NOT prod caps: expansion still
    enumerates every flop) intersect with it. BFS's complement mask makes
    this shrink as the search saturates.
    """
    nt = _host_nnz(a)
    max_tile = float(nt.max()) if nt.size else 1.0
    pc = a.grid[1]
    n = max(a.shape[1], 1)
    f = max(int(frontier_nnz), 1)
    density = f / n
    est = max(max_tile * density, 1.0)
    p_ceil = _pow2(max_tile)
    # worst case for out_cap: the sparse merge buckets entries by
    # destination piece with out_cap // pc slots each, and ALL of a
    # partial's entries (≤ min(max_tile, mb)) may target one piece — the
    # ceiling therefore carries a ×pc factor, or skewed outputs would hit
    # the ceiling with ok still false and the retry loop would give up
    out_bound = min(max_tile, float(a.mb))
    out_est = est
    if mask_allowed is not None:
        allowed = float(max(int(mask_allowed), 1))
        out_bound = min(out_bound, allowed)
        out_est = min(out_est, allowed)
    o_ceil = _pow2(out_bound * pc)
    p_cap = min(_pow2(prod_cap or safety * est), p_ceil)
    o_cap = min(_pow2(out_cap or safety * out_est * pc), o_ceil)
    if prod_cap:
        p_cap = max(p_cap, _pow2(prod_cap))
        p_ceil = max(p_ceil, p_cap)
    if out_cap:
        o_cap = max(o_cap, _pow2(out_cap))
        o_ceil = max(o_ceil, o_cap)
    use_spmv = density > 0.30    # §4.5: SpMSpV stays competitive far past
    #                              where intuition says to switch
    if merge is None:
        # the SpMV rule of thumb made executable: for dense-ish frontiers
        # the dense-accumulator local kernel + psum_scatter merge IS the
        # classic SpMV pipeline (requires a natively-reducible monoid)
        merge = "dense" if use_spmv and add_tag == "sum" else "sparse"
    return SpMSpVPlan(
        prod_cap=p_cap, out_cap=o_cap,
        variant=variant or spmspv_variant_for_density(density),
        merge=merge,
        use_spmv=use_spmv,
        prod_ceiling=p_ceil,
        out_ceiling=o_ceil,
        density=density)


@_obs.timed("plan.spmspv")
def spmspv(a: DistSpMat, x: DistSpVec, sr: Semiring, *, mesh,
           plan: SpMSpVPlan | None = None,
           prod_cap: int | None = None, out_cap: int | None = None,
           variant: str | None = None, merge: str | None = None,
           mask=None,
           safety: float = 4.0, max_attempts: int = 6, growth: int = 4):
    """Planned y = A·x (sparse x, optionally masked). Returns (DistSpVec,
    plan).

    Plans from the *current* frontier size (one host scalar), so iterative
    callers (BFS) get caps that track the frontier; power-of-two
    quantization keeps the number of distinct compilations logarithmic.
    A vector mask additionally caps the output at the admissible-row count.
    """
    if plan is None:
        allowed = None
        if mask is not None:
            from .mask import mask_allowed_count
            allowed = mask_allowed_count(mask)
        plan = plan_spmspv(
            a, int(_host_read("frontier_nnz", jnp.sum(x.nnz))),
            safety=safety,
            prod_cap=prod_cap, out_cap=out_cap, variant=variant, merge=merge,
            add_tag=sr.add.tag, mask_allowed=allowed)
    p = plan
    _plan_event("plan.spmspv", p)
    cur_mask = mask
    post_mask = None
    audit_fails = 0
    while True:
        try:
            y, ok = _spmspv_2d(a, x, sr, mesh=mesh, variant=p.variant,
                               merge=p.merge, prod_cap=p.prod_cap,
                               out_cap=p.out_cap, mask=cur_mask)
        except _audit.AuditError as err:
            audit_fails += 1
            timeout = isinstance(err, _deadline.ExchangeTimeout)
            _obs.event("plan.audit_retry", op="spmspv", site=err.site,
                       attempt=p.attempts, fails=audit_fails,
                       timeout=timeout)
            _obs.counter_add("plan.audit_retries")
            if audit_fails <= MAX_AUDIT_RETRIES:
                warnings.warn(
                    f"SpMSpV attempt {p.attempts} failed audit at "
                    f"{err.site}: {err} — retrying from pristine inputs "
                    f"({audit_fails}/{MAX_AUDIT_RETRIES})",
                    RuntimeWarning, stacklevel=3)
                if timeout:
                    _deadline.backoff_sleep(err.site, audit_fails)
                p = dataclasses.replace(p, attempts=p.attempts + 1)
                continue
            rung = _recover.next_rung(p, cur_mask, kind="spmspv")
            if rung is None:
                if timeout:
                    raise _deadline.TopologyError(
                        f"SpMSpV exchange at {err.site} still over deadline "
                        f"after {p.attempts} attempts with the degradation "
                        f"ladder exhausted (degraded={p.degraded})",
                        err.site) from err
                raise
            p = _recover.apply_rung(rung, p)
            if timeout:
                _deadline.reset(err.site)
            p, cur_mask, post_mask = _spmspv_take_rung(
                rung, p, a, x, safety, sr, cur_mask, post_mask)
            continue
        ok = _faults.flip_ok("plan.spmspv.ok", ok)
        _obs.counter_add("host.sync.plan.ok")
        if bool(jnp.all(ok)):
            if post_mask is not None:
                y = _recover.postfilter_spvec(y, post_mask)
            if p.attempts > 1 or p.degraded:
                _plan_event("plan.spmspv.done", p)
            return y, p
        if p.attempts < max_attempts and not p.at_ceiling():
            _obs.event("plan.overflow_retry", op="spmspv",
                       attempt=p.attempts, prod_cap=p.prod_cap,
                       out_cap=p.out_cap)
            _obs.counter_add("plan.overflow_retries")
            p = p.grown(growth)
            continue
        rung = _recover.next_rung(p, cur_mask, kind="spmspv")
        if rung is None:
            raise RuntimeError(
                f"SpMSpV still overflowing after {p.attempts} attempts "
                f"(prod_cap={p.prod_cap}, out_cap={p.out_cap}) — "
                f"degradation ladder exhausted (degraded={p.degraded})")
        p = _recover.apply_rung(rung, p)
        p, cur_mask, post_mask = _spmspv_take_rung(
            rung, p, a, x, safety, sr, cur_mask, post_mask)


def _spmspv_take_rung(rung, p, a, x, safety, sr, cur_mask, post_mask):
    """SpMSpV counterpart of ``_spgemm_take_rung``: dropping the mask
    invalidates the mask-capped output sizing — re-plan unmasked."""
    p = dataclasses.replace(p, attempts=p.attempts + 1)
    if rung != "postfilter":
        return p, cur_mask, post_mask
    fresh = plan_spmspv(a, int(_host_read("frontier_nnz", jnp.sum(x.nnz))),
                        safety=safety, variant=p.variant, merge=p.merge,
                        add_tag=sr.add.tag)
    p = dataclasses.replace(
        p,
        prod_cap=max(p.prod_cap, fresh.prod_cap),
        out_cap=max(p.out_cap, fresh.out_cap),
        prod_ceiling=max(p.prod_ceiling, fresh.prod_ceiling),
        out_ceiling=max(p.out_ceiling, fresh.out_ceiling))
    return p, None, cur_mask


def spmv_variant(a: DistSpMat) -> str:
    """Local SpMV flavor whose required sort order the tile already has.

    Row-partitioned SpMV wants row-major tiles, col-partitioned wants
    col-major (§4.2); matching the maintained order tag makes the kernel's
    sort a no-op.
    """
    return "col" if a.order == "col" else "row"


# --------------------------------------------------------------------------
# local (single-tile) planning — benchmarks and non-distributed callers
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LocalSpGEMMPlan:
    prod_cap: int
    out_cap: int
    algo: str              # 'esc' | 'dense'
    flops: int             # exact symbolic count
    ratio: float           # estimated compression ratio


def plan_local_spgemm(a: COO, b: COO, *, safety: float = 1.25,
                      dense_threshold: float = 4.0,
                      dense_tile_limit: int = 1 << 22,
                      mask_nnz: int | None = None) -> LocalSpGEMMPlan:
    """Exact symbolic phase for one tile pair (paper §4.1 phase 1).

    ``spgemm_flops`` is exact, so ``prod_cap`` cannot overflow; ``out_cap``
    is bounded by min(flops, dense tile) — and by ``mask_nnz`` when the
    caller multiplies under a structural mask (the masked output pattern is
    a subset of the mask, §4.7). The algo pick mirrors ``spgemm_auto``'s
    compression-ratio hybrid.
    """
    m, n = a.shape[0], b.shape[1]
    fl = int(_host_read("flops", spgemm_flops(a, b)))
    ratio = float(_host_read("ratio", compression_ratio(a, b)))
    prod_cap = _pow2(max(fl, 1) * safety)
    out_bound = min(max(fl, 1), m * n)
    if mask_nnz is not None:
        out_bound = min(out_bound, max(int(mask_nnz), 1))
    out_cap = min(_pow2(out_bound * safety), _pow2(m * n))
    algo = "dense" if (ratio >= dense_threshold and m * n <= dense_tile_limit) \
        else "esc"
    return LocalSpGEMMPlan(prod_cap, out_cap, algo, fl, ratio)


@dataclasses.dataclass(frozen=True)
class LocalSpMSpVPlan:
    prod_cap: int
    out_cap: int
    variant: str
    use_spmv: bool
    density: float


def plan_local_spmspv(a: COO, frontier_nnz: int, *,
                      safety: float = 4.0) -> LocalSpMSpVPlan:
    n = max(a.shape[1], 1)
    density = max(int(frontier_nnz), 1) / n
    nnz = int(_host_read("nnz", a.nnz))
    est = max(nnz * density, 1.0)
    prod_cap = min(_pow2(safety * est), _pow2(max(nnz, 1)))
    out_cap = min(_pow2(safety * est), _pow2(a.shape[0]))
    return LocalSpMSpVPlan(prod_cap, out_cap,
                           spmspv_variant_for_density(density),
                           density > 0.30, density)
