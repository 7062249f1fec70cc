"""Smoke run of the sparse engine's main path on a TPU.

    python chip_smoke.py               # one chip: five phases at R-MAT scale 15
    python chip_smoke.py --four-chips  # 2x2 grid: SpGEMM (scale 16) and
                                       # triangle count (scale 14)

The graph is a symmetrised Graph500 R-MAT (edge factor 16, seed 0) held in
``DistSpMat`` tiles. Each phase drives a public entry point (BFS, PageRank,
FastSV, masked-SpGEMM triangle counting, planned unmasked SpGEMM) and
checks it against a plain SciPy/NumPy reference. Per phase it prints the
first-call time (compilation included), one warm-call time (not a
benchmark), the planner's caps, ``plan.degraded``, the devices' bytes in
use (and the process's peak so far) and how many segment reductions the
compiled Pallas kernel took.

Exits non-zero, before any phase, when JAX finds no TPU; and after any
mismatch, any degradation-ladder rung, audit failure or exchange-deadline
trip, or when the Pallas segmented reduce was never compiled. The last
line of a passing run is ``{"ok": true, "device": {...}}``.

Set ``JAX_COMPILATION_CACHE_DIR`` to keep compiled programs across runs;
otherwise they are cached in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import time
import warnings

import numpy as np
import scipy.sparse as sps
from scipy.sparse import csgraph

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                     # noqa: E402

from repro import obs                                          # noqa: E402
from repro.apps import (bfs_levels, fastsv, pagerank,          # noqa: E402
                        triangle_count)
from repro.core import ARITHMETIC, DistSpMat, make_grid        # noqa: E402
from repro.core import plan as _plan                           # noqa: E402
from repro.io import rmat_coo                                  # noqa: E402
from repro.kernels import segreduce                            # noqa: E402

ONE_CHIP_SCALE = 15
BFS_ROOTS = 4
PAGERANK_ITERS = 30
PAGERANK_L1 = 1e-4
# Column slabs of B for the unmasked A·A phase: at scale 15 the product
# expands 4.4e8 entries, and one planned pass would hold a 2^29-slot
# product buffer (12 B/slot before the sort's own buffers) on a 16 GB chip.
# Slab k holds the columns j with j % SPGEMM_SLABS == k, so on a 2D grid
# every slab spreads over all grid columns.
SPGEMM_SLABS = 16


class Mismatch(AssertionError):
    """A phase's result differs from the host reference."""


@dataclasses.dataclass
class Graph:
    scale: int
    n: int
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    ref: sps.csr_matrix        # float64 host reference of A
    A: DistSpMat


def build_graph(scale: int, grid, mesh) -> Graph:
    """Symmetrised Graph500 R-MAT at ``scale`` in ``grid`` tiles on ``mesh``."""
    shape, rows, cols, vals = rmat_coo(scale, 16, seed=0, symmetrize=True,
                                       drop_self_loops=True)
    A = DistSpMat.from_global_coo(shape, rows, cols, vals, grid, mesh=mesh)
    ref = sps.csr_matrix((vals.astype(np.float64), (rows, cols)), shape=shape)
    return Graph(scale, shape[0], rows, cols, vals, ref, A)


# --------------------------------------------------------------------------
# phases: each runs one public entry point, checks it, returns a summary
# --------------------------------------------------------------------------

def phase_bfs(g: Graph, mesh) -> str:
    """``bfs_levels`` from seeded roots: levels equal SciPy's hop counts."""
    deg = np.diff(g.ref.indptr)
    roots = np.random.default_rng(0).choice(np.flatnonzero(deg > 0),
                                            BFS_ROOTS, replace=False)
    # entry (v, u) is the edge u -> v, so SciPy walks the transpose
    dist = csgraph.shortest_path(g.ref.T.tocsr(), unweighted=True,
                                 indices=roots)
    want = np.where(np.isinf(dist), -1, dist).astype(np.int32)
    # Every level runs at the worst-case SpMSpV caps (all stored entries,
    # all vertices). The planner's per-level caps make each level its own
    # program, and each takes ~30 s to compile for v5e at scale 15.
    caps = dict(prod_cap=int(np.asarray(g.A.nnz).max()), out_cap=g.n)
    for k, root in enumerate(roots):
        got = bfs_levels(g.A, source=int(root), mesh=mesh, **caps)
        if not np.array_equal(got, want[k]):
            bad = int(np.sum(got != want[k]))
            raise Mismatch(f"bfs root {root}: {bad} levels differ")
    return (f"{BFS_ROOTS} roots, levels exact at caps {caps} "
            f"(max level {int(want.max())}, reached "
            f"{int((want >= 0).sum())} of {BFS_ROOTS * g.n})")


def pagerank_reference(ref: sps.csr_matrix, iters: int, alpha=0.85,
                       tol=1e-8) -> np.ndarray:
    """The app's iteration in float64: edge u -> v for entry (v, u)."""
    n = ref.shape[0]
    deg = np.asarray(ref.sum(axis=0)).ravel()
    m = ref.multiply(np.where(deg > 0, 1.0 / np.maximum(deg, 1e-300),
                              0.0)[None, :]).tocsr()
    dangling = deg == 0
    r = np.full(n, 1.0 / n)
    for _ in range(iters):
        r_new = alpha * (m @ r) + (1 - alpha) / n \
            + alpha * r[dangling].sum() / n
        delta = np.abs(r_new - r).sum()
        r = r_new
        if delta < tol:
            break
    return r / r.sum()


def phase_pagerank(g: Graph, mesh) -> str:
    """``pagerank``: L1 distance to the float64 reference <= PAGERANK_L1."""
    got = pagerank(g.A, mesh=mesh, max_iters=PAGERANK_ITERS)
    want = pagerank_reference(g.ref, PAGERANK_ITERS)
    l1 = float(np.abs(got.astype(np.float64) - want).sum())
    if not l1 <= PAGERANK_L1:
        raise Mismatch(f"pagerank L1 {l1!r} > {PAGERANK_L1}")
    return f"{PAGERANK_ITERS} iterations, L1 to reference {l1!r}"


def phase_fastsv(g: Graph, mesh) -> str:
    """``fastsv``: the same partition as SciPy's connected components."""
    got = fastsv(g.A, mesh=mesh)
    ncc, want = csgraph.connected_components(g.ref, directed=False)
    pairs = len(set(zip(got.tolist(), want.tolist())))
    if not (pairs == len(set(got.tolist())) == ncc):
        raise Mismatch(f"fastsv: {len(set(got.tolist()))} labels, "
                       f"{ncc} reference components, {pairs} label pairs")
    return f"{ncc} components, partition equal"


def phase_tricount(g: Graph, mesh) -> str:
    """``triangle_count`` (planned masked SpGEMM): the exact count."""
    got = triangle_count(g.A, mesh=mesh)
    low = sps.tril(g.ref, -1).tocsr()
    want = int(round((low @ low).multiply(low).sum()))
    if got != want:
        raise Mismatch(f"triangle_count {got} != {want}")
    return f"{want} triangles, exact"


def phase_spgemm(g: Graph, mesh, slabs: int = SPGEMM_SLABS,
                 run: tuple[int, ...] | None = None) -> str:
    """Planned unmasked ``spgemm(A, A, ARITHMETIC)``, as ``slabs`` planned
    products A·A[:, slab]: nnz, indices and values equal SciPy's A @ A.
    ``run`` names the slabs to multiply (all by default); they are checked
    against the same columns of A @ A. Each slab's time and plan are
    printed as it ends."""
    run = tuple(range(slabs)) if run is None else run
    slab = g.cols % slabs
    # one tile capacity for every slab, so every slab runs one program
    tile = (g.rows // g.A.mb) * g.A.pc + g.cols // g.A.nb
    per_tile = np.bincount(slab * g.A.pr * g.A.pc + tile)
    cap = max(8, math.ceil(per_tile.max() * 1.25 / 8) * 8)
    parts = []
    for k in run:
        sel = slab == k
        b = DistSpMat.from_global_coo((g.n, g.n), g.rows[sel], g.cols[sel],
                                      g.vals[sel], g.A.grid, mesh=mesh,
                                      cap=cap)
        t0 = time.perf_counter()
        c, p = _plan.spgemm(g.A, b, ARITHMETIC, mesh=mesh)
        parts.append(c.to_global_coo())
        print(f"  slab {k}: {time.perf_counter() - t0!r} s, prod_cap="
              f"{p.prod_cap} out_cap={p.out_cap} merge={p.merge} "
              f"attempts={p.attempts}, {_memory()}")
    r, c, v = (np.concatenate(x) for x in zip(*parts))
    cols = np.flatnonzero(np.isin(np.arange(g.n) % slabs, run))
    got = sps.csr_matrix((v.astype(np.float64),
                          (r, np.searchsorted(cols, c))),
                         shape=(g.n, len(cols)))
    want = (g.ref @ g.ref[:, cols]).tocsr()
    want.sort_indices()
    got.sort_indices()
    if len(v) != want.nnz or got.nnz != want.nnz:
        raise Mismatch(f"spgemm nnz {len(v)} (distinct {got.nnz}) "
                       f"!= {want.nnz}")
    if not (np.array_equal(got.indptr, want.indptr)
            and np.array_equal(got.indices, want.indices)
            and np.array_equal(got.data, want.data)):
        raise Mismatch("spgemm indices or values differ from A @ A")
    return (f"nnz {want.nnz} in {len(run)} of {slabs} slabs, indices and "
            "values exact")


ONE_CHIP_PHASES = (("bfs", phase_bfs), ("pagerank", phase_pagerank),
                   ("fastsv", phase_fastsv), ("tricount", phase_tricount),
                   ("spgemm", phase_spgemm))
# The 2x2-grid run: (name, R-MAT scale, phase). Four chips cost four
# times as much chip time, so A·A runs one slab (all four tiles busy), and
# the triangle count runs at scale 14: at scale 16 the lower triangle's
# heaviest tile expands 9.6e7 products per stage, a third planner attempt
# at 2^28 product slots (12.05 GiB of temporaries, v5e:2x2 compile).
FOUR_CHIP_SLABS = (0,)
FOUR_CHIP_PHASES = (("spgemm", 16, functools.partial(phase_spgemm,
                                                     run=FOUR_CHIP_SLABS)),
                    ("tricount", 14, phase_tricount))
# A phase's warm call repeats it, except A·A: all 16 slabs again took 218 s
# of the 1200-s budget on a v5e, so its warm call is the first slab.
WARM_CALLS = {"spgemm": functools.partial(phase_spgemm, run=(0,))}


# --------------------------------------------------------------------------
# runner
# --------------------------------------------------------------------------

FAULT_COUNTERS = ("ladder.rungs", "audit.failures", "deadline.trips")
PLAN_EVENTS = ("plan.spgemm", "plan.spmspv", "plan.spgemm.done",
               "plan.spmspv.done")


def _plans() -> tuple[str, list[str]]:
    """The planner's decisions since the last obs.reset(): a summary of
    the distinct plans (with how many calls adopted each) and every
    non-empty ``plan.degraded``."""
    seen: dict[tuple, int] = {}
    degraded = []
    for e in obs.events():
        if e["name"] not in PLAN_EVENTS:
            continue
        key = (e["name"], e["prod_cap"], e["out_cap"], e["merge"],
               e["attempts"], e["degraded"])
        seen[key] = seen.get(key, 0) + 1
        if e["degraded"]:
            degraded.append(e["degraded"])
    summary = "; ".join(
        f"{k}x {n} prod_cap={p} out_cap={o} merge={m} attempts={a}"
        for (n, p, o, m, a, _), k in seen.items()) or "none"
    return summary, degraded


def _memory() -> str:
    """Bytes in use now and the process's peak so far (never reset), the
    largest over the devices."""
    stats = [d.memory_stats() or {} for d in jax.devices()]
    now = max((s["bytes_in_use"] for s in stats if "bytes_in_use" in s),
              default="not reported")
    peak = max((s["peak_bytes_in_use"] for s in stats
                if "peak_bytes_in_use" in s), default="not reported")
    return (f"bytes_in_use {now}, peak_bytes_in_use (process peak so far) "
            f"{peak}")


def run_phase(name: str, fn, g: Graph, mesh, say=print,
              warm_call: bool = True) -> None:
    """Run ``fn`` (first call) and, with ``warm_call``, its warm call
    (``WARM_CALLS`` or ``fn`` again); print what it did, and raise on a
    mismatch or on any fault counter."""
    say(f"phase {name}: start, {_memory()}")
    calls0 = dict(segreduce.CALLS)
    obs.reset()
    t0 = time.perf_counter()
    summary = fn(g, mesh)
    first = time.perf_counter() - t0
    warm = "not run"
    if warm_call:
        t0 = time.perf_counter()
        warm_summary = WARM_CALLS.get(name, fn)(g, mesh)
        warm = f"{time.perf_counter() - t0!r} s ({warm_summary})"
    plans, degraded = _plans()
    counts = obs.counters()
    compiled = segreduce.CALLS["compiled"] - calls0["compiled"]
    declined = segreduce.CALLS["declined"] - calls0["declined"]
    say(f"phase {name}: {summary}")
    say(f"phase {name}: first call {first!r} s (includes compilation); "
        f"warm call {warm} (not a benchmark)")
    say(f"phase {name}: planner over the calls run: {plans}")
    say(f"phase {name}: plan.degraded {tuple(degraded)!r}")
    say(f"phase {name}: {_memory()}")
    say(f"phase {name}: pallas segreduce compiled calls {compiled}, "
        f"declined {declined}")
    comm = {k: v for k, v in sorted(counts.items())
            if k.startswith("comm.bytes.")}
    if comm:
        say(f"phase {name}: over the calls run "
            f"{' '.join(f'{k}={v!r}' for k, v in comm.items())}")
    faults = {k: counts.get(k, 0) for k in FAULT_COUNTERS}
    say(f"phase {name}: plan.overflow_retries "
        f"{counts.get('plan.overflow_retries', 0)}")
    say(f"phase {name}: " + " ".join(f"{k}={v}" for k, v in faults.items()))
    if degraded or any(faults.values()):
        raise RuntimeError(f"phase {name} degraded or faulted: "
                           f"degraded={degraded} {faults}")


def _shard_devices(x) -> set:
    return {s.device for s in x.addressable_shards}


def run_four_chips(mesh, say=print, scales=None) -> None:
    """The 2x2-grid phases, each on its own R-MAT graph (``scales`` maps a
    phase name to another scale). Raises if a graph's four tiles do not
    sit on four distinct devices, or as ``run_phase`` does."""
    graphs = {}
    for name, scale, fn in FOUR_CHIP_PHASES:
        scale = (scales or {}).get(name, scale)
        if scale not in graphs:
            t0 = time.perf_counter()
            g = graphs[scale] = build_graph(scale, (2, 2), mesh)
            say(f"graph: R-MAT scale {scale}, {g.n} vertices, "
                f"{len(g.rows)} stored entries, grid (2, 2), built in "
                f"{time.perf_counter() - t0!r} s")
            on = _shard_devices(g.A.row)
            say(f"tiles on devices: {sorted(d.id for d in on)}")
            if len(on) != 4:
                raise RuntimeError(f"the 2x2 tiles sit on {len(on)} devices")
        # four chips cost four times as much: first calls only
        run_phase(name, fn, graphs[scale], mesh, say=say, warm_call=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the 2x2-grid phases on four chips instead")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)   # progress survives a cut

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform = "
              f"{dev.platform!r})", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")

    print("reduced: R-MAT scale 15 per 32,768-wide tile: packed int32 keys "
          "need (m+1)(n+1) < 2^31 per tile, so 46,340 is the widest tile "
          "the engine and its masked kernels take, and 2^15 the widest "
          "power of two under it")
    print(f"reduced: unmasked A·A runs as {SPGEMM_SLABS} planned products "
          f"A·A[:, slab], whose union is A·A")
    # the degradation ladder and audit retries warn; here they are failures
    warnings.filterwarnings("error", message="robust: degrading",
                            category=RuntimeWarning)
    warnings.filterwarnings("error", message=".*failed audit",
                            category=RuntimeWarning)
    if args.four_chips:
        print(f"reduced: on the 2x2 grid A·A runs slab(s) "
              f"{FOUR_CHIP_SLABS} of {SPGEMM_SLABS} at scale 16, the "
              "triangle count runs at scale 14, and no phase makes a warm "
              "call: four chips cost four times the chip time")
        with obs.capture():
            run_four_chips(make_grid(2, 2))
    else:
        print("reduced: BFS levels run at fixed worst-case SpMSpV caps, not "
              "per-level planned caps (one program instead of one per "
              "level)")
        mesh = make_grid(1, 1)
        t0 = time.perf_counter()
        g = build_graph(ONE_CHIP_SCALE, (1, 1), mesh)
        print(f"graph: R-MAT scale {g.scale}, {g.n} vertices, "
              f"{len(g.rows)} stored entries, grid (1, 1), built in "
              f"{time.perf_counter() - t0!r} s")
        with obs.capture():
            for name, fn in ONE_CHIP_PHASES:
                run_phase(name, fn, g, mesh)
    if segreduce.CALLS["compiled"] == 0:
        raise RuntimeError("the compiled Pallas segmented reduce was never "
                           "called")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
