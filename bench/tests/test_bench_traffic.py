"""Each traffic mix's work count and reference against a direct SciPy or
NumPy computation, and a whole run of each cell on the CPU at scale 8."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from bench import cells, graph
from bench import run as bench_run
from bench.reference import graphs as ref
from bench.tests.small import small_cell

SEED = 3_000_000_019          # above 2**31: seeds are not 32-bit
ALL_CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]]
CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]
         if w["chips"] == 1]


def _traffic(name, scale=9):
    cell = small_cell(name, scale)
    g = graph.build(cell.config, SEED)
    return cell, g, cells.client(cell.traffic["client"]).Traffic(
        g, cell.config, cell.traffic, SEED)


def test_relabelled_graph_is_the_same_graph():
    cell = small_cell("spgemm.kron15", 9)
    g0, g1 = graph.build(cell.config, 1), graph.build(cell.config, 2)
    assert not np.array_equal(g0.rows, g1.rows)
    assert g0.host.nnz == g1.host.nnz
    back = g1.host[g1.label][:, g1.label]
    assert (back != g0.host[g0.label][:, g0.label]).nnz == 0
    assert (g0.host != g0.host.T).nnz == 0           # symmetric
    assert g0.host.diagonal().sum() == 0             # no self-loops


def test_relabelling_keeps_every_tile():
    """On a 2x2 grid a seed relabels the vertices inside each tile's
    block: every tile holds as many entries for every seed."""
    cell = small_cell("spgemm.kron16-2x2", 9)
    half = 2 ** 8
    tiles = []
    for seed in (1, SEED):
        g = graph.build(cell.config, seed)
        assert np.array_equal(g.label // half, np.arange(g.n) // half)
        tiles.append(np.bincount((g.rows // half) * 2 + g.cols // half,
                                 minlength=4))
    np.testing.assert_array_equal(*tiles)
    assert not np.array_equal(graph.build(cell.config, 1).label,
                              graph.build(cell.config, SEED).label)


def test_tile_capacity_rule():
    assert graph.tile_cap(882_046, 1) == 1_102_560
    assert graph.tile_cap(1_819_076, 4) == 568_464
    assert graph.tile_cap(3, 4) == 8


def test_spgemm_slabs_work_and_reference():
    _, g, t = _traffic("spgemm.kron15")
    dense = g.host.toarray()
    assert sorted(np.concatenate(t.cols).tolist()) == list(range(g.n))
    assert {len(c) for c in t.cols} == {g.n // len(t.cols)}
    # snake order keeps the slabs' work within one heaviest column's
    weight = dense.sum(axis=0) @ dense
    assert max(t.mults) - min(t.mults) <= weight.max()
    for k in t.items[:4]:
        cols = t.cols[k]
        want = dense @ dense[:, cols]
        # every value is 1, so the product's sum counts the multiplications
        assert t.work(k) == 2 * want.sum()
        got = ref.spgemm_columns(g.host, cols)
        np.testing.assert_array_equal(got.toarray(), want)


def test_bfs_roots_work_and_reference():
    _, g, t = _traffic("bfs.kron15")
    (roots,) = t.items
    deg = np.asarray(g.host.sum(axis=1)).ravel()
    assert all(deg[r] >= 1 for r in roots)
    assert len(set(roots)) == len(roots) == 4
    levels = ref.bfs_levels(g.host, roots)
    assert t.work(roots) == sum(t.work((r,)) for r in roots)
    for root, lv in zip(roots, levels):
        # plain breadth-first search over adjacency lists
        want = np.full(g.n, -1)
        want[root], frontier, d = 0, [root], 0
        while frontier:
            d += 1
            nxt = []
            for u in frontier:
                for v in g.host.indices[g.host.indptr[u]:g.host.indptr[u + 1]]:
                    if want[v] < 0:
                        want[v] = d
                        nxt.append(v)
            frontier = nxt
        np.testing.assert_array_equal(lv, want)
        reached = want >= 0
        assert t.work((root,)) == g.host[reached][:, reached].nnz / 2


def test_tc_masked_work_and_reference():
    _, g, t = _traffic("tc.urand15")
    dense = g.host.toarray()
    assert ref.triangles(g.host) == round(np.trace(dense @ dense @ dense) / 6)
    low = np.tril(dense, -1)
    assert t.counts(0, None)["mults"] == (low @ low).sum()
    assert t.work(0) == dense.sum() / 2


@pytest.mark.parametrize("name", CELLS)
def test_whole_run_on_cpu_is_correct(name):
    cell = small_cell(name)
    res = bench_run.run(cell, SEED, 0.5, False, jax.devices()[:1],
                        cache=False, say=lambda s: None)
    assert res["correct"], res
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("name", ALL_CELLS)
def test_every_seed_does_the_same_work(name):
    cell = small_cell(name, 9)
    d = cells.client(cell.traffic["client"])
    works = []
    for seed in (1, SEED):
        t = d.Traffic(graph.build(cell.config, seed), cell.config,
                      cell.traffic, seed)
        works.append(sorted(t.work(k) for k in t.items))
    assert works[0] == works[1]


def test_seed_fixes_the_inputs():
    for name in CELLS:
        cell = small_cell(name)
        d = cells.client(cell.traffic["client"])
        a = d.Traffic(graph.build(cell.config, SEED), cell.config,
                      cell.traffic, SEED)
        b = d.Traffic(graph.build(cell.config, SEED), cell.config,
                      cell.traffic, SEED)
        assert a.items == b.items

