"""Unit + property tests for local sparse primitives vs dense oracles."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # degrade: property tests importorskip at run
    from _hypothesis_stub import given, settings, st

from repro.core import semiring as S
from repro.core.coo import (COO, SENTINEL, column_range, ewise_intersect,
                            ewise_union, row_range)
from repro.core.local_spgemm import (_expand, compression_ratio, spgemm_auto,
                                     spgemm_dense, spgemm_esc, spgemm_flops)
from repro.core.spmv_local import (spmspv_bucket, spmspv_sort, spmspv_spa,
                                   spmspv_auto, spmv_col, spmv_row,
                                   spvec_from_dense, spvec_to_dense)


def rand_sparse(rng, m, n, density=0.2, cap=None, zero=0.0, ints=False):
    dense = np.zeros((m, n), np.int32 if ints else np.float32)
    mask = rng.random((m, n)) < density
    if ints:
        dense[mask] = rng.integers(1, 9, mask.sum())
    else:
        dense[mask] = rng.random(mask.sum()).astype(np.float32) + 0.5
    cap = cap or max(int(mask.sum()) + 8, 16)
    coo = COO.from_dense(jnp.asarray(dense), cap=cap, zero=0)
    return dense, coo


def dense_semiring_mm(a, b, sr):
    """numpy oracle for C = A ⊕.⊗ B with implicit-zero semantics."""
    m, k = a.shape
    k2, n = b.shape
    out = np.full((m, n), sr.add.identity, np.float64)
    an = a != 0 if sr.add.identity != 0 else None
    for i in range(m):
        for j in range(n):
            acc = sr.add.identity
            for t in range(k):
                if a[i, t] != 0 and b[t, j] != 0:
                    p = np.asarray(sr.mul(jnp.float32(a[i, t]),
                                          jnp.float32(b[t, j])))
                    acc = np.asarray(sr.add.op(jnp.float32(acc),
                                               jnp.float32(p)))
            out[i, j] = acc
    return out


class TestCOO:
    def test_roundtrip(self):
        rng = np.random.default_rng(0)
        dense, coo = rand_sparse(rng, 13, 17, 0.3)
        np.testing.assert_allclose(np.asarray(coo.to_dense()), dense)

    def test_sort_orders(self):
        rng = np.random.default_rng(1)
        dense, coo = rand_sparse(rng, 11, 9, 0.4)
        for order in ("row", "col"):
            s = coo.sort(order)
            np.testing.assert_allclose(np.asarray(s.to_dense()), dense)
            k1 = np.asarray(s.row if order == "row" else s.col)
            nnz = int(s.nnz)
            assert np.all(np.diff(k1[:nnz]) >= 0)

    def test_dedup_sum(self):
        row = jnp.array([1, 1, 2, 1], jnp.int32)
        col = jnp.array([2, 2, 0, 2], jnp.int32)
        val = jnp.array([1.0, 2.0, 5.0, 3.0])
        coo = COO.from_entries((4, 4), row, col, val, cap=8)
        d = coo.dedup(S.PLUS)
        dense = np.asarray(d.to_dense())
        assert dense[1, 2] == 6.0 and dense[2, 0] == 5.0
        assert int(d.nnz) == 2

    def test_dedup_generic_monoid(self):
        # non-tagged monoid: "concat-as-max-abs" — arbitrary associative op
        weird = S.Monoid(lambda a, b: jnp.where(jnp.abs(a) > jnp.abs(b), a, b),
                         0.0, None, "absmax")
        row = jnp.array([0, 0, 1], jnp.int32)
        col = jnp.array([0, 0, 1], jnp.int32)
        val = jnp.array([-5.0, 3.0, 2.0])
        coo = COO.from_entries((2, 2), row, col, val, cap=4)
        d = coo.dedup(weird)
        dense = np.asarray(d.to_dense())
        assert dense[0, 0] == -5.0 and dense[1, 1] == 2.0

    def test_transpose_prune_apply_reduce(self):
        rng = np.random.default_rng(2)
        dense, coo = rand_sparse(rng, 8, 8, 0.4)
        np.testing.assert_allclose(np.asarray(coo.transpose().to_dense()),
                                   dense.T)
        pruned = coo.prune(lambda v: v > 1.0)
        ref = np.where(dense > 1.0, dense, 0.0)
        np.testing.assert_allclose(np.asarray(pruned.to_dense()), ref)
        doubled = coo.apply(lambda v: v * 2)
        np.testing.assert_allclose(np.asarray(doubled.to_dense()), dense * 2)
        np.testing.assert_allclose(np.asarray(coo.reduce(1, S.PLUS)),
                                   dense.sum(1), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(coo.reduce(0, S.PLUS)),
                                   dense.sum(0), rtol=1e-6)

    def test_ewise(self):
        rng = np.random.default_rng(3)
        da, a = rand_sparse(rng, 10, 10, 0.3)
        db, b = rand_sparse(rng, 10, 10, 0.3)
        u = ewise_union(a, b, S.PLUS)
        np.testing.assert_allclose(np.asarray(u.to_dense()), da + db,
                                   rtol=1e-6)
        x = ewise_intersect(a, b, jnp.multiply)
        np.testing.assert_allclose(np.asarray(x.to_dense()), da * db,
                                   rtol=1e-6)

    def test_vector_valued_elements(self):
        # the paper's "neighborhood aggregation on vector data": val dims (3,)
        rng = np.random.default_rng(4)
        row = jnp.array([0, 1, 1], jnp.int32)
        col = jnp.array([1, 0, 0], jnp.int32)
        val = jnp.asarray(rng.random((3, 3)), jnp.float32)
        coo = COO.from_entries((2, 2), row, col, val, cap=6)
        d = coo.dedup(S.PLUS)
        out = np.asarray(d.to_dense())
        np.testing.assert_allclose(out[1, 0], np.asarray(val[1] + val[2]),
                                   rtol=1e-6)


SEMIRINGS = [S.ARITHMETIC, S.MIN_PLUS, S.MAX_MIN, S.BOOLEAN]


class TestLocalSpGEMM:
    @pytest.mark.parametrize("sr", SEMIRINGS, ids=lambda s: s.name)
    @pytest.mark.parametrize("algo", ["esc", "dense"])
    def test_vs_dense_oracle(self, sr, algo):
        rng = np.random.default_rng(7)
        m, k, n = 9, 11, 7
        da, a = rand_sparse(rng, m, k, 0.25)
        db, b = rand_sparse(rng, k, n, 0.25)
        if sr is S.BOOLEAN:
            a = a.apply(lambda v: v > 0)
            b = b.apply(lambda v: v > 0)
        zero = sr.add.identity
        ref = dense_semiring_mm(da, db, sr)
        if algo == "esc":
            c, ok = spgemm_esc(a, b, sr, prod_cap=512, out_cap=256)
        else:
            c, ok = spgemm_dense(a, b, sr, out_cap=256)
        assert bool(ok)
        got = np.asarray(c.to_dense(zero), np.float64)
        # implicit zeros: positions never touched hold `zero` in both
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    def test_flops_exact(self):
        rng = np.random.default_rng(8)
        da, a = rand_sparse(rng, 12, 12, 0.3)
        db, b = rand_sparse(rng, 12, 12, 0.3)
        expect = int(((da != 0).astype(np.int64).T @ (db != 0)).trace())
        # flops = sum_k nnz(A(:,k)) * nnz(B(k,:)) = trace(A_pat^T B_pat)?? no:
        expect = int(sum((da[:, k] != 0).sum() * (db[k, :] != 0).sum()
                         for k in range(12)))
        assert int(spgemm_flops(a, b)) == expect

    def test_auto_matches(self):
        rng = np.random.default_rng(9)
        da, a = rand_sparse(rng, 16, 16, 0.4)
        db, b = rand_sparse(rng, 16, 16, 0.4)
        c, ok = spgemm_auto(a, b, S.ARITHMETIC, prod_cap=2048, out_cap=512)
        assert bool(ok)
        np.testing.assert_allclose(np.asarray(c.to_dense()), da @ db,
                                   rtol=1e-5)

    def test_overflow_flag(self):
        rng = np.random.default_rng(10)
        da, a = rand_sparse(rng, 16, 16, 0.5)
        db, b = rand_sparse(rng, 16, 16, 0.5)
        _, ok = spgemm_esc(a, b, prod_cap=4, out_cap=4)
        assert not bool(ok)

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 10_000), density=st.floats(0.05, 0.5))
    def test_property_esc_equals_dense_path(self, seed, density):
        rng = np.random.default_rng(seed)
        da, a = rand_sparse(rng, 8, 8, density)
        db, b = rand_sparse(rng, 8, 8, density)
        c1, ok1 = spgemm_esc(a, b, prod_cap=1024, out_cap=256)
        c2, ok2 = spgemm_dense(a, b, out_cap=256)
        assert bool(ok1) and bool(ok2)
        np.testing.assert_allclose(np.asarray(c1.to_dense()),
                                   np.asarray(c2.to_dense()), rtol=1e-5)


def _expand_by_search(a, b, sr, prod_cap):
    """The expansion with each slot's owner found by a binary search over
    the owners' end offsets (``searchsorted``): the reference for the
    scatter-and-running-max owner map of ``_expand``."""
    sorted_b = b.order == "row" and a.order != "col"
    if sorted_b:
        walk, other = a, b                  # walk A, ranges of row-sorted B
        start, end = row_range(b.row, jnp.where(a.mask(), a.col, SENTINEL))
    else:
        other, walk = a.sort("col"), b      # walk B, ranges of col-sorted A
        start, end = column_range(other.col,
                                  jnp.where(b.mask(), b.row, SENTINEL))
    cnt = jnp.where(walk.mask(), end - start, 0)
    off = jnp.cumsum(cnt) - cnt
    nprod = jnp.sum(cnt)
    s = jnp.arange(prod_cap, dtype=jnp.int32)
    t = jnp.searchsorted(off + cnt, s, side="right").astype(jnp.int32)
    tc = jnp.clip(t, 0, walk.cap - 1)
    o_idx = jnp.clip(start[tc] + (s - off[tc]), 0, other.cap - 1)
    valid = s < nprod
    ai, bi = (tc, o_idx) if sorted_b else (o_idx, tc)
    sa, sb = (walk, other) if sorted_b else (other, walk)
    out_dtype = sr.out_dtype(a.dtype, b.dtype)
    rows = jnp.where(valid, sa.row[ai], SENTINEL)
    cols = jnp.where(valid, sb.col[bi], SENTINEL)
    vals = sr.mul(sa.val[ai], sb.val[bi]).astype(out_dtype)
    vals = jnp.where(valid.reshape((-1,) + (1,) * (vals.ndim - 1)), vals,
                     jnp.asarray(sr.add.identity, out_dtype))
    return rows, cols, vals, nprod, nprod <= prod_cap


def _tile(rng, m, n, nnz, cap, vdims=()):
    """A row-sorted tile of ``nnz`` distinct random entries."""
    flat = np.sort(rng.choice(m * n, size=nnz, replace=False))
    val = rng.random((nnz,) + vdims).astype(np.float32) + 0.5
    return COO.from_entries((m, n), flat // n, flat % n, val, cap=cap,
                            order="row")


def _expand_case(name, rng):
    """(A, B, prod_cap) for one named case of the owner-map equivalence."""
    if name == "random":
        return _tile(rng, 40, 30, 150, 160), _tile(rng, 30, 50, 200, 210), 4096
    if name == "zero_counts":          # most of A's columns hit empty B rows
        b = _tile(rng, 60, 40, 30, 40)
        return _tile(rng, 50, 60, 300, 320), b, 2048
    if name == "empty":
        return (COO.empty((20, 24), 32), _tile(rng, 24, 18, 40, 48), 256)
    if name == "one_entry":
        return _tile(rng, 1, 1, 1, 1), _tile(rng, 1, 7, 5, 8), 16
    if name == "overflow":             # nprod > prod_cap: ok is false
        return _tile(rng, 40, 30, 300, 320), _tile(rng, 30, 50, 500, 512), 777
    if name == "vector":
        return (_tile(rng, 30, 20, 90, 96, (3,)),
                _tile(rng, 20, 25, 80, 96, (3,)), 1024)
    raise ValueError(name)


EXPAND_CASES = ["random", "zero_counts", "empty", "one_entry", "overflow",
                "vector"]


class TestExpandOwnerMap:
    @pytest.mark.parametrize("sr", [S.ARITHMETIC, S.MIN_PLUS],
                             ids=lambda s: s.name)
    @pytest.mark.parametrize("path", ["sorted_b", "col_sorted_a"])
    @pytest.mark.parametrize("case", EXPAND_CASES)
    def test_bit_identical_to_search(self, case, path, sr):
        rng = np.random.default_rng(EXPAND_CASES.index(case))
        a, b, prod_cap = _expand_case(case, rng)
        if path == "col_sorted_a":
            a = a.sort("col")
        assert (b.order == "row" and a.order != "col") == (path == "sorted_b")
        got = _expand(a, b, sr, prod_cap)
        ref = _expand_by_search(a, b, sr, prod_cap)
        for g, r in zip(got, ref):
            g, r = np.atleast_1d(g), np.atleast_1d(r)
            assert g.dtype == r.dtype and g.shape == r.shape
            np.testing.assert_array_equal(g.view(np.uint8), r.view(np.uint8))
        nprod, ok = int(got[3]), bool(got[4])
        assert ok == (case != "overflow") and ok == (nprod <= prod_cap)
        assert (nprod == 0) == (case == "empty")


class TestSpMV:
    @pytest.mark.parametrize("sr", SEMIRINGS, ids=lambda s: s.name)
    @pytest.mark.parametrize("variant", [spmv_row, spmv_col])
    def test_vs_dense(self, sr, variant):
        rng = np.random.default_rng(11)
        da, a = rand_sparse(rng, 14, 10, 0.3)
        x = jnp.asarray(rng.random(10).astype(np.float32) + 0.5)
        if sr is S.BOOLEAN:
            a = a.apply(lambda v: v > 0)
            x = x > 0
        y = variant(a, x, sr)
        # oracle: treat implicit zeros as absent
        ref = np.full(14, sr.add.identity, np.float64)
        for i in range(14):
            acc = sr.add.identity
            for j in range(10):
                if da[i, j] != 0:
                    p = np.asarray(sr.mul(jnp.float32(da[i, j]),
                                          x[j].astype(jnp.float32)))
                    acc = np.asarray(sr.add.op(jnp.float32(acc),
                                               jnp.float32(p)))
            ref[i] = acc
        np.testing.assert_allclose(np.asarray(y, np.float64), ref,
                                   rtol=1e-5, atol=1e-6)


class TestSpMSpV:
    @pytest.mark.parametrize("variant", [spmspv_sort, spmspv_spa,
                                         spmspv_bucket])
    @pytest.mark.parametrize("f", [1, 3, 8])
    def test_vs_spmv(self, variant, f):
        rng = np.random.default_rng(12)
        da, a = rand_sparse(rng, 20, 16, 0.25)
        xd = np.zeros(16, np.float32)
        nz = rng.choice(16, f, replace=False)
        xd[nz] = rng.random(f).astype(np.float32) + 0.5
        xi, xv, xnnz = spvec_from_dense(jnp.asarray(xd), cap=16)
        (yi, yv, ynnz), ok = variant(a, xi, xv, xnnz, S.ARITHMETIC,
                                     prod_cap=512, out_cap=64)
        assert bool(ok)
        got = np.asarray(spvec_to_dense(yi, yv, 20))
        np.testing.assert_allclose(got, da @ xd, rtol=1e-5, atol=1e-6)

    def test_min_plus_frontier(self):
        # BFS-ish: relax edges from a frontier under (min, +)
        rng = np.random.default_rng(13)
        da, a = rand_sparse(rng, 12, 12, 0.3)
        xd = np.full(12, np.inf, np.float32)
        xd[3] = 0.0
        xi = jnp.array([3] + [SENTINEL] * 3, jnp.int32)
        xv = jnp.array([0.0, np.inf, np.inf, np.inf], jnp.float32)
        (yi, yv, ynnz), ok = spmspv_sort(a, xi, xv, jnp.int32(1), S.MIN_PLUS,
                                         prod_cap=64, out_cap=32)
        got = np.asarray(spvec_to_dense(yi, yv, 12, zero=np.inf))
        ref = np.where(da[:, 3] != 0, da[:, 3] + 0.0, np.inf)
        np.testing.assert_allclose(got, ref, rtol=1e-6)

    def test_auto_dispatch(self):
        rng = np.random.default_rng(14)
        da, a = rand_sparse(rng, 64, 64, 0.1)
        xd = np.zeros(64, np.float32)
        xd[rng.choice(64, 20, replace=False)] = 1.0
        xi, xv, xnnz = spvec_from_dense(jnp.asarray(xd), cap=64)
        (yi, yv, ynnz), ok = spmspv_auto(a, xi, xv, xnnz, S.ARITHMETIC,
                                         prod_cap=2048, out_cap=64)
        assert bool(ok)
        got = np.asarray(spvec_to_dense(yi, yv, 64))
        np.testing.assert_allclose(got, da @ xd, rtol=1e-5)


class TestSegmentReduce:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_generic_matches_fast(self, seed):
        rng = np.random.default_rng(seed)
        n, nseg = 50, 8
        ids = jnp.asarray(rng.integers(0, nseg, n), jnp.int32)
        vals = jnp.asarray(rng.random(n), jnp.float32)
        fast = S.segment_reduce(vals, ids, nseg, S.PLUS)
        generic = S.segment_reduce(vals, ids, nseg,
                                   S.Monoid(jnp.add, 0.0, None, "untagged"))
        np.testing.assert_allclose(np.asarray(fast), np.asarray(generic),
                                   rtol=1e-5)
