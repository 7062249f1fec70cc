"""Pallas kernel tests: shape/dtype sweeps in interpret mode vs ref.py."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # degrade: property tests importorskip at run
    from _hypothesis_stub import given, settings, st

from repro.kernels import ref, segreduce
from repro.kernels.bsr_spmm import bsr_spmm, to_blocked_ell
from repro.kernels.flash_attention import flash_attention
from repro.kernels.segreduce import segment_reduce_pallas
from repro.kernels.semiring_matmul import semiring_matmul
from repro.kernels.ssd_chunk import ssd_chunk


class TestSemiringMatmul:
    @pytest.mark.parametrize("kind", ["plus_times", "min_plus", "max_min"])
    @pytest.mark.parametrize("shape", [(128, 128, 128), (256, 128, 384),
                                       (128, 256, 128)])
    def test_vs_ref(self, kind, shape):
        M, K, N = shape
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
        got = semiring_matmul(a, b, kind=kind, interpret=True)
        want = ref.semiring_matmul(a, b, kind)
        # blockwise K accumulation reassociates the sum vs the oracle
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_or_and(self):
        rng = np.random.default_rng(1)
        a = jnp.asarray(rng.random((128, 128)) < 0.2)
        b = jnp.asarray(rng.random((128, 128)) < 0.2)
        got = semiring_matmul(a, b, kind="or_and", interpret=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(ref.semiring_matmul(
                                          a, b, "or_and")))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        rng = np.random.default_rng(2)
        a = jnp.asarray(rng.standard_normal((128, 128)), dtype)
        b = jnp.asarray(rng.standard_normal((128, 128)), dtype)
        got = semiring_matmul(a, b, kind="plus_times", interpret=True)
        want = jnp.dot(a, b)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-2)

    def test_block_shape_sweep(self):
        rng = np.random.default_rng(3)
        a = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
        want = a @ b
        for bm, bn, bk in [(128, 128, 128), (64, 128, 256), (256, 256, 64)]:
            got = semiring_matmul(a, b, kind="plus_times", bm=bm, bn=bn,
                                  bk=bk, interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-4)


class TestBsrSpmm:
    @pytest.mark.parametrize("density", [0.1, 0.4, 1.0])
    def test_vs_dense(self, density):
        rng = np.random.default_rng(4)
        M, N, n = 256, 384, 128
        bm = bk = 128
        mask = np.kron(rng.random((M // bm, N // bk)) < density,
                       np.ones((bm, bk), bool))
        dense = np.where(mask, rng.standard_normal((M, N)), 0.0) \
            .astype(np.float32)
        cols, vals = to_blocked_ell(dense, bm, bk)
        x = rng.standard_normal((N, n)).astype(np.float32)
        got = bsr_spmm(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(x),
                       interpret=True)
        np.testing.assert_allclose(np.asarray(got), dense @ x, rtol=1e-4,
                                   atol=1e-4)

    def test_ragged_rows_and_padding(self):
        rng = np.random.default_rng(5)
        bm = bk = 128
        dense = np.zeros((384, 512), np.float32)
        dense[:128, :128] = rng.standard_normal((128, 128))    # row 0: 1 blk
        dense[128:256] = rng.standard_normal((128, 512))       # row 1: all
        # row 2: empty
        cols, vals = to_blocked_ell(dense, bm, bk)
        assert cols[2, 0] == -1
        x = rng.standard_normal((512, 256)).astype(np.float32)
        got = bsr_spmm(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(x),
                       interpret=True)
        np.testing.assert_allclose(np.asarray(got), dense @ x, rtol=1e-4,
                                   atol=1e-4)

    def test_grouped_matmul_moe_pattern(self):
        """Block-diagonal A == grouped (per-expert) matmul."""
        rng = np.random.default_rng(6)
        E, bm, bk, n = 4, 128, 128, 128
        dense = np.zeros((E * bm, E * bk), np.float32)
        experts = rng.standard_normal((E, bm, bk)).astype(np.float32)
        for e in range(E):
            dense[e * bm:(e + 1) * bm, e * bk:(e + 1) * bk] = experts[e]
        cols, vals = to_blocked_ell(dense, bm, bk)
        x = rng.standard_normal((E * bk, n)).astype(np.float32)
        got = bsr_spmm(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(x),
                       interpret=True)
        want = np.concatenate(
            [experts[e] @ x[e * bk:(e + 1) * bk] for e in range(E)])
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                                   atol=1e-4)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("S,bq,bkv", [(256, 128, 128), (512, 128, 256)])
    def test_vs_ref(self, causal, S, bq, bkv):
        rng = np.random.default_rng(7)
        B, H, d = 2, 2, 64
        q = jnp.asarray(rng.standard_normal((B, S, H, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, H, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, H, d)), jnp.float32)
        got = flash_attention(q, k, v, causal=causal, bq=bq, bkv=bkv,
                              interpret=True)
        want = ref.flash_attention(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_bf16(self):
        rng = np.random.default_rng(8)
        B, S, H, d = 1, 256, 2, 128
        mk = lambda: jnp.asarray(rng.standard_normal((B, S, H, d)),
                                 jnp.bfloat16)
        q, k, v = mk(), mk(), mk()
        got = flash_attention(q, k, v, causal=True, interpret=True)
        want = ref.flash_attention(q, k, v, True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=5e-2, atol=5e-2)


class TestSSDChunk:
    @pytest.mark.parametrize("q,H,P,N", [(64, 4, 32, 32), (128, 2, 64, 64)])
    def test_vs_ref(self, q, H, P, N):
        rng = np.random.default_rng(9)
        G = 3
        xc = jnp.asarray(rng.standard_normal((G, q, H, P)), jnp.float32)
        dtc = jnp.asarray(rng.random((G, q, H)) * 0.1 + 0.01, jnp.float32)
        A = jnp.asarray(-rng.random(H) - 0.5, jnp.float32)
        Bc = jnp.asarray(rng.standard_normal((G, q, N)), jnp.float32)
        Cc = jnp.asarray(rng.standard_normal((G, q, N)), jnp.float32)
        y, st = ssd_chunk(xc, dtc, A, Bc, Cc, interpret=True)
        for g in range(G):
            yr, str_ = ref.ssd_chunk_diag(xc[g], dtc[g], A, Bc[g], Cc[g])
            np.testing.assert_allclose(np.asarray(y[g]), np.asarray(yr),
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(
                np.asarray(st[g]),
                np.asarray(str_).transpose(0, 1, 2), rtol=1e-4, atol=1e-4)

    def test_matches_model_ssd(self):
        """Kernel y_diag+states == models.layers.ssd_chunked single chunk."""
        from repro.models.layers import ssd_chunked
        rng = np.random.default_rng(10)
        B, S, H, P, N = 2, 64, 2, 16, 16
        xh = jnp.asarray(rng.standard_normal((B, S, H, P)), jnp.float32)
        dt = jnp.asarray(rng.random((B, S, H)) * 0.1 + 0.01, jnp.float32)
        A = jnp.asarray(-rng.random(H) - 0.5, jnp.float32)
        Bm = jnp.asarray(rng.standard_normal((B, S, N)), jnp.float32)
        Cm = jnp.asarray(rng.standard_normal((B, S, N)), jnp.float32)
        y_model, final = ssd_chunked(xh, dt, A, Bm, Cm, chunk=S)
        y_k, st_k = ssd_chunk(xh.reshape(B, S, H, P),
                              dt, A, Bm, Cm, interpret=True)
        np.testing.assert_allclose(np.asarray(y_model),
                                   np.asarray(y_k), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(final),
                                   np.asarray(st_k), rtol=1e-4, atol=1e-4)


class TestSegReduce:
    """Pallas segmented semiring reduce (DESIGN.md §4.4) vs XLA oracle."""

    def _oracle(self, v, ids, s, tag):
        import jax.ops as jo
        if tag == "sum":
            return jo.segment_sum(v, ids, s)
        touched = jo.segment_sum(jnp.ones_like(ids), ids, s) > 0
        if tag == "min":
            return jnp.where(touched, jo.segment_min(v, ids, s), jnp.inf)
        return jnp.where(touched, jo.segment_max(v, ids, s), -jnp.inf)

    @pytest.mark.parametrize("tag", ["sum", "min", "max"])
    @pytest.mark.parametrize("n,s", [(1000, 300), (64, 8), (512, 512),
                                     (300, 1), (5000, 3000)])
    def test_vs_xla(self, tag, n, s):
        rng = np.random.default_rng(0)
        ids = jnp.asarray(np.sort(rng.integers(0, s, n)).astype(np.int32))
        v = jnp.asarray(rng.standard_normal(n).astype(np.float32))
        got = segment_reduce_pallas(v, ids, s, tag, interpret=True)
        want = self._oracle(v, ids, s, tag)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_out_of_range_ids_dropped(self):
        ids = jnp.asarray([0, 1, 5, 7, 9], jnp.int32)
        v = jnp.ones(5, jnp.float32)
        got = segment_reduce_pallas(v, ids, 6, "sum", interpret=True)
        np.testing.assert_allclose(np.asarray(got), [1, 1, 0, 0, 0, 1])

    def test_untouched_segments_hold_identity(self):
        ids = jnp.asarray([2, 2], jnp.int32)
        v = jnp.asarray([4.0, 7.0], jnp.float32)
        got = segment_reduce_pallas(v, ids, 4, "min", interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   [np.inf, np.inf, 4.0, np.inf])

    def test_int_dtype(self):
        got = segment_reduce_pallas(jnp.asarray([3, 4, 5], jnp.int32),
                                    jnp.asarray([0, 0, 2], jnp.int32),
                                    3, "min", interpret=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      [3, 2**31 - 1, 5])

    @pytest.mark.parametrize("tag", ["min", "max"])
    def test_bool_dtype(self, tag):
        """BOOLEAN's LOR ("max") and LAND ("min") on bool streams."""
        rng = np.random.default_rng(3)
        ids = np.sort(rng.integers(0, 40, 300)).astype(np.int32)
        ids[ids == 7] = 8                      # segment 7 stays untouched
        v = rng.random(300) < 0.2
        got = segment_reduce_pallas(jnp.asarray(v), jnp.asarray(ids), 40,
                                    tag, identity=tag == "min",
                                    interpret=True)
        red = np.logical_or if tag == "max" else np.logical_and
        want = np.array([red.reduce(v[ids == t]) if (ids == t).any()
                         else tag == "min" for t in range(40)])
        assert got.dtype == jnp.bool_
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_registered_backend_serves_segment_reduce(self):
        from repro.core import semiring
        rng = np.random.default_rng(1)
        ids = jnp.asarray(np.sort(rng.integers(0, 40, 200)).astype(np.int32))
        v = jnp.asarray(rng.standard_normal(200).astype(np.float32))
        want = semiring.segment_reduce(v, ids, 40, semiring.PLUS,
                                       sorted_ids=True)
        segreduce.register(interpret=True)
        try:
            got = semiring.segment_reduce(v, ids, 40, semiring.PLUS,
                                          sorted_ids=True)
            # vector-valued entries must fall through to the pure-JAX path
            v2 = jnp.stack([v, v], axis=1)
            got2 = semiring.segment_reduce(v2, ids, 40, semiring.PLUS,
                                           sorted_ids=True)
        finally:
            segreduce.unregister()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        assert got2.shape == (40, 2)

    def test_dedup_through_pallas_backend(self):
        """COO.dedup with the kernel registered == without (end-to-end)."""
        from repro.core.coo import COO
        from repro.core.semiring import PLUS
        rng = np.random.default_rng(2)
        a = COO.from_entries((16, 16), rng.integers(0, 16, 40),
                             rng.integers(0, 16, 40),
                             rng.random(40).astype(np.float32), cap=64)
        want = a.dedup(PLUS)
        segreduce.register(interpret=True)
        try:
            got = a.dedup(PLUS)
        finally:
            segreduce.unregister()
        assert int(got.nnz) == int(want.nnz)
        np.testing.assert_allclose(np.asarray(got.to_dense()),
                                   np.asarray(want.to_dense()), rtol=1e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000),
       kind=st.sampled_from(["plus_times", "min_plus", "max_min"]))
def test_property_semiring_matmul(seed, kind):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
    got = semiring_matmul(a, b, kind=kind, interpret=True)
    want = ref.semiring_matmul(a, b, kind)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
