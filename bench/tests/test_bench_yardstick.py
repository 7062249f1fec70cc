"""The benchmark's own copies: generator, peaks and kernel byte counts."""
from __future__ import annotations

import numpy as np

from bench.gen import rmat as bench_rmat
from bench.peaks import PEAKS, peaks
from bench.roofline import segreduce_bytes, segreduce_shape


def test_copied_generator_gives_the_programs_edges():
    from repro.io import rmat as prog_rmat
    for params in (bench_rmat.GRAPH500, (0.25, 0.25, 0.25, 0.25)):
        want = prog_rmat.rmat_coo(10, 16, seed=123, params=params,
                                  symmetrize=True, drop_self_loops=True)
        got = bench_rmat.rmat_coo(10, 16, seed=123, params=params,
                                  symmetrize=True, drop_self_loops=True)
        assert want[0] == got[0]
        for w, g in zip(want[1:], got[1:]):
            np.testing.assert_array_equal(w, g)


def test_peaks_of_v5e():
    assert peaks("TPU v5 lite") == dict(flops=197e12, hbm_bw=819e9)
    assert set(PEAKS) == {"TPU v5 lite"}


def test_segreduce_bytes_of_one_shape():
    # 65,536 int32 ids and float32 values in, 1,000 float32 sums out
    assert segreduce_bytes(65536, 1000, 4) == 65536 * 8 + 4000 == 528288


def test_segreduce_shape_from_the_trace_op_text():
    hlo = ("%segment_reduce_pallas.1 = (f32[1024]{0:T(1024)}, s32[1024]"
           "{0:T(1024)}) custom-call(s32[65536]{0:T(1024)S(1)} "
           "%and_select_fusion, f32[65536]{0:T(1024)S(1)} %copy-done), "
           "custom_call_target=\"tpu_custom_call\", "
           "operand_layout_constraints={s32[65536]{0}, f32[65536]{0}}")
    assert segreduce_shape(hlo) == (65536, 1024, 4)
    assert segreduce_bytes(*segreduce_shape(hlo)) == 65536 * 8 + 4096
