"""The trace reduction on a small v5e trace, against numbers worked out by
hand from its events (``record_trace.py`` recorded it on one chip).

Inside ``bench.window`` (start 51,981,828 ns, 86,331,726 ns long) the
device ran eight ops, none overlapping:

    reverse            71,667,816 +   1,192
    iota               71,669,010 +      46
    sort.6             71,669,057 +  45,297   sort
    fusion (scatter)   93,444,964 + 572,431   scatter_segment
    copy-start        115,220,236 +       4
    and_select_fusion 115,220,241 +     741
    copy-done         115,220,984 +     287
    segment_reduce_pallas.1  115,221,272 + 228,125   pallas_segreduce

Busy: their sum, 848,123 ns. The four long idle gaps are the host's
20-ms sleeps: 22,864,157 (last op to window end), 21,730,610,
21,202,841 and 19,685,988 ns (window start to first op).
"""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import xplane

TRACE = str(Path(__file__).resolve().parent / "data" / "small.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return xplane.reduce(TRACE, "bench.window")


def test_window_and_busy(red):
    assert red.devices == 1
    assert red.window_s == pytest.approx(86_331_726e-9, abs=1e-12)
    assert red.busy_s == pytest.approx(848_123e-9, abs=1e-12)
    assert red.idle_share == pytest.approx(1 - 848_123 / 86_331_726)
    assert len(red.ops) == 8


def test_device_time_by_category(red):
    assert red.category_s == pytest.approx({
        "sort": 45_297e-9, "scatter_segment": 572_431e-9,
        "pallas_segreduce": 228_125e-9}, abs=1e-12)


def test_top_ops(red):
    names = [n for n, _ in red.top_ops]
    assert names[:3] == ["fusion", "segment_reduce_pallas.1", "sort.6"]
    assert red.top_ops[0][1] == pytest.approx(572_431e-9, abs=1e-12)


def test_idle_gaps_by_host_annotation(red):
    longest = [s for _, s in red.idle_gaps[:4]]
    assert longest == pytest.approx(
        [22_864_157e-9, 21_730_610e-9, 21_202_841e-9, 19_685_988e-9],
        abs=1e-12)
    assert {n for n, _ in red.idle_gaps[:4]} == {"bench.window / $time sleep"}


def test_op_metadata_names_the_kernel_and_its_shape(red):
    (op,) = [o for o in red.ops if o.category == "pallas_segreduce"]
    assert op.stats["source"].startswith("src/repro/kernels/segreduce.py")
    assert xplane.primitive(op.stats["tf_op"]) == "pallas_call"
    from bench.roofline import segreduce_shape
    assert segreduce_shape(op.name) == (65536, 1000, 4)


def test_category_rules():
    cat = xplane.category
    assert cat({"tf_op": "jit(f)/jit(g)/sort:"}) == "sort"
    assert cat({"tf_op": "jit(f)/scatter-add:"}) == "scatter_segment"
    assert cat({"tf_op": "jit(f)/pallas_call:",
                "source": "src/repro/kernels/segreduce.py:130"}) \
        == "pallas_segreduce"
    assert cat({"tf_op": "jit(f)/pallas_call:", "source": "x.py:1"}) is None
    assert cat({"tf_op": "jit(f)/gather:",
                "source": "src/repro/core/mask.py:88"}) == "mask_probe"
    assert cat({"tf_op": "jit(f)/gather:"}) is None


def test_a_window_that_is_not_there_is_an_error():
    with pytest.raises(ValueError, match="no host annotation"):
        xplane.reduce(TRACE, "bench.no_such_window")
