"""Device idle time attributed to the program's host spans
(``bench/spans.py``), on synthetic intervals worked out by hand, and the
readers of the program's own counters and named scopes."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import spans, xplane

# one host line, in ns: a call holds a program, then the planner with a
# program inside it, then a fetch; the window runs on past the call
LINE = [(0, 100, "bench.call:x"), (10, 30, "program.a"),
        (12, 28, "PjitFunction(body)"), (40, 90, "plan.spgemm"),
        (42, 88, "spmspv.execute"), (50, 60, "program.b"),
        (92, 98, "dist.fetch")]
# device busy [0, 5), [20, 45), [70, 95): idle [5, 20), [45, 70), [95, 110)
BUSY = [(0, 5), (20, 45), (70, 95)]


def test_innermost_span_wins():
    got = spans.idle_by_layer(LINE, {"d0": BUSY}, 0, 110)
    # [5, 10) call alone, [10, 20) program.a; [45, 50) plan, [50, 60)
    # program.b, [60, 70) plan; [95, 98) fetch, [98, 100) call alone,
    # [100, 110) outside every call
    assert got == {"unattributed": 7, "programs": 20, "plan": 15,
                   "fetch": 3, "outside": 10}
    assert sum(got.values()) == 15 + 25 + 15


def test_clipped_to_the_window():
    got = spans.idle_by_layer(LINE, {"d0": BUSY}, 8, 52)
    # idle [8, 20): call alone to 10, program.a to 20; idle [45, 52):
    # plan to 50, program.b to 52
    assert got == {"unattributed": 2, "programs": 12, "plan": 5}


def test_of_two_spans_that_start_together_the_shorter_is_inner():
    line = [(0, 100, "plan.spmspv"), (0, 50, "program.p")]
    got = spans.idle_by_layer(line, {"d0": []}, 0, 100)
    assert got == {"programs": 50, "plan": 50}


def test_spans_of_no_layer_are_transparent():
    assert spans.layer("bfs.level") is None
    assert spans.layer("spgemm2d.execute") is None
    assert spans.layer("PjitFunction(body)") is None
    assert spans.layer("program.spgemm_2d") == "programs"
    assert spans.layer("plan.spmspv") == "plan"
    assert spans.layer("dist.fetch") == "fetch"
    assert spans.layer("bench.call:bfs_roots") == "unattributed"


def _run(ops, devices, calls, obs=None):
    trace = xplane.Reduced(devices, 1e-7, 0.0, ops, {}, [], [])
    return SimpleNamespace(trace=trace, calls=calls, obs=obs)


def _op(device, start, dur, tf_op=""):
    return xplane.Op(device, "op", start, dur, {"tf_op": tf_op}, None)


def test_mean_over_devices_per_call(monkeypatch):
    # window [0, 100), a program from 40 on; device 0 busy to 50, device 1
    # busy all through: 50 idle ns under the program on device 0 alone
    monkeypatch.setattr(spans, "host_spans", lambda path: (
        0, 100, ((0, 100, "bench.window"), (40, 100, "program.p"))))
    run = _run([_op("d0", 0, 50), _op("d1", 0, 100)], 2, 2)
    split = spans.idle_split(run, "synthetic")
    assert split == {"programs": pytest.approx(50 / 2 / 2 * 1e-6)}


def test_a_trace_without_program_spans_reads_nothing(monkeypatch):
    monkeypatch.setattr(spans, "host_spans", lambda path: (
        0, 100, ((0, 100, "bench.window"), (0, 90, "bench.call:x"))))
    assert spans.idle_split(_run([_op("d0", 0, 5)], 1, 1), "old") is None


def test_readers_find_the_trace_of_the_run_that_holds_them(tmp_path):
    # bench/run.py holds the run as ``rec`` beside its ``trace_dir``
    deep = tmp_path / "plugins" / "profile" / "t"
    deep.mkdir(parents=True)
    (deep / "host.xplane.pb").write_bytes(b"")
    rec = _run([], 1, 1)
    trace_dir = str(tmp_path)                            # noqa: F841
    assert spans.trace_path(rec) == str(deep / "host.xplane.pb")
    assert spans.trace_path(_run([], 1, 1)) is None


def test_scope_device_ms_is_the_union_of_scoped_ops():
    ops = [_op("d0", 0, 4e6, "jit(f)/shard_map/exchange/ppermute"),
           _op("d0", 2e6, 4e6, "jit(f)/exchange/all-gather"),
           _op("d0", 10e6, 9e6, "jit(f)/exchange_not/sort"),
           _op("d1", 0, 2e6, "jit(g)/program.x/exchange/psum")]
    got = spans.scope_device_ms_per_call(_run(ops, 2, 2), "exchange")
    assert got == pytest.approx((6 + 2) / 2 / 2)
    assert spans.scope_device_ms_per_call(
        _run(ops[2:3], 1, 1), "exchange") is None


def test_counters_per_call_of_the_obs_pass():
    obs = SimpleNamespace(calls=4, counters={
        "host.sync.plan.ok": 8, "host.sync.fetch": 16, "host.syncs": 99,
        "fetch.bytes": 400, "comm.bytes.x": 1})
    run = _run([], 1, 1, obs)
    assert spans.obs_per_call(run, "host.sync") == 6
    assert spans.obs_per_call(run, "fetch.bytes") == 100
    old = SimpleNamespace(calls=4, counters={"comm.bytes.x": 1})
    assert spans.obs_per_call(_run([], 1, 1, old), "host.sync") is None
    assert spans.obs_per_call(_run([], 1, 1, None), "host.sync") is None


# ---------------------------------------------------------------------------
# a small v5e trace (record_spans_trace.py recorded it on one chip)
# ---------------------------------------------------------------------------
#
# The main host line, in ns: bench.window 43,503,521 + 219,098,019;
# bench.call:spans 63,581,382 + 178,442,617; plan.spgemm 84,312,233 +
# 112,901,424; program.demo 105,179,614 + 70,320,492; dist.fetch
# 197,224,017 + 24,524,141. The device ran three ops, all inside
# program.demo: rev.1 174,968,822 + 1,376 and sort.6 174,970,245 + 60,750
# (both under the named scope ``exchange``), iota 174,970,198 + 46.
#
# Busy: 1,376 + 46 + 60,750 = 62,172. Idle by innermost span: outside the
# call 20,077,861 + 20,577,541 = 40,655,402; the call alone 20,730,851 +
# 10,360 + 20,275,841 = 41,017,052; plan.spgemm 20,867,381 + 21,713,551 =
# 42,580,932; program.demo 70,320,492 - 62,172 = 70,258,320; dist.fetch
# 24,524,141. They add up to the window less busy, 219,035,847.

TRACE = str(Path(__file__).resolve().parent / "data" / "spans.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return SimpleNamespace(trace=xplane.reduce(TRACE, "bench.window"),
                           calls=1, obs=None)


def test_program_spans_sit_on_the_benchmarks_host_line():
    w0, w1, line = spans.host_spans(TRACE)
    assert (w0, w1 - w0) == (43_503_521, 219_098_019)
    names = [n for _, _, n in line]
    for n in ("bench.window", "bench.call:spans", "plan.spgemm",
              "program.demo", "dist.fetch"):
        assert n in names, n


def test_idle_split_of_the_recorded_trace(recorded):
    assert recorded.trace.busy_s == pytest.approx(62_172e-9, abs=1e-12)
    split = spans.idle_split(recorded, TRACE)
    assert split == pytest.approx({
        "outside": 40.655402, "unattributed": 41.017052,
        "plan": 42.580932, "programs": 70.258320, "fetch": 24.524141},
        abs=1e-9)
    assert sum(split.values()) == pytest.approx(
        219_098_019e-6 - 62_172e-6, abs=1e-9)


def test_named_scope_in_the_recorded_trace(recorded):
    # rev.1 and sort.6, not the iota, which has no op path
    assert spans.scope_device_ms_per_call(recorded, "exchange") \
        == pytest.approx((1_376 + 60_750) * 1e-6, abs=1e-12)
    (sort,) = [o for o in recorded.trace.ops if o.name.startswith("%sort")]
    assert sort.stats["tf_op"] == "jit(body)/exchange/jit(sort)/sort:"
    assert xplane.primitive(sort.stats["tf_op"]) == "sort"
    assert recorded.trace.category_s == pytest.approx(
        {"sort": 60_750e-9}, abs=1e-12)
