"""Flight-recorder tests (obs/): disabled-mode fast path, span nesting and
thread-safety, Chrome-trace schema, deadline.stats (the public window view),
counter determinism across seeded subprocess runs, and the chaos test — an
injected compressed-exchange fault must surface as audit + ladder events in
the trace, with no stderr scraping.
"""
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import ARITHMETIC, make_grid, DistSpMat
from repro.core.plan import spgemm as spgemm_planned
from repro.obs import recorder
from repro.robust import audit, deadline, faults


@pytest.fixture(scope="module")
def mesh():
    return make_grid(1, 1)


def make_graph(n=40, density=0.3, seed=0):
    rng = np.random.default_rng(seed)
    dense = np.where(rng.random((n, n)) < density,
                     rng.random((n, n)).astype(np.float32) + 0.5, 0.0)
    r, c = np.nonzero(dense)
    return dense, (r.astype(np.int64), c.astype(np.int64),
                   dense[r, c].astype(np.float32))


# --------------------------------------------------------------------------
# disabled mode: the near-zero-overhead contract
# --------------------------------------------------------------------------

class TestDisabled:
    def test_disabled_span_is_shared_noop(self):
        assert not obs.enabled()
        s1 = obs.span("x", a=1)
        s2 = obs.span("y")
        assert s1 is s2 is recorder._NOOP      # no allocation per call

    def test_disabled_records_nothing(self):
        obs.counter_add("c", 5)
        obs.event("e", k=1)
        with obs.span("s"):
            pass
        assert obs.counters() == {}
        assert obs.events() == []
        assert obs.snapshot()["spans"] == {}

    def test_disabled_timed_calls_through(self):
        calls = []

        @obs.timed("t")
        def fn(x):
            calls.append(x)
            return x + 1

        assert fn(1) == 2 and calls == [1]
        assert obs.snapshot()["spans"] == {}

    def test_sync_passthrough_when_disabled(self):
        x = object()
        assert obs.sync(x) is x

    def test_disabled_overhead_under_1pct(self):
        # the acceptance bound is <1% on spgemm_local; a pure-python probe
        # bounds the per-call cost far below any kernel's wall time
        def bare():
            return sum(range(50))

        @obs.timed("probe")
        def probed():
            return sum(range(50))

        n = 20000
        for f in (bare, probed):      # warm
            for _ in range(200):
                f()
        t0 = time.perf_counter()
        for _ in range(n):
            bare()
        t_bare = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(n):
            probed()
        t_probed = time.perf_counter() - t0
        # generous CI bound: the disabled wrapper is one boolean read
        assert t_probed < t_bare * 2.0, (t_bare, t_probed)


# --------------------------------------------------------------------------
# recording: nesting, thread-safety, capture scoping
# --------------------------------------------------------------------------

class TestSpans:
    def test_nesting_depths(self):
        with obs.capture() as rec:
            with obs.span("outer"):
                with obs.span("inner"):
                    time.sleep(0.001)
            snap = rec.snapshot()
            evs = rec.trace_events()
        assert set(snap["spans"]) == {"outer", "inner"}
        byname = {e["name"]: e for e in evs if e.get("cat") == "span"}
        o, i = byname["outer"], byname["inner"]
        assert o["ts"] <= i["ts"]
        assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3

    def test_span_attrs_exported(self):
        with obs.capture() as rec:
            with obs.span("s", schedule="rotate", q=2, flag=True):
                pass
            evs = rec.trace_events()
        (e,) = [x for x in evs if x.get("cat") == "span"]
        assert e["args"] == {"schedule": "rotate", "q": 2, "flag": True}

    def test_thread_safety(self):
        nthreads, per = 8, 50

        def work(k):
            for i in range(per):
                with obs.span(f"t{k}"):
                    obs.counter_add("ops")

        with obs.capture() as rec:
            ts = [threading.Thread(target=work, args=(k,))
                  for k in range(nthreads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            snap = rec.snapshot()
        assert snap["counters"]["ops"] == nthreads * per
        for k in range(nthreads):
            assert snap["spans"][f"t{k}"]["count"] == per

    def test_capture_restores_prior_state(self):
        assert not obs.enabled()
        with obs.capture():
            assert obs.enabled()
            obs.counter_add("x", 1)
        assert not obs.enabled()
        assert obs.counters() == {}

    def test_out_of_order_exit(self):
        with obs.capture() as rec:
            a = obs.span("a")
            b = obs.span("b")
            a.__enter__()
            b.__enter__()
            a.__exit__(None, None, None)
            b.__exit__(None, None, None)
            snap = rec.snapshot()
        assert set(snap["spans"]) == {"a", "b"}

    def test_coverage(self):
        with obs.capture() as rec:
            with obs.span("parent"):
                with obs.span("child"):
                    time.sleep(0.005)
            cov = rec.coverage("parent")
        assert 0.5 < cov <= 1.0


# --------------------------------------------------------------------------
# Chrome-trace schema
# --------------------------------------------------------------------------

class TestTraceSchema:
    def test_trace_file_schema(self, tmp_path):
        path = str(tmp_path / "trace.json")
        with obs.capture() as rec:
            with obs.span("s", k="v"):
                obs.counter_add("bytes", 128)
            obs.event("decision", rung="serial-schedule")
            rec.write_trace(path)
        doc = json.load(open(path))
        assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
        assert doc["displayTimeUnit"] == "ms"
        assert {"epoch_unix_s", "pid"} <= set(doc["otherData"])
        evs = doc["traceEvents"]
        phases = {e["ph"] for e in evs}
        assert {"X", "C", "i", "M"} <= phases
        for e in evs:
            assert {"name", "ph", "pid", "tid"} <= set(e)
            if e["ph"] == "X":
                assert e["ts"] >= 0 and e["dur"] >= 0
                assert e["cat"] == "span"
            if e["ph"] == "i":
                assert e["s"] == "t" and e["cat"] == "event"
            if e["ph"] == "C":
                assert "value" in e["args"]
        # instant event payload survives export as plain JSON
        (inst,) = [e for e in evs if e["ph"] == "i"]
        assert inst["args"]["rung"] == "serial-schedule"

    def test_nonjson_attrs_stringified(self):
        with obs.capture() as rec:
            with obs.span("s", obj=np.int64(3), tup=("a", "b")):
                pass
            evs = rec.trace_events()
        (e,) = [x for x in evs if x.get("cat") == "span"]
        json.dumps(e)                              # must be serializable
        assert e["args"]["tup"] == "('a', 'b')"


# --------------------------------------------------------------------------
# deadline.stats — the public window view (satellite 1)
# --------------------------------------------------------------------------

class TestDeadlineStats:
    def test_stats_empty_site(self):
        g = deadline.ExchangeGuard(startup_deadline=1.0)
        st = g.stats("never-seen")
        # warmup: no samples yet, budget falls back to the startup deadline
        assert st == {"n": 0, "median_s": None, "budget_s": 1.0, "trips": 0}

    def test_stats_tracks_window_and_budget(self):
        g = deadline.ExchangeGuard(startup_deadline=1.0)
        for _ in range(5):
            with g.watch("site.a"):
                time.sleep(0.001)
        st = g.stats("site.a")
        assert st["n"] == 5
        assert st["median_s"] == pytest.approx(0.001, rel=5.0)
        assert st["budget_s"] > 0
        assert st["trips"] == 0
        assert g.sites() == ["site.a"]

    def test_trips_counted_and_survive_reset(self):
        g = deadline.ExchangeGuard(startup_deadline=0.001)
        with pytest.raises(deadline.ExchangeTimeout):
            with g.watch("site.b"):
                time.sleep(0.01)
        assert g.stats("site.b")["trips"] == 1
        g.reset()
        assert g.stats("site.b")["trips"] == 1    # trips survive reset
        assert g.stats("site.b")["n"] == 0        # samples do not

    def test_module_level_stats(self):
        with deadline.configure(startup_deadline=1.0):
            with deadline.watch("site.c"):
                pass
            assert deadline.stats("site.c")["n"] == 1
            assert "site.c" in deadline.sites()
        with deadline.configure(off=True):
            assert deadline.stats("site.c") == \
                {"n": 0, "median_s": None, "budget_s": None, "trips": 0}
            assert deadline.sites() == []

    def test_trip_emits_obs_event(self):
        g = deadline.ExchangeGuard(startup_deadline=0.001)
        with obs.capture() as rec:
            with pytest.raises(deadline.ExchangeTimeout):
                with g.watch("site.d"):
                    time.sleep(0.01)
            evs = rec.events("deadline.trip")
            ctr = rec.counters()
        assert len(evs) == 1 and evs[0]["site"] == "site.d"
        assert evs[0]["elapsed_s"] > evs[0]["budget_s"]
        assert ctr["deadline.trips"] == 1

    def test_snapshot_includes_deadline_section(self):
        with deadline.configure(startup_deadline=1.0):
            with obs.capture() as rec:
                with deadline.watch("site.e"):
                    pass
                snap = rec.snapshot()
        assert snap["deadline"]["site.e"]["n"] == 1


# --------------------------------------------------------------------------
# engine integration: spans + counters from a real planned multiply
# --------------------------------------------------------------------------

class TestEngineIntegration:
    def test_spgemm_planned_records(self, mesh):
        _, (r, c, v) = make_graph(seed=1)
        A = DistSpMat.from_global_coo((40, 40), r, c, v, (1, 1), mesh=mesh)
        with obs.capture() as rec:
            spgemm_planned(A, A, ARITHMETIC, mesh=mesh)
            snap = rec.snapshot()
            # coverage reads live buffers — compute before capture() exits
            cov = rec.coverage("spgemm2d")
        assert "spgemm2d" in snap["spans"]
        assert "spgemm2d.execute" in snap["spans"]
        assert snap["events"].get("plan.spgemm") == 1
        comm = [k for k in snap["counters"] if k.startswith("comm.bytes.")]
        assert comm, snap["counters"]
        # per-stage spans account for >=90% of the wrapper span
        assert cov >= 0.9, cov

    def test_payload_nbytes_matches_live_entries(self, mesh):
        _, (r, c, v) = make_graph(seed=2)
        A = DistSpMat.from_global_coo((40, 40), r, c, v, (1, 1), mesh=mesh)
        nnz = int(np.sum(np.asarray(A.nnz)))
        # int32 row + int32 col + f32 val = 12 bytes per live entry
        assert audit.payload_nbytes(A) == nnz * 12

    def test_chaos_fault_lands_in_trace(self, mesh):
        """An injected compressed-exchange fault must be visible in the
        flight recorder alone: audit.failure + retry events in obs, and in
        the exported Chrome trace — no stderr scraping (satellite)."""
        _, (r, c, v) = make_graph(seed=3)
        A = DistSpMat.from_global_coo((40, 40), r, c, v, (1, 1), mesh=mesh)
        with obs.capture() as rec:
            with audit.at_level("boundary"), \
                    faults.inject("dist.compressed_exchange:corrupt_val"), \
                    pytest.warns(RuntimeWarning, match="failed audit"):
                _, plan = spgemm_planned(A, A, ARITHMETIC, mesh=mesh,
                                         compress="int8")
            assert plan.attempts == 2
            fails = rec.events("audit.failure")
            retries = rec.events("plan.audit_retry")
            ctr = rec.counters()
            evs = rec.trace_events()
        assert any(f["site"] == "dist.compressed_exchange" for f in fails)
        assert retries and retries[0]["op"] == "spgemm"
        assert ctr["audit.failures"] >= 1
        assert ctr["plan.audit_retries"] >= 1
        names = {e["name"] for e in evs if e["ph"] == "i"}
        assert {"audit.failure", "plan.audit_retry"} <= names

    def test_ladder_rung_mirrored_as_event(self, mesh):
        """Persistent corruption walks the ladder; the RuntimeWarning is
        mirrored as a ladder.rung obs event (satellite)."""
        _, (r, c, v) = make_graph(seed=4)
        A = DistSpMat.from_global_coo((40, 40), r, c, v, (1, 1), mesh=mesh)
        with obs.capture() as rec:
            with audit.at_level("boundary"), \
                    faults.inject(
                        "dist.compressed_exchange:corrupt_val:count=99"), \
                    pytest.warns(RuntimeWarning, match="degrading pipeline"):
                _, plan = spgemm_planned(A, A, ARITHMETIC, mesh=mesh,
                                         compress="int8")
            rungs = rec.events("ladder.rung")
            ctr = rec.counters()
        assert plan.degraded
        assert any(e["rung"].startswith("serial-schedule") for e in rungs)
        assert ctr["ladder.rungs"] >= 1


# --------------------------------------------------------------------------
# determinism: identical seeded runs -> identical counters (subprocess)
# --------------------------------------------------------------------------

class TestDeterminism:
    def test_counters_deterministic_across_runs(self):
        script = os.path.join(os.path.dirname(__file__), "obs_scenario.py")
        env = dict(os.environ, REPRO_DEVICES="4")
        env.pop("XLA_FLAGS", None)
        outs = []
        for _ in range(2):
            proc = subprocess.run([sys.executable, script],
                                  capture_output=True, text=True, env=env,
                                  timeout=600)
            assert proc.returncode == 0, proc.stderr[-2000:]
            outs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
        assert outs[0] == outs[1]
        assert any(k.startswith("comm.bytes.") for k in outs[0])


# --------------------------------------------------------------------------
# one clock: spans as profiler annotations and named scopes
# --------------------------------------------------------------------------

def _sorted_twice(x):
    from repro.core.coo import COO
    with obs.span("layer.demo"):
        y = jnp.sort(x) * 2
    n = x.shape[0]
    c = COO(jnp.arange(n, dtype=jnp.int32)[::-1],
            jnp.zeros(n, jnp.int32), x, jnp.int32(n), (n, 1), "none")
    return y, c.sort("row").val


def _lowered(fn, *args) -> str:
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def _host_line(path, window):
    """Events ``(start, end, name)`` of the host line that holds
    ``window`` in the profiler trace at ``path``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for line in pd.find_plane_with_name("/host:CPU").lines:
        evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
               for e in line.events]
        if any(n == window for _, _, n in evs):
            return evs
    raise AssertionError(f"no host line holds {window}")


def _profile(tmp_path, body):
    import glob
    from jax.profiler import TraceAnnotation
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.window"):
            body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    return _host_line(path, "bench.window")


class TestProfilerClock:
    def test_span_inside_jit_names_the_ops(self):
        x = jnp.arange(8, dtype=jnp.float32)
        text = _lowered(_sorted_twice, x)
        assert "jit(_sorted_twice)/layer.demo/mul" in text
        # the engine's own decorator names the ops it traces
        assert "jit(_sorted_twice)/merge.sort/" in text

    def test_lowered_program_is_the_same_with_the_recorder_on(self):
        x = jnp.arange(8, dtype=jnp.float32)
        off = _lowered(_sorted_twice, x)
        with obs.capture() as rec:
            on = _lowered(_sorted_twice, x)
            assert rec.snapshot()["spans"] == {}   # nothing on the host
        assert on == off

    def test_disabled_span_inside_tracing_is_a_named_scope(self):
        seen = []

        @jax.jit
        def f(x):
            seen.append(obs.span("layer.demo"))
            return x + 1

        f(jnp.ones(2))
        assert seen and seen[0] is not recorder._NOOP
        assert obs.span("layer.demo") is recorder._NOOP

    def test_host_span_is_an_annotation_on_the_same_host_line(self,
                                                             tmp_path):
        def body():
            with obs.span("program.demo"):
                jnp.ones(4).block_until_ready()
            with obs.span("plan.demo", k=1):     # attributes stay off the
                pass                            # annotation's name

        evs = _profile(tmp_path, body)
        (w0, w1, _), = [e for e in evs if e[2] == "bench.window"]
        for name in ("program.demo", "plan.demo"):
            (s, e, _), = [x for x in evs if x[2] == name]
            assert w0 <= s <= e <= w1, name

    def test_recorded_span_is_also_an_annotation(self, tmp_path):
        with obs.capture() as rec:
            evs = _profile(tmp_path, lambda: obs.span("fetch.demo")
                           .__enter__().__exit__(None, None, None))
            assert rec.snapshot()["spans"]["fetch.demo"]["count"] == 1
        assert any(n == "fetch.demo" for _, _, n in evs)

    def test_no_annotation_without_a_profiler_session(self):
        from jax.profiler import TraceAnnotation
        assert not TraceAnnotation.is_enabled()
        assert obs.span("program.demo") is recorder._NOOP


class TestPrograms:
    def test_program_span_named_by_the_enclosing_function(self, mesh):
        from jax.sharding import PartitionSpec as P
        from repro.core import compat

        def double(x):
            def body(t):
                return t * 2
            return compat.shard_map(body, mesh=mesh, in_specs=P("row"),
                                    out_specs=P("row"))

        prog = double(None)
        assert prog.name == "program.double"
        x = jnp.arange(4.0)
        # the jitted function's own attributes stay reachable
        assert "mul" in prog.lower(x).as_text()
        with obs.capture() as rec:
            np.testing.assert_array_equal(np.asarray(prog(x)), 2 * x)
            snap = rec.snapshot()
        assert snap["spans"]["program.double"]["count"] == 1

    def test_wrapper_frames_are_not_an_ops_source(self):
        """A program's ops keep the caller's line as their source (the
        profiler's ``source`` stat, which bench/ops.json reads)."""
        from jax._src import source_info_util
        from repro.core import compat
        obs.span("bind")
        for path in (compat.__file__, recorder.__file__):
            assert not source_info_util.is_user_filename(path), path
        assert source_info_util.is_user_filename(__file__)

    def test_program_name_of_a_module_level_function(self):
        from repro.core.compat import program_name
        assert program_name(make_graph) == "program.make_graph"


def _sync_counters(snap):
    return {k: v for k, v in snap["counters"].items()
            if k.startswith("host.sync.") or k == "fetch.bytes"}


class TestHostSyncs:
    def _planned_and_bfs(self, mesh):
        from repro.apps import bfs_levels
        _, (r, c, v) = make_graph(seed=5)
        A = DistSpMat.from_global_coo((40, 40), r, c, v, (1, 1), mesh=mesh)
        with obs.capture() as rec:
            C, _ = spgemm_planned(A, A, ARITHMETIC, mesh=mesh)
            C.to_global_coo()
            levels = bfs_levels(A, 0, mesh=mesh)
            snap = rec.snapshot()
        return A, C, levels, snap

    def test_counters_repeat_across_seeded_runs(self, mesh):
        first = _sync_counters(self._planned_and_bfs(mesh)[3])
        second = _sync_counters(self._planned_and_bfs(mesh)[3])
        assert first == second
        for key in ("host.sync.plan.nnz", "host.sync.plan.ok",
                    "host.sync.plan.frontier_nnz", "host.sync.bfs.frontier",
                    "host.sync.fetch", "fetch.bytes"):
            assert first.get(key, 0) > 0, (key, first)

    def test_fetch_bytes_from_shapes(self, mesh):
        _, (r, c, v) = make_graph(seed=5)
        A = DistSpMat.from_global_coo((40, 40), r, c, v, (1, 1), mesh=mesh)
        with obs.capture() as rec:
            A.to_global_coo()
            cnt = rec.counters()
            assert rec.snapshot()["spans"]["dist.fetch"]["count"] == 1
        # row, col and val of every padded slot, and the nnz per tile
        assert cnt["fetch.bytes"] == A.cap * (4 + 4 + 4) + 4
        assert cnt["host.sync.fetch"] == 4

    def test_one_frontier_read_a_level(self, mesh):
        _, _, levels, snap = self._planned_and_bfs(mesh)
        # one read a level, and the read that ends the search
        assert snap["counters"]["host.sync.bfs.frontier"] \
            == int(levels.max()) + 2
        assert snap["spans"]["bfs.level"]["count"] == int(levels.max()) + 1

    def test_no_read_for_the_recorder_when_it_is_off(self, mesh,
                                                     monkeypatch):
        from repro.apps import bfs as bfs_mod
        _, (r, c, v) = make_graph(seed=5)
        A = DistSpMat.from_global_coo((40, 40), r, c, v, (1, 1), mesh=mesh)
        reads = []
        real = bfs_mod.spvec_nnz
        monkeypatch.setattr(bfs_mod, "spvec_nnz",
                            lambda x: reads.append(1) or real(x))
        levels = bfs_mod.bfs_levels(A, 0, mesh=mesh)
        assert len(reads) == int(levels.max()) + 2


def test_planner_warnings_point_at_the_caller(mesh):
    """The planned call runs inside its ``plan.spgemm`` span's wrapper; its
    warnings still name the line that called it."""
    _, (r, c, v) = make_graph(seed=3)
    A = DistSpMat.from_global_coo((40, 40), r, c, v, (1, 1), mesh=mesh)
    with audit.at_level("boundary"), \
            faults.inject("dist.compressed_exchange:corrupt_val:count=99"), \
            pytest.warns(RuntimeWarning) as caught:
        spgemm_planned(A, A, ARITHMETIC, mesh=mesh, compress="int8")
    assert {w.filename for w in caught} == {__file__}
