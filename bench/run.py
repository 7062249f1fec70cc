"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are named in
``BENCHMARK.json`` at the root of the checkout. A run has three steps:

1. Set-up (``setup_s``, from process start to the window): build the
   graph on the host from the seed, place it in tiles on the chips, and
   make the traffic's warm-up call, so that every program the window
   runs is compiled or read from JAX's persistent compilation cache.
2. Window: calls back to back from one caller for ``--seconds``; the call
   in flight when the time is up finishes. The cell's rate is all the
   work completed over all the time from the window's start to the end of
   its last call. With ``--trace 1`` the profiler records the window and
   the per-layer metrics are reported instead of the end-to-end ones. The
   program's flight recorder (``repro.obs``) blocks after every SUMMA
   execute when it is on, so it stays off in the window; metrics that read
   its counters or events (``OBS = True`` in their reader) get them from an
   obs pass after the window, which calls each of the window's calls again.
3. Check: after the window, with the device state freed, every call's
   output is compared with the plain reference; ``correct`` holds when
   every number compared is within its limit and no call failed.

Exits non-zero, printing no result, when JAX finds no TPU, a device kind
without peaks in ``bench/peaks.py``, or fewer chips than the cell needs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                               # noqa: E402
import dataclasses                                            # noqa: E402
import json                                                   # noqa: E402
import os                                                     # noqa: E402
import shutil                                                 # noqa: E402
import sys                                                    # noqa: E402
import tempfile                                               # noqa: E402
import traceback                                              # noqa: E402
import warnings                                               # noqa: E402
from pathlib import Path                                      # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # the script's own directory would shadow the standard library
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

import numpy as np                                            # noqa: E402

from bench import cells, graph, xplane                        # noqa: E402
from bench.peaks import peaks                                 # noqa: E402

COMPILE_CACHE = ROOT / ".jax_cache"
# warnings by which the program reports a fallback: a degradation-ladder
# rung or a failed audit; a call that raises one counts as failed
FAULT_WARNINGS = ("robust:", "failed audit")


class Refused(RuntimeError):
    """The run cannot be measured here (platform, chips, device kind)."""


def use_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    a fixed directory in the checkout. Every program is kept, however
    short its compile, so that a warm run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(COMPILE_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Compiles:
    """Programs handed to XLA, and how many of them the persistent cache
    held (``cache_hits``) or XLA compiled (``cache_misses``), from JAX's
    monitoring events. The program re-traces its programs on every call,
    so a warm call reads each of them from the cache again."""

    def __init__(self):
        import jax
        self.n = {"programs": 0, "program_s": 0.0, "cache_hits": 0,
                  "cache_misses": 0}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.n["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.n["cache_misses"] += 1

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n["programs"] += 1
            self.n["program_s"] += secs

    def since(self, before: dict) -> dict:
        return {k: v - before[k] for k, v in self.n.items()}

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)


@dataclasses.dataclass
class ObsPass:
    """The window's calls made again after it, with ``repro.obs`` on."""
    calls: int
    counts: dict                # the client's per-call counts, summed
    counters: dict
    events: list


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader gets (``bench/metrics/*.py``)."""
    cell: cells.Cell
    calls: int                  # completed calls in the window
    window_s: float             # host clock, window start to last call end
    counts: dict                # the client's per-call counts, summed
    programs: dict              # Compiles counts over the window
    trace: xplane.Reduced | None
    obs: ObsPass | None
    peak_bw: float              # bytes/s of the chip's HBM


def _add(counts: dict, more: dict) -> None:
    for k, v in more.items():
        counts[k] = counts.get(k, 0) + v


def _obs_pass(traffic, call, items, say):
    """Each of ``items`` called once more with ``repro.obs`` recording;
    returns the pass and how many of its calls failed."""
    from repro import obs
    counts, failed = {}, 0
    obs.enable()
    obs.reset()
    try:
        for item in items:
            out, _, fault = _call(call, item)
            if fault:
                failed += 1
                say(f"failed obs pass call ({item}): {fault}")
            else:
                _add(counts, traffic.counts(item, out))
        got = ObsPass(len(items), counts, obs.counters(), obs.events())
    finally:
        obs.disable()
    for k, v in sorted(got.counters.items()):
        if k.startswith(("comm.bytes.", "plan.", "ladder.", "audit.",
                         "deadline.")):
            say(f"obs pass counter {k} = {v!r}")
    return got, failed


def _call(call, item):
    """``call(item)`` -> (output, info, failure or None)."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        try:
            out, info = call(item)
        except Exception as err:        # a failed call; the run goes on
            traceback.print_exc()
            return None, {}, f"{type(err).__name__}: {err}"
    faults = [str(w.message) for w in caught
              if any(f in str(w.message) for f in FAULT_WARNINGS)]
    if info.get("degraded"):
        faults.append(f"plan degraded {info['degraded']}")
    return out, info, "; ".join(faults) or None


def run(cell: cells.Cell, seed: int, seconds: float, trace: bool,
        devices: list, *, cache: bool = True, wrap=None,
        say=lambda s: print(s, file=sys.stderr)) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object.

    ``wrap(call)`` replaces the timed call (the fault tests break it)."""
    if cache:
        say(f"compile cache: {use_compile_cache()}")
    compiles = Compiles()
    try:
        return _run(cell, seed, seconds, trace, devices, compiles, wrap, say)
    finally:
        compiles.close()


def _run(cell, seed, seconds, trace, devices, compiles, wrap, say) -> dict:
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core import DistSpMat, make_grid

    cfg = cell.config
    with TraceAnnotation("bench.setup"):
        g = graph.build(cfg, seed)
        pr, pc = cfg["grid"]
        mesh = make_grid(pr, pc, devices=devices)
        a = DistSpMat.from_global_coo(
            (g.n, g.n), g.rows, g.cols, np.ones(len(g.rows), np.float32),
            (pr, pc), mesh=mesh, cap=g.tile_cap)
        traffic = cells.client(cell.traffic["client"]).Traffic(
            g, cfg, cell.traffic, seed)
        traffic.place(a, mesh)
        call = traffic.call if wrap is None else wrap(traffic.call)
        for item in traffic.warmup:
            _, _, fault = _call(call, item)
            if fault:
                say(f"warm-up call {item} failed: {fault}")
    say(f"graph: {g.n} vertices, {len(g.rows)} stored entries, grid "
        f"{pr}x{pc}; set-up programs {compiles.n}")

    from repro.kernels import segreduce
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    before = dict(compiles.n)
    reduces = dict(segreduce.CALLS)
    done, failed, counts, faults = [], 0, {}, []
    work = 0.0
    items = traffic.items
    if trace:
        # host events from JAX's own C++ annotations only: the Python
        # tracer records every Python call, slows the host by a fifth in
        # the BFS cell and writes hundreds of MB for one window
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    with TraceAnnotation("bench.window"):
        i = 0
        while True:
            item = items[i % len(items)]
            with TraceAnnotation(f"bench.call:{cell.traffic['client']}"):
                out, info, fault = _call(call, item)
            i += 1
            if fault:
                failed += 1
                faults.append(f"call {i} ({item}): {fault}")
            else:
                done.append((item, out))
                work += traffic.work(item)
                _add(counts, traffic.counts(item, out))
            if time.perf_counter() - t0 >= seconds:
                break
    t1 = time.perf_counter()
    in_window = compiles.since(before)
    if trace:
        jax.profiler.stop_trace()
    setup_s = t0 - T_START
    window_s = t1 - t0
    say(f"window: {i} calls ({failed} failed) in {window_s!r} s; "
        f"programs in the window {in_window}; counts {counts}")
    for f in faults:
        say(f"failed {f}")
    say("segreduce.CALLS in the window (dispatches as programs are traced) "
        f"{ {k: v - reduces[k] for k, v in segreduce.CALLS.items()} }")

    readers = {m["name"]: cells.metric_reader(m["name"])
               for m in (cell.per_layer if trace else [])}
    obs_pass = None
    if any(getattr(r, "OBS", False) for r in readers.values()):
        obs_pass, obs_failed = _obs_pass(
            traffic, call, list(dict.fromkeys(k for k, _ in done)), say)
        i += obs_pass.calls
        failed += obs_failed

    stats = [d.memory_stats() or {} for d in devices]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    traffic.release()
    del a
    numbers = traffic.check(done)
    checks = {k: {"value": v, "limit": traffic.limits[k]}
              for k, v in numbers.items()}
    correct = (failed == 0 and bool(done)
               and all(c["value"] <= c["limit"] for c in checks.values()))

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    metrics = {}
    result = {"correct": correct, "attempted": i, "failed": failed}
    if not trace:
        values = {"setup_s": setup_s,
                  cell.traffic["rate_metric"]: work / window_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        red = xplane.reduce(xplane.find_trace(trace_dir), "bench.window")
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        rec = Run(cell, len(done), window_s, counts, in_window, red,
                  obs_pass, peaks(dev.device_kind)["hbm_bw"])
        for m in cell.per_layer:
            value = readers[m["name"]].read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red.top_ops,
                               "idle_gaps": red.idle_gaps}
        for cat, s in sorted(red.category_s.items()):
            say(f"device seconds in {cat}: {s!r}")
        shutil.rmtree(trace_dir)
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    for k, c in checks.items():
        say(f"check {k} = {c['value']!r} (limit {c['limit']!r})")
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_for(cell: cells.Cell) -> list:
    """The chips the cell runs on; raises ``Refused`` where there are none."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise Refused(f"no TPU: jax.devices()[0].platform is "
                      f"{dev.platform!r}")
    try:
        peaks(dev.device_kind)
    except KeyError as err:
        raise Refused(str(err)) from None
    if len(devices) < cell.chips:
        raise Refused(f"the cell needs {cell.chips} chips, JAX finds "
                      f"{len(devices)}")
    return devices[:cell.chips]


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = cells.resolve(cells.load_benchmark(), args.workload)
        try:
            import repro                                  # noqa: F401
        except ImportError as err:
            raise Refused(f"the program is not in this checkout: {err}")
        devices = devices_for(cell)
    except (Refused, KeyError, FileNotFoundError) as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
