"""Host-side flight recorder: spans, counters, structured events.

The observability substrate every perf PR proves its claims against
(DESIGN.md §9). Three design rules, in priority order:

1. **Near-zero overhead when disabled.** A host span with neither the
   recorder nor a profiler session recording costs one trace-state read
   and one ``TraceAnnotation.is_enabled()`` check, and ``span()`` hands
   back a shared no-op singleton (no allocation beyond the caller's
   kwargs); ``counter_add``/``event`` read the module-level ``_enabled``
   boolean and return before touching any state. Attribute *formatting*
   never happens at record time; raw values are stored and stringified
   only at export.
2. **One clock, one set of names.** A span reached on the host opens a
   ``jax.profiler.TraceAnnotation`` of its name whenever a profiler
   session records, so it lands on the profiler's host line beside JAX's
   own events, and is kept in the in-memory buffer when the recorder is
   on. A span reached while JAX traces a program (jit/shard_map/scan)
   enters ``jax.named_scope`` of its name instead, so the program's
   device ops carry the layer in their op path. The scope does not depend
   on whether the recorder is on: turning it on changes no program.
3. **Deterministic metrics.** Counter and event *values* derive only from
   data sizes and control-flow decisions (payload bytes, retry counts,
   ladder rungs, host syncs) — two identically-seeded runs produce
   identical counter totals, which the subprocess determinism test pins.

Enablement: ``REPRO_TRACE=<path>`` (Chrome-trace dump at exit, see
``export.py``) or ``REPRO_OBS=1`` (record + ``snapshot()`` only) at
import, or :func:`enable` / the scoped :func:`capture` at runtime.

This module imports nothing from ``repro`` (robust and core import us);
jax is imported lazily, at the first span.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any

_lock = threading.Lock()
_tls = threading.local()

_enabled = False                 # THE fast-path check — one module global
_epoch_perf = 0.0                # perf_counter() at enable-time (trace t=0)
_epoch_wall = 0.0                # matching wall-clock epoch (seconds)

# finished spans: (name, tid, t0, dur, depth, attrs)   [t0 rel. epoch_perf]
_spans: list[tuple] = []
# instant events: (name, tid, t, attrs)
_events: list[tuple] = []
# counters: monotonic totals + a (name, t, total) series for counter tracks
_counters: dict[str, float] = {}
_counter_series: list[tuple] = []

_MAX_RECORDS = 1_000_000         # backstop against unbounded growth

# jax's trace context and eager trace, TraceAnnotation.is_enabled,
# TraceAnnotation and named_scope: bound by _bind_jax at the first use
_ctx = _eval_trace = _profiling = _TraceAnnotation = _named_scope = None


# --------------------------------------------------------------------------
# where a hook runs: host or jax tracing; is a profiler session recording
# --------------------------------------------------------------------------

def _bind_jax():
    """Bind the jax hooks on first use (jax is imported lazily)."""
    global _ctx, _eval_trace, _profiling, _TraceAnnotation, _named_scope
    import jax
    from jax._src import core, source_info_util
    _ctx, _eval_trace = core.trace_ctx, core.eval_trace
    # the hooks' frames are not where an op comes from
    source_info_util.register_exclusion(__file__)
    _profiling = jax.profiler.TraceAnnotation.is_enabled
    _TraceAnnotation = jax.profiler.TraceAnnotation
    _named_scope = jax.named_scope


def tracing() -> bool:
    """True when called from inside jax tracing (jit/shard_map/scan):
    the thread's current trace is not jax's eager one."""
    if _ctx is None:
        _bind_jax()
    return _ctx.trace is not _eval_trace


def enabled() -> bool:
    """The raw switch (no tracing check) — cheapest possible read."""
    return _enabled


def recording() -> bool:
    """True when hooks should record: enabled AND on the host side.

    Use this to guard any host transfer done purely for observability
    (e.g. summing ``nnz`` for payload-byte counters).
    """
    return _enabled and not tracing()


# --------------------------------------------------------------------------
# spans
# --------------------------------------------------------------------------

def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


class _NoopSpan:
    """Shared do-nothing span — what ``span()`` returns when disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "attrs", "t0", "depth", "annotation")

    def __init__(self, name: str, attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        stack = _stack()
        self.depth = len(stack)
        stack.append(self)
        self.annotation = (_TraceAnnotation(self.name) if _profiling()
                           else None)
        if self.annotation is not None:
            self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:                    # exited out of order
            stack.remove(self)
        with _lock:
            if len(_spans) < _MAX_RECORDS:
                _spans.append((self.name, threading.get_ident(),
                               self.t0 - _epoch_perf, dur, self.depth,
                               self.attrs))
        return False


def span(name: str, **attrs):
    """Context manager naming one region of the program.

    ``with obs.span("spgemm2d.execute", schedule=s): ...`` — on the host,
    a profiler annotation while a profiler session records, and a
    recorded span (thread-safe, nestable: depth comes from a thread-local
    stack) while the recorder is on; the shared no-op when neither
    records. Inside jax tracing, ``jax.named_scope(name)``: the ops traced
    in the region carry ``name`` in their op path.
    """
    if _ctx is None:
        _bind_jax()
    if _ctx.trace is not _eval_trace:
        return _named_scope(name)
    if _enabled:
        return _Span(name, attrs)
    if _profiling():
        return _TraceAnnotation(name)
    return _NOOP


def timed(name: str, **attrs):
    """Decorator form of :func:`span` for whole functions."""
    def deco(fn):
        import functools
        if _ctx is None:
            _bind_jax()

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            if not (_enabled or _profiling()) \
                    and _ctx.trace is _eval_trace:
                return fn(*a, **kw)
            with span(name, **attrs):
                return fn(*a, **kw)
        return wrapper
    return deco


def sync(x):
    """``jax.block_until_ready(x)`` when recording (else free).

    Inside an execute span this makes the span cover device execution, not
    just async dispatch — tracing mode buys honest timings with the wait;
    disabled mode pays nothing and keeps async dispatch.
    """
    if _enabled and not tracing():
        import jax
        jax.block_until_ready(x)
    return x


# --------------------------------------------------------------------------
# metrics: counters / instant events
# --------------------------------------------------------------------------

def counter_add(name: str, value: float = 1):
    """Add to a monotonic counter (also sampled for the trace track)."""
    if not _enabled:
        return
    t = time.perf_counter() - _epoch_perf
    with _lock:
        total = _counters.get(name, 0) + value
        _counters[name] = total
        if len(_counter_series) < _MAX_RECORDS:
            _counter_series.append((name, t, total))


def event(name: str, **attrs):
    """Record an instant structured event (planner decision, ladder rung)."""
    if not _enabled:
        return
    if tracing():
        return
    t = time.perf_counter() - _epoch_perf
    with _lock:
        if len(_events) < _MAX_RECORDS:
            _events.append((name, threading.get_ident(), t, attrs))


def counters() -> dict[str, float]:
    with _lock:
        return dict(_counters)


def events(name: str | None = None) -> list[dict]:
    """Recorded instant events as plain dicts (newest last)."""
    with _lock:
        evs = list(_events)
    out = [dict(name=n, t=t, **a) for n, _tid, t, a in evs
           if name is None or n == name]
    return out


# --------------------------------------------------------------------------
# lifecycle
# --------------------------------------------------------------------------

def enable():
    """Start recording (idempotent). The epoch anchors trace timestamps."""
    global _enabled, _epoch_perf, _epoch_wall
    if _enabled:
        return
    _epoch_perf = time.perf_counter()
    _epoch_wall = time.time()
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def reset():
    """Clear every recorded span/event/counter (keeps the enabled state)."""
    with _lock:
        _spans.clear()
        _events.clear()
        _counters.clear()
        _counter_series.clear()


@contextlib.contextmanager
def capture():
    """Scoped recording into fresh buffers; prior state restored on exit.

    The unit-test workhorse: ``with obs.capture(): ... obs.snapshot()``
    never leaks spans into (or inherits spans from) the surrounding run.
    Yields the ``repro.obs`` package so callers can ``rec.snapshot()``,
    ``rec.trace_events()``, ``rec.write_trace(path)`` etc.
    """
    import sys
    global _enabled
    with _lock:
        saved = (_enabled, list(_spans), list(_events), dict(_counters),
                 list(_counter_series))
        _spans.clear()
        _events.clear()
        _counters.clear()
        _counter_series.clear()
    _enabled = False
    enable()
    try:
        yield sys.modules[__package__]
    finally:
        with _lock:
            _enabled = saved[0]
            _spans[:] = saved[1]
            _events[:] = saved[2]
            _counters.clear()
            _counters.update(saved[3])
            _counter_series[:] = saved[4]


# --------------------------------------------------------------------------
# aggregation: snapshot / coverage
# --------------------------------------------------------------------------

def _percentile(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def snapshot() -> dict[str, Any]:
    """Plain-dict summary: per-site span stats + counter totals.

    ``{"spans": {site: {count, total_us, p50_us, p99_us}},
       "counters": {...}, "events": {name: count},
       "deadline": {site: {n, median_s, budget_s, trips}}}``

    This is what ``benchmarks/run.py --json`` embeds as ``trace_summary``
    in every ``BENCH_*.json``. The deadline section is pulled live from
    ``robust/deadline.stats`` (lazy import — obs stays dependency-free).
    """
    with _lock:
        spans = list(_spans)
        evs = list(_events)
        cts = dict(_counters)
    per_site: dict[str, list[float]] = {}
    for name, _tid, _t0, dur, _depth, _attrs in spans:
        per_site.setdefault(name, []).append(dur * 1e6)
    span_stats = {}
    for name, durs in sorted(per_site.items()):
        durs.sort()
        span_stats[name] = {
            "count": len(durs),
            "total_us": round(sum(durs), 1),
            "p50_us": round(_percentile(durs, 0.50), 1),
            "p99_us": round(_percentile(durs, 0.99), 1),
        }
    ev_counts: dict[str, int] = {}
    for name, _tid, _t, _attrs in evs:
        ev_counts[name] = ev_counts.get(name, 0) + 1
    out = {"spans": span_stats, "counters": cts, "events": ev_counts}
    dl = _deadline_stats()
    if dl:
        out["deadline"] = dl
    return out


def _deadline_stats() -> dict:
    try:
        from repro.robust import deadline
    except Exception:                          # pragma: no cover
        return {}
    g = deadline.active_guard()
    if g is None:
        return {}
    return {site: g.stats(site) for site in g.sites()}


def coverage(parent: str) -> float:
    """Fraction of ``parent`` span time covered by directly-nested spans.

    For every finished span named ``parent``, sums the durations of spans
    one level deeper on the same thread whose start falls inside the
    parent's window, and divides by the summed parent durations. This is
    the self-check behind the "per-stage spans account for >=90% of each
    swept SpGEMM call" acceptance gate.
    """
    with _lock:
        spans = list(_spans)
    parents = [(tid, t0, dur, depth) for name, tid, t0, dur, depth, _ in spans
               if name == parent]
    if not parents:
        return 0.0
    total = sum(p[2] for p in parents)
    covered = 0.0
    for name, tid, t0, dur, depth, _ in spans:
        if name == parent:
            continue
        for ptid, pt0, pdur, pdepth in parents:
            if tid == ptid and depth == pdepth + 1 \
                    and pt0 <= t0 and t0 + dur <= pt0 + pdur + 1e-9:
                covered += dur
                break
    return covered / max(total, 1e-12)


def _raw_records():
    """(spans, events, counter_series, epoch_wall) for the trace export."""
    with _lock:
        return (list(_spans), list(_events), list(_counter_series),
                _epoch_wall)


# --------------------------------------------------------------------------
# environment init (REPRO_TRACE=<path> | REPRO_OBS=1)
# --------------------------------------------------------------------------

def _env_init():
    path = os.environ.get("REPRO_TRACE", "").strip()
    if path:
        enable()
        import atexit

        def _dump(path=path):
            # lazy: export imports this module, so importing it here at
            # module-init time would be circular
            from . import export
            export.write_trace(path)

        atexit.register(_dump)
    elif os.environ.get("REPRO_OBS", "").strip() not in ("", "0"):
        enable()


_env_init()
