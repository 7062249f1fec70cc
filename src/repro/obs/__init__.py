"""repro.obs — the program's one tracing system (DESIGN.md §9).

Spans + counters/events + Chrome-trace export. Host spans become
``jax.profiler`` annotations while a profiler session records, and spans
reached inside jax tracing name the traced ops (``jax.named_scope``).
Recording into the in-memory buffer is off by default;
``REPRO_TRACE=<path>`` enables recording and dumps a Perfetto-loadable
trace at exit, ``REPRO_OBS=1`` enables recording without a dump (the
``snapshot()``-only mode the benchmark subprocesses use).
"""
from .export import trace_events, write_trace
from .recorder import (capture, counter_add, counters, coverage, disable,
                       enable, enabled, event, events, recording,
                       reset, snapshot, span, sync, timed, tracing)

__all__ = [
    "capture", "counter_add", "counters", "coverage", "disable", "enable",
    "enabled", "event", "events", "recording", "reset",
    "snapshot", "span", "sync", "timed", "tracing", "trace_events",
    "write_trace",
]
