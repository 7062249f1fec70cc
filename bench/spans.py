"""The program's own host spans in a profiler trace, and the device's idle
time under them.

``repro.obs`` opens a profiler annotation for each span the program
reaches on the host while a profiler session records: ``program.<f>``
around every call of a shard_map program (tracing, lowering, the
persistent-cache read, the dispatch), ``plan.spgemm`` / ``plan.spmspv``
around a planned call, ``dist.fetch`` around a device-to-host fetch. They
sit on the main host thread's line of the trace, beside the benchmark's
``bench.*`` annotations.

The attribution rule: each idle nanosecond of a device inside the traced
window belongs to the innermost span of a layer (``LAYERS``) open on the
main host thread at that moment; idle time inside a ``bench.call:*``
annotation under no layer span is ``unattributed``, and idle time outside
every call is ``outside``. Other spans of the program (``bfs.level``,
``spgemm2d.execute``) are transparent. Per device, then the mean over the
devices, as ``bench.readers.device_ms_per_call`` takes it.
"""
from __future__ import annotations

import functools
import glob
import heapq
import os
import sys

from bench import xplane

WINDOW = "bench.window"
CALL_PREFIX = "bench.call:"


def layer(name: str) -> str | None:
    """The layer a host span names, or None for a span that is not one."""
    if name.startswith("program."):
        return "programs"
    if name.startswith("plan."):
        return "plan"
    if name == "dist.fetch":
        return "fetch"
    if name.startswith(CALL_PREFIX):
        return "unattributed"
    return None


LAYERS = ("programs", "plan", "fetch")


def innermost(spans: list, w0: float, w1: float) -> list:
    """``[(start, end, label)]``: ``[w0, w1)`` cut where the innermost of
    the properly nested ``spans`` (``(start, end, label)``) changes; the
    label is None where none is open. The innermost open span is the one
    that started last (of two that start together, the shorter)."""
    bounds = sorted([(s, 0, i) for i, (s, _, _) in enumerate(spans)]
                    + [(e, 1, i) for i, (_, e, _) in enumerate(spans)],
                    key=lambda b: (b[0], -b[1]))
    out, heap, closed, t = [], [], set(), w0

    def top():
        while heap and heap[0][2] in closed:
            heapq.heappop(heap)
        return spans[heap[0][2]][2] if heap else None

    for x, kind, i in bounds:
        x = min(max(x, w0), w1)
        if x > t:
            out.append((t, x, top()))
            t = x
        if kind == 0:
            heapq.heappush(heap, (-spans[i][0], spans[i][1], i))
        else:
            closed.add(i)
    if w1 > t:
        out.append((t, w1, top()))
    return out


def _idle(busy: list, w0: float, w1: float) -> list:
    """The stretches of ``[w0, w1)`` that no interval of ``busy`` covers."""
    out, t = [], w0
    for a, b in xplane._union(busy):
        if a > t:
            out.append((t, min(a, w1)))
        t = max(t, b)
    if w1 > t:
        out.append((t, w1))
    return [(a, b) for a, b in out if b > a]


def idle_by_layer(spans: list, busy: dict, w0: float, w1: float) -> dict:
    """Idle nanoseconds per label of ``layer``, and ``outside``, summed
    over the devices of ``busy`` (``{device: [(start, end)]}``)."""
    segs = innermost([(s, e, layer(n)) for s, e, n in spans
                      if layer(n) is not None], w0, w1)
    out: dict = {}
    for intervals in busy.values():
        i = 0
        for a, b in _idle(intervals, w0, w1):
            while i < len(segs) and segs[i][1] <= a:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < b:
                s, e, lab = segs[j]
                key = lab or "outside"
                out[key] = out.get(key, 0.0) + min(b, e) - max(a, s)
                j += 1
    return out


def trace_path(run) -> str | None:
    """The profiler trace that ``run.trace`` was reduced from.

    ``bench/run.py`` keeps the trace on disk until the metric readers have
    run, in the ``trace_dir`` of the call that holds ``run`` as ``rec``,
    but does not hand its path to them."""
    f = sys._getframe(1)
    while f is not None:
        if f.f_locals.get("rec") is run and f.f_locals.get("trace_dir"):
            found = glob.glob(os.path.join(f.f_locals["trace_dir"], "**",
                                           "*.xplane.pb"), recursive=True)
            return found[0] if len(found) == 1 else None
        f = f.f_back
    return None


@functools.lru_cache(maxsize=2)
def host_spans(path: str, window: str = WINDOW) -> tuple:
    """``(w0, w1, spans)``: the first ``window`` annotation's bounds and
    every event ``(start, end, name)`` of the main host thread, the line
    that holds it, in ns on the trace's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for line in pd.find_plane_with_name("/host:CPU").lines:
        evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
               for e in line.events]
        for s, e, n in evs:
            if n == window:
                return s, e, tuple(evs)
    raise ValueError(f"no host annotation {window!r} in {path}")


_SHOWN: set = set()


def idle_split(run, path: str) -> dict | None:
    """Device-idle milliseconds per completed call by label (``LAYERS``,
    ``unattributed``, ``outside``), mean over the devices, from the trace
    at ``path``; None where it holds no span of a layer. The first split
    of each trace is printed to stderr."""
    w0, w1, spans = host_spans(path)
    if not any(layer(n) in LAYERS for _, _, n in spans):
        return None
    busy: dict = {}
    for o in run.trace.ops:
        busy.setdefault(o.device, []).append(
            (o.start_ns, o.start_ns + o.dur_ns))
    split = {k: v * 1e-6 / run.trace.devices / run.calls
             for k, v in idle_by_layer(spans, busy, w0, w1).items()}
    if path not in _SHOWN:
        _SHOWN.add(path)
        print(f"bench.spans: device-idle ms per call by host span {split}",
              file=sys.stderr)
    return split


def idle_ms_per_call(run, name: str) -> float | None:
    """Device-idle ms per completed call under the layer ``name``."""
    if run.trace is None or not run.calls:
        return None
    path = trace_path(run)
    split = None if path is None else idle_split(run, path)
    return None if split is None else split.get(name, 0.0)


def scope_device_ms_per_call(run, scope: str) -> float | None:
    """Device ms per completed call in ops whose op path holds the named
    scope ``scope`` (the union of their intervals on each device, mean
    over the devices)."""
    if run.trace is None or not run.calls:
        return None
    per: dict = {}
    for o in run.trace.ops:
        if scope in o.stats.get("tf_op", "").split("/"):
            per.setdefault(o.device, []).append(
                (o.start_ns, o.start_ns + o.dur_ns))
    if not per:
        return None
    ns = sum(b - a for iv in per.values() for a, b in xplane._union(iv))
    return ns * 1e-6 / run.trace.devices / run.calls


def obs_per_call(run, prefix: str) -> float | None:
    """Σ of the obs pass's counters named ``prefix`` or ``prefix.*``, per
    call of the pass; None where the program counts none."""
    if run.obs is None or not run.obs.calls:
        return None
    got = [v for k, v in run.obs.counters.items()
           if k == prefix or k.startswith(prefix + ".")]
    return sum(got) / run.obs.calls if got else None
