"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Events and their times come from ``jax.profiler.ProfileData``. What an
event is comes from its metadata, which ``ProfileData`` does not expose:
each device op's ``tf_op`` (the JAX op path, ending in the primitive),
``hlo_category`` and ``source`` (the program file and line that made it).
``_op_metadata`` reads those few fields from the file's protobuf wire
format. Which ops count as sort, scatter/segment, the Pallas kernel or the work
of one program module is data: ``bench/ops.json``.

Busy time is the union of the intervals of the device's ``XLA Ops`` line,
clipped to the traced window. An idle gap is a stretch of the window
between them; it is named by the benchmark's own annotation open on the
host at its midpoint and the innermost host event open there.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from pathlib import Path

OPS = json.loads((Path(__file__).resolve().parent / "ops.json").read_text())
BENCH_PREFIX = "bench."       # the benchmark's own host annotations


# --------------------------------------------------------------------------
# the metadata reader (protobuf wire format of tsl's xplane.proto)
# --------------------------------------------------------------------------

def _varint(b: bytes, i: int) -> tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes):
    """(field number, value) of one message: ints for varints, bytes for
    length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 1:
            v, i = None, i + 8
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield f, v


# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata
# = 5 (maps: key 1, value 2); XEventMetadata.name = 2, .stats = 5;
# XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5.
_WANTED = ("tf_op", "hlo_category", "source")


def _op_metadata(raw: bytes) -> dict[str, dict[str, dict[str, str]]]:
    """{plane name: {event name: {stat: text}}} for the stats in _WANTED."""
    out = {}
    for f, plane in _fields(raw):
        if f != 1:
            continue
        name, events, stat_names = None, [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = v.decode()
            elif g == 4:
                events.append(dict(_fields(v)).get(2, b""))
            elif g == 5:
                md = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[md.get(1)] = md.get(2, b"").decode()
        if not (name or "").startswith("/device:"):
            continue
        table = out.setdefault(name, {})
        for ev in events:
            ename, stats = None, {}
            for g, v in _fields(ev):
                if g == 2:
                    ename = v.decode()
                elif g == 5:
                    st = dict(_fields(v))
                    sname = stat_names.get(st.get(1))
                    if sname in _WANTED and isinstance(st.get(5), bytes):
                        stats[sname] = st[5].decode()
            if ename is not None:
                table.setdefault(ename, stats)
    return out


# --------------------------------------------------------------------------
# categories
# --------------------------------------------------------------------------

def primitive(tf_op: str) -> str:
    """The last component of a JAX op path: ``jit(f)/jit(g)/sort:`` -> sort."""
    return tf_op.rstrip(":").rsplit("/", 1)[-1]


def category(stats: dict) -> str | None:
    """The first ``ops.json`` category whose rules a device op with these
    stats meets: its primitive matches one of ``primitive`` in full, and
    its source file holds one of ``source``; a rule left out holds."""
    prim = primitive(stats.get("tf_op", ""))
    src = stats.get("source", "")
    for cat, rule in OPS["categories"].items():
        if "primitive" in rule and not any(
                re.fullmatch(p, prim) for p in rule["primitive"]):
            continue
        if "source" in rule and not any(s in src for s in rule["source"]):
            continue
        return cat
    return None


# --------------------------------------------------------------------------
# the reduction
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Op:
    device: str
    name: str               # the HLO instruction text
    start_ns: float
    dur_ns: float
    stats: dict
    category: str | None


@dataclasses.dataclass
class Reduced:
    devices: int
    window_s: float
    busy_s: float                  # union of op intervals, mean over devices
    ops: list                      # every device op inside the window
    category_s: dict               # category -> busy seconds, all devices
    top_ops: list                  # [name, seconds]: the 10 largest
    idle_gaps: list                # [label, seconds]: the 10 longest

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_trace(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} .xplane.pb under {log_dir}")
    return files[0]


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _short(name: str) -> str:
    """An HLO instruction's name without its shapes and operands."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def reduce(path: str, window: str) -> Reduced:
    """Reduce the trace at ``path`` over the host annotation ``window``
    (its first occurrence on the host plane marks the traced window)."""
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    meta = _op_metadata(raw)
    pd = ProfileData.from_serialized_xspace(raw)

    host = pd.find_plane_with_name("/host:CPU")
    host_events = []               # (start, end, name, depth-order) per line
    w0 = w1 = None
    main_line = None
    for line in host.lines:
        evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
               for e in line.events]
        for s, e, n in evs:
            if n == window and w0 is None:
                w0, w1, main_line = s, e, line.name
        host_events.append((line.name, evs))
    if w0 is None:
        raise ValueError(f"no host annotation {window!r} in {path}")
    main = sorted(next(evs for name, evs in host_events
                       if name == main_line))

    ops, busy, devices = [], 0.0, 0
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        table = meta.get(plane.name, {})
        dev_ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                s, t = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
                if t <= s:
                    continue
                st = table.get(e.name, {})
                dev_ops.append(Op(plane.name, e.name, s, t - s, st,
                                  category(st)))
        if not dev_ops:
            continue
        devices += 1
        ops += dev_ops
        busy += sum(b - a for a, b in _union(
            [(o.start_ns, o.start_ns + o.dur_ns) for o in dev_ops]))
    if devices == 0:
        raise ValueError(f"no device op inside {window!r} in {path}")

    # a category's time is the union of its ops' intervals on each device,
    # so that a while loop and the ops inside it count once
    spans: dict[tuple, list] = {}
    by_name: dict[str, float] = {}
    for o in ops:
        if o.category:
            spans.setdefault((o.category, o.device), []).append(
                (o.start_ns, o.start_ns + o.dur_ns))
        key = _short(o.name)
        by_name[key] = by_name.get(key, 0.0) + o.dur_ns * 1e-9
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    cat_s: dict[str, float] = {}
    for (cat, _), iv in spans.items():
        cat_s[cat] = cat_s.get(cat, 0.0) + sum(
            b - a for a, b in _union(iv)) * 1e-9

    # idle gaps of the first device, named by what the host was doing
    first = min(o.device for o in ops)
    spans = _union([(o.start_ns, o.start_ns + o.dur_ns)
                    for o in ops if o.device == first])
    edges = [w0] + [x for ab in spans for x in ab] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        named.append([_host_label(main, (a + b) / 2), (b - a) * 1e-9])
    return Reduced(devices, (w1 - w0) * 1e-9, busy * 1e-9 / devices, ops,
                   cat_s, [[n, s] for n, s in top], named)


def _host_label(main: list, t: float) -> str:
    """The innermost benchmark annotation and innermost host event open at
    ``t`` on the main host thread."""
    open_ = [(s, e, n) for s, e, n in main if s <= t < e]
    ours = [n for s, e, n in open_ if n.startswith(BENCH_PREFIX)]
    inner = min(open_, key=lambda x: x[1] - x[0])[2] if open_ else "none"
    label = ours[-1] if ours else "outside the benchmark's annotations"
    return label if inner == label else f"{label} / {inner}"
