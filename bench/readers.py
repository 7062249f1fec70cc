"""What the per-layer metric readers (``bench/metrics/*.py``) share.

Each reader returns ``None`` where its run holds nothing to read, and the
metric is then left out of the result line.
"""
from __future__ import annotations

from bench.roofline import segreduce_bytes, segreduce_shape


def idle_share_pct(run) -> float | None:
    """Share of the traced window in which no op ran on the device, %."""
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share


def device_ms_per_call(run, category: str) -> float | None:
    """Device milliseconds per completed call in one ``ops.json``
    category, averaged over the devices."""
    if run.trace is None or not run.calls:
        return None
    s = run.trace.category_s.get(category)
    if not s:
        return None
    return 1e3 * s / run.trace.devices / run.calls


def planned_slots(events: list, op: str = "spgemm") -> int:
    """Product slots per device and stage that the planner allocated over
    every attempt of every ``op`` call, from the ``plan.*`` events: the
    adopted plan, each overflowed attempt, and the plan a retry ended on."""
    total, cur = 0, None
    for e in events:
        name = e["name"]
        if name == f"plan.{op}":
            total += cur or 0
            cur = e["prod_cap"]
        elif name == "plan.overflow_retry" and e.get("op") == op:
            total += e["prod_cap"]
        elif name == f"plan.{op}.done":
            cur = e["prod_cap"]
    return total + (cur or 0)


def slot_use_pct(run) -> float | None:
    """Useful multiplications over the product slots allocated, %, over
    the calls of the obs pass: a stage's slots on each device, times the
    stages and the devices."""
    if run.obs is None:
        return None
    mults = run.obs.counts.get("mults")
    pr, pc = run.cell.config["grid"]
    slots = planned_slots(run.obs.events) * pr * pr * pc
    if not mults or not slots:
        return None
    return 100.0 * mults / slots


def comm_bytes_per_call(run) -> float | None:
    """Live payload bytes the program counts at its guarded exchange
    boundaries (``comm.bytes.<site>``), per call of the obs pass."""
    if run.obs is None or not run.obs.calls:
        return None
    total = sum(v for k, v in run.obs.counters.items()
                if k.startswith("comm.bytes."))
    return total / run.obs.calls if total else None


def segreduce_roofline_pct(run) -> float | None:
    """Share of the HBM roofline the Pallas segmented reduce reached: the
    bytes it needs over its device time and the chip's HBM bandwidth, %.
    The kernel moves bytes and does next to no arithmetic, so bandwidth
    bounds it."""
    if run.trace is None:
        return None
    ops = [o for o in run.trace.ops if o.category == "pallas_segreduce"]
    if not ops:
        return None
    need = sum(segreduce_bytes(*segreduce_shape(o.name)) for o in ops)
    secs = sum(o.dur_ns for o in ops) * 1e-9
    return 100.0 * need / (secs * run.peak_bw)


def cache_reads_per_call(run) -> float | None:
    """Programs read from JAX's persistent cache per completed call: the
    program re-traces its shard_map programs on every call, and each
    re-traced program is looked up in the cache again."""
    if not run.calls:
        return None
    return run.programs["cache_hits"] / run.calls
