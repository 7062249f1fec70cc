"""Roofline-term extraction from compiled SPMD executables (DESIGN.md §7).

The compiled module is per-device (post-GSPMD partitioning), so
``cost_analysis()`` FLOPs/bytes are PER-DEVICE numbers. Collective bytes
come from walking the compiled HLO text and converting each collective's
result shape into wire bytes per device:

  all-gather        recv = result × (g-1)/g
  reduce-scatter    send = result × (g-1)          (input = result × g)
  all-reduce        2 × result × (g-1)/g           (ring reduce+broadcast)
  all-to-all        result × (g-1)/g
  collective-permute result                        (one neighbor hop)

with g = participants (parsed from replica_groups). The collective term
divides by ONE ICI link (50 GB/s): a deliberately conservative single-link
serialization model — multi-link overlap is credited in §Perf only when
the schedule provably uses disjoint axes. The peaks come from ``PEAKS``,
keyed by the ``device_kind`` jax reports; a kind that is not in the table
is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import json
import re

# Per-chip peaks by device_kind. TPU v5e ("TPU v5 lite"): 197 TFLOP/s bf16
# and 819 GB/s HBM (Google Cloud documentation, "TPU v5e"); the collective
# term uses one 50 GB/s ICI link (the serialization model above).
PEAKS = {"TPU v5 lite": dict(flops=197e12, hbm_bw=819e9, link_bw=50e9)}


def peaks(device_kind: str) -> dict:
    """The ``PEAKS`` entry for ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no roofline peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^\s]*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]*)\}")
_GROUPS2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device wire bytes by collective kind + op counts."""
    out = {k: 0.0 for k in ("all-gather", "all-reduce", "reduce-scatter",
                            "all-to-all", "collective-permute")}
    counts = {k: 0 for k in out}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done(" in line:
            continue                      # count the -start only
        result_type, kind = m.group(1), m.group(2)
        size = _shape_bytes(result_type)
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            g = len([t for t in gm.group(1).split(",") if t])
        else:
            gm2 = _GROUPS2_RE.search(line)
            if gm2:
                g = int(gm2.group(2))
        g = max(g, 2)
        if kind == "all-gather":
            wire = size * (g - 1) / g
        elif kind == "reduce-scatter":
            wire = size * (g - 1)
        elif kind == "all-reduce":
            wire = 2 * size * (g - 1) / g
        elif kind == "all-to-all":
            wire = size * (g - 1) / g
        else:                             # collective-permute
            wire = size
        out[kind] += wire
        counts[kind] += 1
    return dict(bytes=out, counts=counts,
                total=float(sum(out.values())))


def cost_dict(compiled) -> dict:
    """compiled.cost_analysis() as a dict (empty when XLA reports none)."""
    return compiled.cost_analysis() or {}


def raw_metrics(compiled) -> dict:
    """Per-device flops/bytes/collective-wire-bytes of one executable."""
    ca = cost_dict(compiled)
    coll = collective_bytes(compiled.as_text())
    return dict(flops=float(ca.get("flops", 0.0)),
                bytes=float(ca.get("bytes accessed", 0.0)),
                coll=coll)


def extrapolate_raw(r1: dict, r2: dict, reps: int) -> dict:
    """Linear depth extrapolation: total = probe1 + (probe2-probe1)·(reps-1).

    XLA's cost analysis counts while-loop (lax.scan) bodies ONCE, so a
    scanned L-layer model reports ~1-layer flops. We therefore lower two
    UNROLLED shallow probes (depth = 1 and 2 periods); their difference is
    the exact per-period cost (fwd+bwd+remat+optimizer slice), and
    everything outside the stack (embedding, logits, loss) is the probe-1
    intercept. Exact for costs linear in depth — which all stacked-layer
    costs are.
    """
    out = dict(flops=r1["flops"] + (r2["flops"] - r1["flops"]) * (reps - 1),
               bytes=r1["bytes"] + (r2["bytes"] - r1["bytes"]) * (reps - 1))
    coll_b = {}
    for k in r1["coll"]["bytes"]:
        b1, b2 = r1["coll"]["bytes"][k], r2["coll"]["bytes"][k]
        coll_b[k] = b1 + (b2 - b1) * (reps - 1)
    counts = {}
    for k in r1["coll"]["counts"]:
        c1, c2 = r1["coll"]["counts"][k], r2["coll"]["counts"][k]
        counts[k] = int(c1 + (c2 - c1) * (reps - 1))
    out["coll"] = dict(bytes=coll_b, counts=counts,
                       total=float(sum(coll_b.values())))
    return out


def terms_from_raw(raw: dict, *, n_devices: int, model_flops: float,
                   device_kind: str, memory_stats=None) -> dict:
    pk = peaks(device_kind)
    flops, bytes_accessed = raw["flops"], raw["bytes"]
    t_compute = flops / pk["flops"]
    t_memory = bytes_accessed / pk["hbm_bw"]
    t_coll = raw["coll"]["total"] / pk["link_bw"]
    terms = dict(compute=t_compute, memory=t_memory, collective=t_coll)
    bound = max(t_compute, t_memory, t_coll)
    result = dict(
        device_kind=device_kind,
        per_device_flops=flops,
        per_device_bytes=bytes_accessed,
        collective=raw["coll"],
        terms_seconds=terms,
        dominant=max(terms, key=terms.get),
        step_time_lower_bound_s=bound,
        model_flops_global=model_flops,
        hlo_flops_global=flops * n_devices,
        useful_flops_ratio=(model_flops / (flops * n_devices))
        if flops and model_flops else None,
        roofline_fraction=(model_flops / n_devices / pk["flops"]) / bound
        if bound and model_flops else None)
    if memory_stats is not None:
        ma = memory_stats
        result["memory_per_device"] = dict(
            args=ma.argument_size_in_bytes, out=ma.output_size_in_bytes,
            temp=ma.temp_size_in_bytes, alias=ma.alias_size_in_bytes,
            total_transient=ma.argument_size_in_bytes +
            ma.output_size_in_bytes + ma.temp_size_in_bytes -
            ma.alias_size_in_bytes)
    return result


def roofline_terms(compiled, *, n_devices: int, device_kind: str,
                   model_flops: float = 0.0):
    """Compute the three roofline terms from a compiled executable."""
    return terms_from_raw(raw_metrics(compiled), n_devices=n_devices,
                          model_flops=model_flops, device_kind=device_kind,
                          memory_stats=compiled.memory_analysis())
