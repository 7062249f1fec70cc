"""Bytes a kernel needs, from its shapes: the numerators of roofline shares."""
from __future__ import annotations

import re

ITEMSIZE = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
            "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
            "pred": 1}
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9]+)\]")


def segreduce_bytes(n: int, segments: int, itemsize: int) -> int:
    """HBM bytes the segmented reduce needs: n int32 ids and n values in,
    one value per segment out. The kernel's touch counts are its own
    bookkeeping and not counted."""
    return n * (4 + itemsize) + segments * itemsize


def segreduce_shape(hlo: str) -> tuple[int, int, int]:
    """(n, segments, itemsize) of a Pallas segmented-reduce op, from its
    HLO instruction text ``%x = (vals[s], s32[s]) custom-call(s32[n] ids,
    vals[n] values), ...``."""
    head, args = hlo.split(" custom-call(", 1)
    out = _SHAPE.findall(head.split(" = ", 1)[1])
    ins = _SHAPE.findall(args)
    (vdt, s), (_, n) = out[0], ins[0]
    return int(n), int(s), ITEMSIZE[vdt]
