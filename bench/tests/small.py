"""Cells of ``BENCHMARK.json`` cut to a size that a CPU test run holds."""
from __future__ import annotations

import contextlib
import dataclasses

from bench import cells

SCALE = 8


def small_cell(name: str, scale: int = SCALE) -> cells.Cell:
    """The cell ``name`` at R-MAT scale ``scale``, with at most 4 BFS
    search keys; the graph sizes its tiles itself (``graph.tile_cap``)."""
    cell = cells.resolve(cells.load_benchmark(), name)
    traffic = dict(cell.traffic)
    if "search_keys" in traffic:
        traffic["search_keys"] = 4
    return dataclasses.replace(cell, config=dict(cell.config, scale=scale),
                               traffic=traffic)


@contextlib.contextmanager
def no_profiler(busy_s: float = 0.5, window_s: float = 1.0):
    """``--trace 1`` runs on the CPU, which has neither a device trace
    nor peaks: starting and stopping the profiler does nothing, the trace
    reduces to a window ``busy_s`` of ``window_s`` busy, and the peaks
    are ones."""
    import jax

    from bench import run, xplane
    fake = xplane.Reduced(1, window_s, busy_s, [], {}, [], [])
    saved = (jax.profiler.start_trace, jax.profiler.stop_trace,
             xplane.find_trace, xplane.reduce, run.peaks)
    jax.profiler.start_trace = lambda *a, **k: None
    jax.profiler.stop_trace = lambda: None
    xplane.find_trace = lambda log_dir: log_dir
    xplane.reduce = lambda path, window: fake
    run.peaks = lambda kind: {"flops": 1.0, "hbm_bw": 1.0}
    try:
        yield fake
    finally:
        (jax.profiler.start_trace, jax.profiler.stop_trace,
         xplane.find_trace, xplane.reduce, run.peaks) = saved
