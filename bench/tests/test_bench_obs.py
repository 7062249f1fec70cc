"""A traced run drives its window as an untraced one does, with the
program's flight recorder (``repro.obs``) off, and reads the recorder's
events from an obs pass after the window, only where a metric needs it."""
from __future__ import annotations

import jax
import pytest

from bench import cells
from bench import run as bench_run
from bench.tests.small import no_profiler, small_cell

SEED = 4_000_000_007
CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]
         if w["chips"] == 1]


@pytest.mark.parametrize("name", CELLS)
def test_window_runs_without_obs(name):
    from repro import obs
    cell = small_cell(name)
    seen = []

    def wrap(call):
        def watched(item):
            seen.append(obs.enabled())
            return call(item)
        return watched

    with no_profiler():
        res = bench_run.run(cell, SEED, 0.3, True, jax.devices()[:1],
                            cache=False, wrap=wrap, say=lambda s: None)
    assert res["correct"], res
    needs = any(getattr(cells.metric_reader(m["name"]), "OBS", False)
                for m in cell.per_layer)
    # the warm-up call, the window's calls, then the obs pass, if any
    n_obs = sum(seen)
    assert seen == [False] * (len(seen) - n_obs) + [True] * n_obs
    assert (n_obs > 0) == needs
    assert res["attempted"] == len(seen) - 1
    assert not obs.enabled()
    for m in cell.per_layer:
        if m["name"].startswith("plan.slot_use."):
            assert 0 < res["metrics"][m["name"]]["value"] <= 100
