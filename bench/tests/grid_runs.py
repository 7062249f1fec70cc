"""Runs of the 2x2 cell (``spgemm.kron16-2x2``) on four CPU devices.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python -m bench.tests.grid_runs

Prints one JSON line per case with the run's result: a sound run, a
traced one, and the faults of ``bench/tests/faults.py``, the exchange
between chips left out among them. ``test_bench_grid.py`` starts it once
and reads each case.
"""
from __future__ import annotations

import dataclasses
import json

import jax

from bench import run as bench_run
from bench.tests import faults
from bench.tests.small import no_profiler, small_cell

CELL = "spgemm.kron16-2x2"
SEED = 2_718_281_828
# case: (wrap, R-MAT scale, traced); the control's values pass 256, where
# bfloat16 first rounds them, from scale 13 on
CASES = {
    "sound": (None, 9, False),
    "traced": (None, 9, True),
    "altered": (faults.altered, 9, False),
    "halved": (faults.halved, 9, False),
    "no_exchange": (faults.no_exchange, 9, False),
    "control": (faults.control, 13, False),
}


def cell_at(scale: int):
    cell = small_cell(CELL, scale)
    # a small graph's slab tiles are less even than the cell's own
    return dataclasses.replace(
        cell, traffic=dict(cell.traffic, slab_cap_pad=2.0))


def main() -> None:
    devices = jax.devices()[:4]
    assert len(devices) == 4, devices
    for name, (wrap, scale, traced) in CASES.items():
        with no_profiler():
            res = bench_run.run(cell_at(scale), SEED, 0.3, traced, devices,
                                cache=False, wrap=wrap, say=lambda s: None)
        print(json.dumps({"case": name, "result": res}), flush=True)


if __name__ == "__main__":
    main()
