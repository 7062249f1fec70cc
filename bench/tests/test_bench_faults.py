"""``correct`` comes out false when the timed path is broken underneath,
and when the control (the reference one step below the stated precision
or guarantee) takes the program's place.

Each run goes through ``bench.run.run`` on the CPU at scale 8 (13 for
the A·A control): the whole
run but the look for a chip, with the timed call wrapped. The faults a
graph cell can have are an answer altered where it is produced and half
of the answer left out; these cells run on one chip, so no exchange
between chips can be left out, and they keep no state between calls
(the 2x2 cell's faults, that one among them, are in test_bench_grid.py).
"""
from __future__ import annotations

import jax
import pytest

from bench import run as bench_run
from bench.tests import faults
from bench.tests.small import small_cell

SEED = 2_718_281_828


def _run(name, wrap, scale=8):
    return bench_run.run(small_cell(name, scale), SEED, 0.3, False,
                         jax.devices()[:1], cache=False, wrap=wrap,
                         say=lambda s: None)


CASES = [(cell, kind) for cell in ("spgemm.kron15", "bfs.kron15",
                                   "tc.urand15")
         for kind in ("altered", "halved", "control")]


@pytest.mark.parametrize("cell,kind", CASES,
                         ids=[f"{c}-{k}" for c, k in CASES])
def test_broken_timed_path_is_not_correct(cell, kind):
    wrap = getattr(faults, kind)
    # A·A's values pass 256, where bfloat16 first rounds them, from R-MAT
    # scale 13 on, in every slab (at the cell's scale 15: 488 or more)
    scale = 13 if (cell, kind) == ("spgemm.kron15", "control") else 8
    res = _run(cell, wrap, scale)
    assert res["correct"] is False, res
    assert res["failed"] == 0
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_a_call_that_raises_is_failed():
    def wrap(call):
        def broken(item):
            raise RuntimeError("device lost")
        return broken
    res = _run("tc.urand15", wrap)
    assert res["correct"] is False and res["failed"] == res["attempted"]


def test_a_degraded_plan_is_failed():
    import warnings

    def wrap(call):
        def degraded(item):
            warnings.warn("robust: degrading SpGEMM to the serial schedule",
                          RuntimeWarning)
            return call(item)
        return degraded
    res = _run("tc.urand15", wrap)
    assert res["correct"] is False and res["failed"] == res["attempted"]
