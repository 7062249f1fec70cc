"""The 2x2 cell on four CPU devices: a sound run and a traced one are
correct, and ``correct`` comes out false with the answer altered, half of
it left out, the exchange between chips left out, or the control in the
program's place. One subprocess (``bench/tests/grid_runs.py``, four
forced host devices) runs every case; each test reads one."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import cells
from bench.tests import grid_runs


@pytest.fixture(scope="module")
def results():
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(cells.ROOT), str(cells.ROOT / "src"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-m", "bench.tests.grid_runs"],
                          cwd=cells.ROOT, capture_output=True, text=True,
                          env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    return {ln["case"]: ln["result"] for ln in lines}


def test_sound_run_is_correct(results):
    res = results["sound"]
    assert res["correct"], res
    assert res["device"]["count"] == 4
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert res["metrics"]["spgemm_flop_rate"]["value"] > 0


def test_traced_run_reads_the_exchange_from_its_obs_pass(results):
    res = results["traced"]
    assert res["correct"], res
    m = res["metrics"]
    assert m["exchange.comm_bytes.spgemm"]["value"] > 0
    assert 0 < m["plan.slot_use.spgemm"]["value"] <= 100


@pytest.mark.parametrize("case", ["altered", "halved", "no_exchange",
                                  "control"])
def test_broken_timed_path_is_not_correct(results, case):
    res = results[case]
    assert res["correct"] is False, res
    assert res["failed"] == 0
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_every_case_ran(results):
    assert set(results) == set(grid_runs.CASES)
