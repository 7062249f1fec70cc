"""``bench/run.py`` refuses, with no result line, wherever it cannot
measure: no TPU, an unknown device kind, too few chips, or a checkout
without the program."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench import cells
from bench import run as bench_run

ROOT = cells.ROOT
CELL = cells.load_benchmark()["workloads"][0]["name"]
ARGS = ["--workload", CELL, "--seed", "4294967311", "--seconds", "1",
        "--trace", "0"]


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          capture_output=True, text=True, env=env,
                          timeout=300)


def test_exits_nonzero_without_a_tpu():
    proc = _cli(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _cli(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "the program is not in this checkout" in proc.stderr


def _fake(monkeypatch, kind, count):
    import jax
    dev = SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * count)


def test_unknown_device_kind_is_refused(monkeypatch):
    _fake(monkeypatch, "TPU v99", 4)
    cell = cells.resolve(cells.load_benchmark(), CELL)
    with pytest.raises(bench_run.Refused, match="no peaks"):
        bench_run.devices_for(cell)


def test_too_few_chips_are_refused(monkeypatch):
    _fake(monkeypatch, "TPU v5 lite", 1)
    cell = cells.resolve(cells.load_benchmark(), CELL)
    cell = cells.Cell(cell.name, 4, cell.config, cell.traffic,
                      cell.end_to_end, cell.per_layer)
    with pytest.raises(bench_run.Refused, match="needs 4 chips"):
        bench_run.devices_for(cell)


def test_a_known_chip_is_taken(monkeypatch):
    _fake(monkeypatch, "TPU v5 lite", 4)
    cell = cells.resolve(cells.load_benchmark(), CELL)
    assert len(bench_run.devices_for(cell)) == cell.chips


def test_result_line_is_json_with_the_contract_keys():
    """The result object's keys, in order, with ``checks`` last."""
    import jax
    from bench.tests.small import small_cell
    res = bench_run.run(small_cell("tc.urand15"), 7, 0.2, False,
                        jax.devices()[:1], cache=False, say=lambda s: None)
    line = json.loads(json.dumps(res))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
