"""Dry-run integration: one small cell must lower+compile on both meshes
(subprocess: the dry-run owns its 512 forced host devices)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_dryrun(extra, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun"] + extra,
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=timeout)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc.stdout


def test_single_and_multipod_cell(tmp_path):
    out = run_dryrun(["--arch", "mamba2-2.7b", "--shape", "long_500k",
                      "--both-meshes", "--out-dir", str(tmp_path)])
    assert "dry-run OK" in out
    assert "CompiledMemoryStats" in out          # memory_analysis printed
    assert "flops" in out                        # cost_analysis printed
    files = sorted(os.listdir(tmp_path))
    assert any("_sp" in f for f in files) and any("_mp" in f for f in files)
    d = json.load(open(tmp_path / [f for f in files if "_sp" in f][0]))
    assert d["terms_seconds"]["compute"] >= 0
    assert d["dominant"] in ("compute", "memory", "collective")
    assert d["collective"]["counts"]["all-reduce"] >= 0


def test_registry_cell_accounting():
    from repro.configs.registry import valid_cells, cell_valid
    cells = valid_cells()
    assert len(cells) == 31                      # DESIGN.md §6 accounting
    ok, why = cell_valid("hubert-xlarge", "decode_32k")
    assert not ok and "encoder" in why
    ok, why = cell_valid("qwen2-72b", "long_500k")
    assert not ok and "sub-quadratic" in why
    ok, _ = cell_valid("jamba-1.5-large-398b", "long_500k")
    assert ok


def test_roofline_peaks_keyed_by_device_kind():
    from repro.launch.roofline import peaks
    assert peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(ValueError, match="no roofline peaks"):
        peaks("cpu")
