"""CPU rehearsal of chip_smoke.py: every one-chip phase at R-MAT scale 8
on a 1x1 grid, and the --four-chips phases on a 2x2 grid of four forced
host devices, checked against the same host references the chip run uses."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro import obs  # noqa: E402
from repro.core import make_grid  # noqa: E402

SCALE = 8


@pytest.fixture(scope="module")
def graph():
    mesh = make_grid(1, 1)
    return chip_smoke.build_graph(SCALE, (1, 1), mesh), mesh


@pytest.mark.parametrize("name,fn", chip_smoke.ONE_CHIP_PHASES,
                         ids=[n for n, _ in chip_smoke.ONE_CHIP_PHASES])
def test_phase_matches_reference(graph, name, fn):
    g, mesh = graph
    lines = []
    with obs.capture():
        chip_smoke.run_phase(name, fn, g, mesh, say=lines.append)
    text = "\n".join(lines)
    assert "(includes compilation)" in text and "(not a benchmark)" in text
    assert "warm call not run" not in text
    assert "plan.degraded ()" in text
    assert "ladder.rungs=0 audit.failures=0 deadline.trips=0" in text


def test_four_chip_phases_on_forced_host_devices():
    """``run_four_chips`` on a 2x2 grid of 4 CPU devices, at scale 9 for
    A·A and 8 for the triangle count: tiles on 4 distinct devices, results
    equal to the host reference, no fault counted."""
    code = ("import chip_smoke as cs\n"
            "from repro import obs\n"
            "from repro.core import make_grid\n"
            "with obs.capture():\n"
            "    cs.run_four_chips(make_grid(2, 2),\n"
            "                      scales={'spgemm': 9, 'tricount': 8})\n")
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = proc.stdout
    assert out.count("tiles on devices: [0, 1, 2, 3]") == 2, out
    assert "phase spgemm: nnz" in out and "indices and values exact" in out
    assert "phase tricount:" in out and "triangles, exact" in out
    assert out.count("plan.degraded ()") == 2
    assert out.count("ladder.rungs=0 audit.failures=0 deadline.trips=0") == 2


def test_refuses_without_tpu():
    """On the CPU the script exits non-zero and prints no ok line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins untouched; else <checkout>/.jax_cache."""
    import jax
    from repro.launch.cache import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert use_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
