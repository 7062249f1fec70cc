"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Distributed benchmarks run in
subprocesses on forced host CPU devices (``JAX_PLATFORMS=cpu``; their rows
carry ``device=cpu``); everything else runs on the single real device.
``--full`` widens the sweeps.

``--json`` additionally writes the perf-trajectory artifacts (repo root):
``BENCH_spgemm.json`` from the spgemm_local rows, ``BENCH_dist.json``
from the distributed rows (the §4.8 sweep + evolution + scaling) and
``BENCH_robust.json`` from the elastic-recovery rows (time-to-detect,
regrid, checkpoint, steps-lost), each as benchmark rows plus every
``*_speedup*``/``*_ratio`` key, so future PRs can diff perf trajectories.
Subsets that would silently omit an artifact are rejected: with
``--only``, ``--json`` requires ``spgemm_local``, ``dist`` and ``robust``
in the subset, and a failed dist subprocess is a hard error rather than a
skipped artifact. CI's bench-smoke job runs ``REPRO_DEVICES=8 python -m
benchmarks.run --only spgemm_local,dist,robust --json`` from the repo
root — the ``-m`` form is required so the ``benchmarks`` package
resolves.

  robust          §8      elastic recovery: detect/regrid/ckpt/steps-lost
  spmspv_sweep    Fig 3   SpMSpV/SpMV variant selection vs sparsity
  spgemm_local    §4.1    hash↔dense vs heap↔ESC crossover
  dist(evolution) Fig 5/6 2D SUMMA variants vs 3D CA (time + coll bytes)
  dist(scaling)   Fig 7   CA collective bytes vs p (AOT)
  apps            Fig 8/9/10  FastSV / HipMCL breakdown / PageRank / BFS
  io              Table 5 ASCII vs binary vs label-format parallel I/O
  kernels         §5      kernel-path microbenches (oracle timings)
  roofline        §Roofline  aggregated dry-run cells (if present)
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ROOT = os.path.join(os.path.dirname(__file__), "..")


def emit(rows):
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


def write_bench_json(rows, path=None, trace_summary=None):
    """Trajectory artifact: µs per benchmark + every speedup/ratio key.

    ``trace_summary`` (obs.snapshot() or a dict of them) is embedded
    verbatim so each BENCH_*.json carries its flight-recorder view —
    per-site span stats, counter totals, deadline windows."""
    path = path or os.path.join(ROOT, "BENCH_spgemm.json")
    doc = {
        "benchmarks": {name: {"us": round(us, 1), "derived": derived}
                       for name, us, derived in rows},
        "speedups": {name: round(us, 3) for name, us, _ in rows
                     if "speedup" in name or "ratio" in name},
    }
    if trace_summary is not None:
        doc["trace_summary"] = trace_summary
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(f"# wrote {os.path.relpath(path)}", file=sys.stderr)
    return doc


def run_dist(which: str, devices: int | None = None):
    """Run one dist_bench mode in a forced-device subprocess.

    Returns ``(rows, trace_summary)`` — the parsed ``(name, us, derived)``
    rows plus the child's flight-recorder snapshot (from its
    ``# trace_summary=`` stdout line; None when the child recorded
    nothing) — or ``(None, None)`` on failure (the caller decides whether
    that is fatal — it is under ``--json``). The child records with
    ``REPRO_OBS=1``; a parent ``REPRO_TRACE=<p>`` is rewritten to
    ``<p-base>.dist_<which>.json`` so each subprocess writes its own
    Chrome trace instead of clobbering the parent's.
    """
    if devices is None:
        devices = int(os.environ.get("REPRO_DEVICES", "16"))
    # the children run on forced host devices: pin them to the CPU so they
    # never contend for an accelerator this process may hold
    env = dict(os.environ, REPRO_DEVICES=str(devices), REPRO_OBS="1",
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    trace = os.environ.get("REPRO_TRACE")
    if trace:
        base, ext = os.path.splitext(trace)
        env["REPRO_TRACE"] = f"{base}.dist_{which}{ext or '.json'}"
    script = os.path.join(os.path.dirname(__file__), "dist_bench.py")
    proc = subprocess.run([sys.executable, script, which],
                          capture_output=True, text=True, env=env,
                          timeout=3600)
    if proc.returncode != 0:
        print(f"dist_bench_{which},0.0,FAILED", flush=True)
        sys.stderr.write(proc.stderr[-2000:])
        return None, None
    rows, summary = [], None
    for line in proc.stdout.strip().splitlines():
        if line.startswith("# trace_summary="):
            try:
                summary = json.loads(line.split("=", 1)[1])
            except ValueError:
                summary = None
            continue
        if not line or line.startswith("#"):
            print(line)
            continue
        name, us, derived = line.split(",", 2)
        rows.append((name, float(us), f"{derived};device=cpu"))
        print(f"{name},{us},{rows[-1][2]}")
    return rows, summary


def kernels_bench(quick=True):
    import time
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    rows = []
    rng = np.random.default_rng(0)

    def t(fn, *args, reps=3):
        jfn = jax.jit(fn)
        jax.block_until_ready(jfn(*args))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = jfn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps * 1e6

    a = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    for kind in ("plus_times", "min_plus", "max_min"):
        rows.append((f"kernel_semiring_{kind}_ref256",
                     t(lambda x, y, k=kind: ref.semiring_matmul(x, y, k),
                       a, a), "oracle-on-CPU"))
    B, S, H, d = 1, 512, 4, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, d)), jnp.float32)
    rows.append(("kernel_flash_attn_ref512",
                 t(lambda x: ref.flash_attention(x, x, x, True), q),
                 "oracle-on-CPU"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benches")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_spgemm.json (spgemm rows + speedups)")
    args, _ = ap.parse_known_args()
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None
    if args.json and only is not None and not {"spgemm_local", "dist",
                                               "robust"} <= only:
        # each artifact is built from its section's rows; silently writing
        # nothing (the old behavior) made perf-trajectory runs vacuous
        ap.error("--json writes BENCH_spgemm.json from the spgemm_local "
                 "rows, BENCH_dist.json from the dist rows and "
                 "BENCH_robust.json from the robust rows; include all "
                 "three in --only (or drop --only)")

    def want(name):
        return only is None or name in only

    obs = None
    if args.json:
        # flight recorder on for the in-process sections: each BENCH json
        # embeds its own section's snapshot (reset between sections)
        from repro import obs
        obs.enable()

    if want("spmspv"):
        from benchmarks import spmspv_sweep
        emit(spmspv_sweep.run(quick=quick))
    if want("spgemm_local"):
        from benchmarks import spgemm_local
        if obs:
            obs.reset()
        rows = spgemm_local.run(quick=quick)
        emit(rows)
        if args.json:
            write_bench_json(rows, trace_summary=obs.snapshot())
    if want("dist"):
        parts = [run_dist("sweep"), run_dist("evolution"),
                 run_dist("scaling")]
        if args.json:
            if any(rows is None for rows, _ in parts):
                raise SystemExit(
                    "dist benchmark subprocess failed — refusing to write "
                    "a partial BENCH_dist.json")
            summaries = {mode: s for mode, (_, s) in
                         zip(("sweep", "evolution", "scaling"), parts)
                         if s is not None}
            write_bench_json([r for rows, _ in parts for r in rows],
                             path=os.path.join(ROOT, "BENCH_dist.json"),
                             trace_summary=summaries or None)
    if want("robust"):
        from benchmarks import robust_bench
        if obs:
            obs.reset()
        rows = robust_bench.run(quick=quick)
        emit(rows)
        if args.json:
            write_bench_json(rows,
                             path=os.path.join(ROOT, "BENCH_robust.json"),
                             trace_summary=obs.snapshot())
    if want("apps"):
        from benchmarks import apps_bench
        emit(apps_bench.run(quick=quick))
    if want("io"):
        from benchmarks import io_bench
        emit(io_bench.run(quick=quick))
    if want("kernels"):
        emit(kernels_bench(quick=quick))
    if want("roofline"):
        from benchmarks import roofline_table
        emit(roofline_table.run(quick=quick))


if __name__ == "__main__":
    main()
